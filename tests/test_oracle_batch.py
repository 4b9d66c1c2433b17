"""The batched oracle (`oracle.evaluate_population`) against the
iterative one it replaces in the search's replay, and
`search._Recorder.record_batch` against `record` called member by
member: the same numbers, bit for bit, on every shipped spec."""
import numpy as np
import pytest

from repro.core.arch import GemminiHW
from repro.core.archspec import (EDGE_SPEC, GEMMINI_SPEC, HWConfig,
                                 TPU_V5E_SPEC, resolve_spec)
from repro.core.cosa import cosa_map_workload
from repro.core.hw_infer import minimal_hw_for, random_hw_for
from repro.core.mapping import (NORDERS, SPATIAL, TEMPORAL, random_mapping,
                                stack_mappings, unstack_mappings)
from repro.core.oracle import evaluate_population, evaluate_workload
from repro.core.problem import Layer, Workload
from repro.core.search import SearchConfig, _Recorder, dosa_search
from repro.obs import telemetry as obs
from repro.workloads.dnn_zoo import get_workload

# Each spec with a multi-layer workload holding a layer big enough to
# overflow its PE cap and (TPU v5e) its fixed VMEM, and a small fixed
# hardware point under which many mappings overflow.
SPECS = {
    "gemmini": (GEMMINI_SPEC, get_workload("resnet50").layers[:8],
                GemminiHW(pe_dim=16, acc_kb=32.0, sp_kb=256.0)),
    "tpu_v5e": (TPU_V5E_SPEC,
                (Layer.matmul(128, 4096, 65536, name="head"),
                 Layer.matmul(128, 14336, 4096, repeat=3, name="ffn"),
                 Layer.matmul(128, 4096, 1024, repeat=8, name="kv")),
                TPU_V5E_SPEC.default_hw),
    "edge3": (EDGE_SPEC, get_workload("bert").layers,
              HWConfig(pe_dim=16, cap_kb=(256.0,))),
}


def _all_in_backing(cspec, layer) -> np.ndarray:
    f = np.ones((2, cspec.n_levels, 7))
    f[TEMPORAL, cspec.backing] = layer.dims
    return f


def _planted(cspec, layers, hw):
    """(layer index, factors, reason) for one mapping per invalid reason
    `oracle.evaluate` has that this spec and hardware can hit."""
    out = []
    li = max(range(len(layers)), key=lambda i: np.prod(layers[i].dims))
    lay = layers[li]
    d = int(np.argmax(lay.dims))
    base = _all_in_backing(cspec, lay)

    f = base.copy()
    f[TEMPORAL, cspec.backing, d] *= 2
    out.append((li, f, "factor products != dims"))
    f = base.copy()
    f[TEMPORAL, 1, d] = 0.5
    f[TEMPORAL, cspec.backing, d] *= 2
    out.append((li, f, "factor < 1"))
    f = base.copy()
    f[TEMPORAL, 1, d] = 1.5
    f[TEMPORAL, cspec.backing, d] /= 1.5
    out.append((li, f, "non-integer factors"))

    reg = [k for k in range(7) if k not in cspec.spec.level0_temporal_dims
           and lay.dims[k] > 1][0]
    f = base.copy()
    f[TEMPORAL, 0, reg] = lay.dims[reg]
    f[TEMPORAL, cspec.backing, reg] = 1
    out.append((li, f, "unrealizable temporal factor at registers"))

    cap = cspec.spec.max_pe_dim if hw is None else hw.pe_dim
    pi, lvl, sd = next((i, lvl, sd) for i, layer in enumerate(layers)
                       for lvl, sd in cspec.spatial_sites
                       if layer.dims[sd] % (2 * cap) == 0)
    f = _all_in_backing(cspec, layers[pi])
    f[SPATIAL, lvl, sd] = 2 * cap
    f[TEMPORAL, cspec.backing, sd] /= 2 * cap
    out.append((pi, f, "PE array exceeds the spec cap" if hw is None
                else "PE array overflow"))

    # A level whose capacity binds: fixed silicon always, searched
    # levels under fixed hardware.  Whole tensors at the level below
    # the backing store overflow it.
    binds = (dict(cspec.fixed_capacity) if hw is None else
             set(cspec.searched_levels) | set(dict(cspec.fixed_capacity)))
    if cspec.backing - 1 in binds:
        bound = cspec.b_matrix[cspec.backing - 1]
        oi = max(range(len(layers)), key=lambda i: sum(
            w for w, b in zip(layers[i].tensor_sizes(), bound) if b))
        f = np.ones((2, cspec.n_levels, 7))
        f[TEMPORAL, cspec.backing - 1] = layers[oi].dims
        out.append((oi, f, f"{cspec.level_names[cspec.backing - 1]} "
                           "overflow"))
    return out


def _population(cspec, layers, hw, n, rng):
    """n members: random valid mappings and CoSA mappings (onto `hw` or
    random hardware), each with random loop orders."""
    fs, orders = [], []
    for b in range(n):
        if b % 2:
            hw_b = hw if hw is not None else random_hw_for(cspec, rng)
            ms = cosa_map_workload(list(layers), hw_b, spec=cspec)
        else:
            ms = [random_mapping(np.asarray(lay.dims), rng, spec=cspec)
                  for lay in layers]
        for m in ms:
            m.order = rng.integers(0, NORDERS, size=cspec.n_levels)
        f, o = stack_mappings(ms)
        fs.append(f)
        orders.append(o)
    return np.stack(fs), np.stack(orders)


@pytest.mark.parametrize("quantize", [True, False])
@pytest.mark.parametrize("fixed", [False, True])
@pytest.mark.parametrize("spec_name", sorted(SPECS))
def test_population_edps_equal_the_iterative_oracle(spec_name, fixed,
                                                    quantize):
    spec, layers, hw_fixed = SPECS[spec_name]
    cspec = resolve_spec(spec)
    hw = hw_fixed if fixed else None
    rng = np.random.default_rng(
        [sorted(SPECS).index(spec_name), fixed, quantize])
    # One planted member per reason, first: every other layer of it
    # held in the backing store (valid anywhere), so its reason shows.
    planted = _planted(cspec, layers, hw)
    n_planted = len(planted)
    reasons = [reason for _, _, reason in planted]
    held = np.stack([_all_in_backing(cspec, lay) for lay in layers])
    fs_p = np.repeat(held[None], n_planted, axis=0)
    for k, (li, f, _) in enumerate(planted):
        fs_p[k, li] = f
    fs, orders = _population(cspec, layers, hw, 16, rng)
    fs = np.concatenate([fs_p, fs])
    orders = np.concatenate([orders[:n_planted], orders])

    got, caps = evaluate_population(fs, orders, layers, hw=hw,
                                    quantize_dram=quantize, spec=spec)
    want, seen = [], []
    for b in range(len(fs)):
        edp, results = evaluate_workload(
            unstack_mappings(fs[b], orders[b]), layers, hw=hw,
            quantize_dram=quantize, spec=spec)
        want.append(edp)
        seen.append(results[-1].reason)
        if np.isfinite(edp):
            assert np.array_equal(caps[b],
                                  np.stack([r.caps for r in results]))
    want = np.array(want)
    assert got.dtype == np.float64
    assert np.array_equal(got, want)           # bit for bit, inf included
    assert seen[:n_planted] == reasons         # each plant hits its reason
    assert np.isinf(got[:n_planted]).all()
    assert np.isfinite(got).sum() >= 3         # and valid members remain


def _recorders(spec, layers, **kw):
    wl = Workload(layers=tuple(layers), name="w")
    cfg = SearchConfig(spec=spec, n_start_points=1, **kw)
    cspec = resolve_spec(spec)
    return (_Recorder(wl, cfg, cspec), _Recorder(wl, cfg, cspec), cspec)


def _assert_same_state(a: _Recorder, b: _Recorder):
    assert (a.evals, a.improved) == (b.evals, b.improved)
    ra, rb = a.best, b.best
    assert ra.best_edp == rb.best_edp
    assert ra.history == rb.history
    assert ra.best_hw == rb.best_hw
    assert len(ra.best_mappings) == len(rb.best_mappings)
    for ma, mb in zip(ra.best_mappings, rb.best_mappings):
        assert np.array_equal(ma.f, mb.f) and ma.f.dtype == mb.f.dtype
        assert np.array_equal(ma.order, mb.order)


@pytest.mark.parametrize("fixed", [False, True])
@pytest.mark.parametrize("spec_name", sorted(SPECS))
def test_record_batch_equals_record_member_by_member(spec_name, fixed):
    spec, layers, hw_fixed = SPECS[spec_name]
    kw = dict(fixed_hw=hw_fixed, fix_pe_only=False) if fixed else {}
    seq, bat, cspec = _recorders(spec, layers, **kw)
    assert bat.oracle_path == "batch"
    hw = hw_fixed if fixed else None
    rng = np.random.default_rng(7 + fixed)
    fs, orders = _population(cspec, layers, hw, 24, rng)
    edps, _ = evaluate_population(fs, orders, layers, hw=hw, spec=spec)
    finite = np.nonzero(np.isfinite(edps))[0]
    assert len(finite) >= 3
    # Batch 1: finite members from worst to best (each sets a new best),
    # then the best again (a tie: no improvement) and an invalid one.
    ranked = finite[np.argsort(-edps[finite], kind="stable")]
    invalid = np.nonzero(~np.isfinite(edps))[0]
    idx1 = list(ranked) + [ranked[-1]] + list(invalid[:1])
    # Batch 2: everything in random order, the best twice more.
    idx2 = list(rng.permutation(len(fs))) + [ranked[-1], ranked[-1]]

    counters = obs.get_metrics()
    c_batch = counters.counter("oracle_batch_candidates_total")
    for idx in (idx1, idx2):
        for k in idx:
            seq.record(unstack_mappings(fs[k], orders[k]))
        before = c_batch.total()
        bat.record_batch(fs[idx], orders[idx])
        assert c_batch.total() - before == len(idx)
        _assert_same_state(seq, bat)
        seq.count(5)
        bat.count(5)
    assert bat.improved == len(set(edps[ranked].tolist()))
    assert bat.improved >= 2
    if not fixed:       # best_hw from the batch's tile words, no walk
        assert bat.best.best_hw == minimal_hw_for(
            cspec, bat.best.best_mappings, list(layers))


def _latency_model(mappings, workload):
    return float(evaluate_workload(mappings, workload.layers)[0]) * 1.5


@pytest.mark.parametrize("kw", [
    dict(latency_model=_latency_model),
    dict(fixed_hw=GemminiHW(pe_dim=16, acc_kb=32.0, sp_kb=256.0),
         fix_pe_only=True),
], ids=["latency_model", "fix_pe_only"])
def test_configs_priced_per_candidate_take_the_scalar_path(kw):
    spec, layers, hw_fixed = SPECS["gemmini"]
    seq, bat, cspec = _recorders(spec, layers, **kw)
    assert bat.oracle_path == "scalar"
    fs, orders = _population(cspec, layers, hw_fixed, 8,
                             np.random.default_rng(3))
    m = obs.get_metrics()
    c_batch = m.counter("oracle_batch_candidates_total")
    c_scalar = m.counter("oracle_scalar_candidates_total")
    b0, s0 = c_batch.total(), c_scalar.total()
    bat.record_batch(fs, orders)
    assert (c_batch.total() - b0, c_scalar.total() - s0) == (0, len(fs))
    for f, o in zip(fs, orders):
        seq.record(unstack_mappings(f, o))
    _assert_same_state(seq, bat)

    # The direct fused driver names the path on its replay spans.
    wl = Workload(layers=tuple(layers[:2]), name="w2")
    cfg = SearchConfig(steps=4, round_every=2, n_start_points=2, seed=1,
                       **kw)
    tr = obs.Tracer()
    old = obs.set_tracer(tr)
    try:
        dosa_search(wl, cfg, population=2)
        dosa_search(wl, SearchConfig(steps=4, round_every=2,
                                     n_start_points=2, seed=1),
                    population=2)
    finally:
        obs.set_tracer(old)
    paths = [s.attrs["path"] for s in tr.spans_named("search.oracle")]
    assert paths == ["scalar"] * 2 + ["batch"] * 2
