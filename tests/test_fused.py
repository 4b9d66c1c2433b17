"""Device-resident fused search engine: the whole one-loop protocol
(GD segments + nearest-divisor rounding + ordering re-selection +
best-EDP tracking) compiled into ONE program per population chunk.

Covers: device rounding vs the numpy reference (property-fuzzed over
all three shipped specs), seeded fused-vs-host-batched `dosa_search`
equivalence (identical best_edp / n_evals / history), single-program
compilation (no per-segment dispatch), fused fleet equivalence, the
divisor tables, and the population best-tracking entry points."""
import hypothesis
import hypothesis.strategies as st
import numpy as np
import pytest

from repro.core import fleet as fleet_mod
from repro.core.archspec import (EDGE_SPEC, GEMMINI_SPEC, TPU_V5E_SPEC,
                                 compile_spec, padded_divisor_tables)
from repro.core.fleet import fleet_search
from repro.core.mapping import stack_mappings
from repro.core.problem import Layer, Workload, divisors
from repro.core.rounding import round_population, round_population_device
from repro.analysis import contracts
from repro.core.search import (SearchConfig, dosa_search, make_fused_runner)

ALL_SPECS = (GEMMINI_SPEC, TPU_V5E_SPEC, EDGE_SPEC)


@pytest.fixture(scope="module")
def two_layer_workload() -> Workload:
    return Workload(layers=(
        Layer.conv(64, 64, 3, 56, name="c1"),
        Layer.matmul(512, 1024, 768, name="m1"),
    ), name="two")


# ---------------------------------------------------------------------------
# Divisor tables
# ---------------------------------------------------------------------------

def test_padded_divisor_tables():
    dims = np.array([[3, 3, 28, 28, 64, 128, 2],
                     [1, 1, 512, 1, 768, 1024, 1]])
    divs, logs = padded_divisor_tables(dims)
    assert divs.shape == logs.shape and divs.shape[:2] == (2, 7)
    for li in range(2):
        for di in range(7):
            ds = divisors(int(dims[li, di]))
            row = divs[li, di]
            assert list(row[:len(ds)]) == ds          # ascending, complete
            assert (row[len(ds):] == 0).all()         # zero padding
            np.testing.assert_array_equal(
                logs[li, di, :len(ds)],
                np.log(np.asarray(ds, dtype=np.float64)).astype(np.float32))
    # cached: same dims -> same (read-only) table objects
    divs2, _ = padded_divisor_tables(dims.copy())
    assert divs2 is divs
    assert not divs.flags.writeable


# ---------------------------------------------------------------------------
# Device rounding == numpy reference (Sec. 5.3.2 projection)
# ---------------------------------------------------------------------------

_dim_vals = st.sampled_from([1, 2, 3, 5, 8, 12, 16, 56, 64, 100, 128, 3136])


@hypothesis.settings(max_examples=40, deadline=None)
@hypothesis.given(
    dims0=st.tuples(*[_dim_vals] * 7),
    dims1=st.tuples(*[_dim_vals] * 7),
    seed=st.integers(0, 2 ** 31 - 1),
    spec_i=st.integers(0, len(ALL_SPECS) - 1),
)
def test_round_population_device_matches_host(dims0, dims1, seed, spec_i):
    """Exact factor equality on every site for random continuous
    populations, random problem dims, every shipped spec; orders pass
    through rounding untouched on both paths."""
    spec = ALL_SPECS[spec_i]
    cspec = compile_spec(spec)
    rng = np.random.default_rng(seed)
    dims = np.asarray([dims0, dims1], dtype=np.int64)
    P, L, nl = 3, 2, cspec.n_levels
    fs = np.exp(rng.normal(0.0, 2.5, size=(P, L, 2, nl, 7))) \
        .astype(np.float32)
    orders = rng.integers(0, 3, size=(P, L, nl))
    ref = round_population(fs.astype(float), orders, dims, spec=cspec)
    ref_f = np.stack([stack_mappings(ms)[0] for ms in ref])
    ref_o = np.stack([stack_mappings(ms)[1] for ms in ref])
    dev_f = round_population_device(fs, dims, spec=cspec)
    np.testing.assert_array_equal(dev_f, ref_f)
    np.testing.assert_array_equal(ref_o, orders)       # orders preserved
    # every rounded mapping is a valid integer mapping of its dims
    assert np.array_equal(dev_f.prod(axis=(2, 3)),
                          np.broadcast_to(dims, (P, L, 7)).astype(float))


def test_round_population_device_respects_pe_cap():
    dims = np.array([[1, 1, 64, 1, 64, 256, 1]])
    fs = np.full((2, 1, 2, 4, 7), 200.0, dtype=np.float32)
    dev_f = round_population_device(fs, dims, pe_cap=16, spec=GEMMINI_SPEC)
    ref = round_population(fs.astype(float), np.zeros((2, 1, 4), np.int64),
                           dims, pe_cap=16, spec=GEMMINI_SPEC)
    ref_f = np.stack([stack_mappings(ms)[0] for ms in ref])
    np.testing.assert_array_equal(dev_f, ref_f)
    from repro.core.mapping import SPATIAL
    assert dev_f[:, :, SPATIAL].max() <= 16


# ---------------------------------------------------------------------------
# Fused engine == host-batched engine (seeded, on divisor grids)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", [None, TPU_V5E_SPEC, EDGE_SPEC])
def test_fused_matches_host_batched(two_layer_workload, spec):
    """The acceptance contract: identical best_edp, n_evals, start_edps
    and oracle history between the fused and host-batched engines."""
    cfg = SearchConfig(steps=50, round_every=20, n_start_points=2, seed=0,
                       spec=spec)
    host = dosa_search(two_layer_workload, cfg, population=2, fused=False)
    fus = dosa_search(two_layer_workload, cfg, population=2, fused=True)
    assert fus.best_edp == host.best_edp
    assert fus.n_evals == host.n_evals
    assert fus.start_edps == host.start_edps
    assert fus.history == host.history
    for mf, mh in zip(fus.best_mappings, host.best_mappings):
        np.testing.assert_array_equal(mf.f, mh.f)
        np.testing.assert_array_equal(mf.order, mh.order)


@pytest.mark.slow
@pytest.mark.parametrize("spec", [None, TPU_V5E_SPEC, EDGE_SPEC])
def test_fused_matches_host_batched_fig7_workload(spec):
    """Same contract on a fig7 workload (unet) for every shipped spec —
    the device rounding/ordering path sees the real layer mix."""
    from repro.workloads import dnn_zoo
    wl = dnn_zoo.get_workload("unet")
    cfg = SearchConfig(steps=60, round_every=25, n_start_points=2, seed=11,
                       spec=spec)
    host = dosa_search(wl, cfg, population=2, fused=False)
    fus = dosa_search(wl, cfg, population=2, fused=True)
    assert fus.best_edp == host.best_edp
    assert fus.n_evals == host.n_evals
    assert fus.history == host.history


def test_fused_chunks_and_ordering_none(two_layer_workload):
    """Chunked populations (P < n_start_points) and ordering_mode='none'
    run through the same fused scan."""
    cfg = SearchConfig(steps=40, round_every=20, n_start_points=3, seed=2,
                       ordering_mode="none")
    host = dosa_search(two_layer_workload, cfg, population=2, fused=False)
    fus = dosa_search(two_layer_workload, cfg, population=2, fused=True)
    assert fus.best_edp == host.best_edp
    assert fus.n_evals == host.n_evals


def test_fused_is_single_compiled_program(two_layer_workload):
    """No per-segment dispatch: a steps/round_every split into three
    segments (two full + remainder tail) compiles exactly ONE top-level
    program, and a repeat search stays warm (no retrace)."""
    cfg = SearchConfig(steps=50, round_every=20, n_start_points=2, seed=7)
    dosa_search(two_layer_workload, cfg, population=2, fused=True)
    run_fused, *_ = make_fused_runner(two_layer_workload, cfg)
    contracts.assert_no_recompile(run_fused)
    # a repeat search stays warm (still exactly one compiled program)
    contracts.assert_no_recompile(
        run_fused,
        calls=[lambda: dosa_search(two_layer_workload, cfg,
                                   population=2, fused=True)])


def test_ragged_final_chunk_does_not_recompile(two_layer_workload):
    """A ragged last chunk (n_start_points % population != 0) pads to
    the population shape with inert replicated members instead of
    compiling a second program — and the padding stays invisible to
    accounting (host-batched parity pins that)."""
    cfg = SearchConfig(steps=40, round_every=20, n_start_points=3, seed=2,
                       ordering_mode="none")
    host = dosa_search(two_layer_workload, cfg, population=2, fused=False)
    fus = dosa_search(two_layer_workload, cfg, population=2, fused=True)
    assert fus.best_edp == host.best_edp
    assert fus.n_evals == host.n_evals
    run_fused, *_ = make_fused_runner(two_layer_workload, cfg)
    contracts.assert_no_recompile(run_fused)


def test_fused_fixed_hw_mode(two_layer_workload):
    from repro.core.arch import GEMMINI_DEFAULT
    cfg = SearchConfig(steps=40, round_every=20, n_start_points=2, seed=1,
                       fixed_hw=GEMMINI_DEFAULT, fix_pe_only=True)
    host = dosa_search(two_layer_workload, cfg, population=2, fused=False)
    fus = dosa_search(two_layer_workload, cfg, population=2, fused=True)
    assert fus.best_edp == host.best_edp
    assert fus.n_evals == host.n_evals
    assert fus.best_hw.pe_dim == GEMMINI_DEFAULT.pe_dim


# ---------------------------------------------------------------------------
# Fused fleet == host-batched fleet
# ---------------------------------------------------------------------------

def test_fused_fleet_matches_host_batched_fleet():
    wl = Workload(layers=(Layer.matmul(256, 512, 384, name="m"),),
                  name="gemm")
    cfg = SearchConfig(steps=40, round_every=20, n_start_points=2, seed=3)
    host = fleet_search(wl, ALL_SPECS, cfg, fused=False)
    fus = fleet_search(wl, ALL_SPECS, cfg, fused=True)
    assert len(fus.entries) == len(host.entries)
    for h, f in zip(host.entries, fus.entries):
        assert (f.spec_name, f.workload) == (h.spec_name, h.workload)
        assert f.best_edp == h.best_edp
        assert f.n_evals == h.n_evals
        assert f.start_edps == h.start_edps


def test_fused_fleet_one_engine_per_group():
    """The fused fleet engine is cached per structural group: 3 specs ->
    2 groups -> 2 cached engines, same-group specs sharing one stacked
    device program."""
    wl = Workload(layers=(Layer.matmul(64, 64, 64),), name="m")
    cfg = SearchConfig(steps=20, round_every=10, n_start_points=1, seed=0)
    fleet_mod._FLEET_ENGINE_CACHE.clear()
    fleet_search(wl, ALL_SPECS, cfg, fused=True)
    assert len(fleet_mod._FLEET_ENGINE_CACHE) == 2


# ---------------------------------------------------------------------------
# Population best-tracking entry points (model.py)
# ---------------------------------------------------------------------------

def test_population_best_update():
    import jax.numpy as jnp

    from repro.core.model import (population_best_init,
                                  population_best_update)

    f0 = jnp.zeros((3, 2, 2, 4, 7))
    o0 = jnp.zeros((3, 2, 4), dtype=jnp.int32)
    best = population_best_init(f0, o0)
    assert bool(jnp.all(jnp.isinf(best.edp)))
    f1, o1 = f0 + 1.0, o0 + 1
    best = population_best_update(best, jnp.asarray([3.0, 5.0, 7.0]), f1, o1)
    f2, o2 = f0 + 2.0, o0 + 2
    best = population_best_update(best, jnp.asarray([4.0, 2.0, 7.0]), f2, o2)
    # member 0 keeps candidate 1, member 1 takes candidate 2, member 2
    # keeps the first (ties do not replace the incumbent)
    assert list(np.asarray(best.edp)) == [3.0, 2.0, 7.0]
    assert float(best.f[0, 0, 0, 0, 0]) == 1.0
    assert float(best.f[1, 0, 0, 0, 0]) == 2.0
    assert float(best.f[2, 0, 0, 0, 0]) == 1.0
    assert int(best.orders[1, 0, 0]) == 2


def test_fused_device_best_is_min_of_segments(two_layer_workload):
    """The scan-carried best tracker agrees with the elementwise min of
    the per-segment model EDPs it saw."""
    import jax.numpy as jnp

    from repro.core.search import (orders_from_population,
                                   generate_start_points,
                                   theta_from_population)

    cfg = SearchConfig(steps=40, round_every=20, n_start_points=2, seed=4)
    starts, _, _ = generate_start_points(two_layer_workload, cfg)
    run_fused, *_ = make_fused_runner(two_layer_workload, cfg)
    cspec = compile_spec(GEMMINI_SPEC)
    theta = jnp.asarray(theta_from_population(starts, cspec.free_mask),
                        dtype=jnp.float32)
    orders = jnp.asarray(orders_from_population(starts))
    (f_seg, o_seg, edps), best = run_fused(theta, orders, n_full=2, rem=0,
                                           seg_len=20)
    assert edps.shape == (2, 2) and f_seg.shape[0] == o_seg.shape[0] == 2
    np.testing.assert_allclose(np.asarray(best.edp),
                               np.asarray(edps).min(axis=0))


def test_fused_phases_are_named_scopes_that_change_no_numbers(
        two_layer_workload, monkeypatch):
    """The fused program's lowered HLO carries op_names under each of
    the phase scopes `gd`, `round`, `ordering` and `best`; without the
    scopes the lowered program (its locations aside) and the search's
    numbers are bit-identical."""
    import contextlib
    import re

    import jax
    import jax.numpy as jnp

    from repro.core import search
    cfg = SearchConfig(steps=40, round_every=20, n_start_points=2, seed=3)
    key = search._engine_key(two_layer_workload, cfg, "fused")
    pop, _, _ = search.generate_start_points(two_layer_workload, cfg)
    free_mask = search._cspec(cfg).free_mask

    def lowered_and_result():
        search._ENGINE_CACHE.discard(key)
        run_fused = make_fused_runner(two_layer_workload, cfg)[0]
        theta = jnp.asarray(search.theta_from_population(pop, free_mask),
                            dtype=jnp.float32)
        orders = jnp.asarray(search.orders_from_population(pop))
        low = run_fused.lower(theta, orders, n_full=2, rem=0, seg_len=20,
                              shards=1)
        res = dosa_search(two_layer_workload, cfg, population=2,
                          fused=True)
        return low, (res.best_edp, res.n_evals, res.history)

    scoped, got = lowered_and_result()
    names = re.findall(r'op_name="([^"]*)"',
                       scoped.as_text(dialect="hlo", debug_info=True))
    for scope in ("gd", "round", "ordering", "best"):
        assert any(scope in n.split("/") for n in names), scope
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope",
                  lambda name: contextlib.nullcontext())
        plain, want = lowered_and_result()
    search._ENGINE_CACHE.discard(key)
    # the StableHLO text leaves out locations, where the scopes live
    assert plain.as_text() == scoped.as_text()
    assert got == want
