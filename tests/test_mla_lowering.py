"""Latent attention, shared experts and the expert-parallel share:
`lm_extract`'s lowering of Kimi-K2 against the plain reference
(`workloads.lm_reference`) on seeded random weights, the served whole
decode step against the sequential driver, the benchmark's Kimi
configuration against `extract`, and every other architecture's
lowering pinned."""
import collections
import dataclasses
import hashlib
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import SearchRequest
from repro.configs import ARCH_IDS, get_config
from repro.configs.base import SHAPES, ShapeConfig, shape_applicable
from repro.configs.kimi_k2_1t import ROUTED_SCALING_FACTOR
from repro.core.archspec import TPU_V5E_SPEC, bucket_workload
from repro.core.search import SearchConfig, dosa_search
from repro.serve.cosearch_service import CoSearchService, ServiceConfig
from repro.workloads import lm_reference as ref
from repro.workloads.lm_extract import extract

ROOT = pathlib.Path(__file__).resolve().parents[1]
KIMI_BENCH = ROOT / "bench" / "configs" / "tpuv5e-kimik2-decode.json"

# Absorbed and un-absorbed latent attention are the same function; in
# float32 they differ only by the order of the sums, since W_UK is
# folded into the query and W_UV into the output.  The relative error
# of that reassociation is a few float32 epsilons (1.2e-7 each) times
# the growth over the contractions: 2.4e-7 on these shapes.  bfloat16
# (epsilon 7.8e-3) reads 6.5e-3.  1e-4 lies between them with room on
# both sides.
ABSORB_RTOL = 1e-4


def _rel(a, b) -> float:
    a, b = (np.asarray(t, np.float64) for t in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _small_mla():
    cfg = get_config("kimi_k2_1t", reduced=True)
    w = ref.init_mla(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 9, cfg.d_model))
    return cfg, w, x


@pytest.mark.parametrize("dtype,agrees", [(jnp.float32, True),
                                          (jnp.bfloat16, False)])
def test_absorbed_decode_equals_unabsorbed(dtype, agrees):
    """(a) The last position of un-absorbed causal attention equals one
    absorbed decode step against the latent cache of the positions
    before it;
    the bfloat16 control must miss the tolerance."""
    cfg, w, x = _small_mla()
    s = x.shape[1]
    with jax.default_matmul_precision("highest"):
        want = ref.mla_prefill(w, cfg, x)[:, -1]
        c, kr = ref.latent(w, cfg, x[:, :-1], jnp.arange(s - 1))
        w_, x_, c_, kr_ = jax.tree.map(lambda t: t.astype(dtype),
                                       (w, x, c, kr))
        got = ref.mla_decode_absorbed(w_, cfg, x_[:, -1], c_, kr_, s - 1)
    assert (_rel(got, want) <= ABSORB_RTOL) == agrees


# ------------------------------------------------ (b) GEMMs at full width

def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            if hasattr(j, "eqns"):
                yield j
            elif hasattr(j, "jaxpr") and hasattr(j.jaxpr, "eqns"):
                yield j.jaxpr


def _dots(fn, *args) -> collections.Counter:
    """Every dot_general of fn traced on abstract args, as (M, K, N) x
    count: M the lhs free size, K the contracted size, N the rhs free
    size, count the batch size."""
    out = collections.Counter()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
                ls = eqn.invars[0].aval.shape
                rs = eqn.invars[1].aval.shape

                def size(shape, keep):
                    return math.prod(shape[i] for i in keep)
                m = size(ls, [i for i in range(len(ls))
                              if i not in lc and i not in lb])
                n = size(rs, [i for i in range(len(rs))
                              if i not in rc and i not in rb])
                out[(m, size(ls, lc), n)] += size(ls, lb)
            for sub in _sub_jaxprs(eqn):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


def _gemms(wl) -> collections.Counter:
    out = collections.Counter()
    for lay in wl.layers:
        r, s, p, q, c, k, n = lay.dims
        assert (r, s, q, n) == (1, 1, 1, 1), lay
        out[(p, c, k)] += lay.repeat
    return out


def _full_width_weights(cfg):
    """Weight shapes at the published widths, without allocating."""
    mla = jax.eval_shape(lambda: ref.init_mla(jax.random.PRNGKey(0), cfg))
    ffn = jax.eval_shape(lambda: ref.init_mlp(jax.random.PRNGKey(0),
                                              cfg.d_model, cfg.dense_width))
    head = jax.ShapeDtypeStruct((cfg.d_model, cfg.vocab_size), jnp.float32)
    return mla, ffn, head


@pytest.mark.parametrize("batch,kv_len", [(24, 32768), (6, 131072)])
def test_decode_gemms_equal_absorbed_reference(batch, kv_len):
    """(b) The leading layer's decode step (absorbed latent attention,
    the dense FFN) and the LM head, traced at the published widths,
    run exactly the GEMMs `extract` emits for the one-layer model."""
    cfg = dataclasses.replace(get_config("kimi_k2_1t"), n_layers=1)
    mla, ffn, head = _full_width_weights(cfg)

    def step(mla, ffn, head, x, c, kr):
        h = ref.mla_decode_absorbed(mla, cfg, x, c, kr, kv_len - 1)
        return ref.swiglu(ffn, h) @ head

    f32 = jnp.float32
    args = (mla, ffn, head,
            jax.ShapeDtypeStruct((batch, cfg.d_model), f32),
            jax.ShapeDtypeStruct((batch, kv_len - 1, cfg.kv_lora_rank),
                                 f32),
            jax.ShapeDtypeStruct((batch, kv_len - 1, cfg.qk_rope_head_dim),
                                 f32))
    with jax.default_matmul_precision("highest"):
        got = _dots(step, *args)
    wl = extract(cfg, ShapeConfig("d", kv_len, batch, "decode"))
    assert got == _gemms(wl)
    # score and context: the 64 heads are the rows, once per sequence
    assert got[(64, 576, kv_len)] == batch
    assert got[(64, kv_len, 512)] == batch


def test_prefill_gemms_equal_unabsorbed_reference():
    """(b) The same for prefill against the un-absorbed form: `kv_b`
    up-projects the latent; score at qk width 192, context at v width
    128, per (sequence, head)."""
    cfg = dataclasses.replace(get_config("kimi_k2_1t"), n_layers=1)
    mla, ffn, head = _full_width_weights(cfg)
    batch, seq = 2, 4096

    def step(mla, ffn, head, x):
        h = ref.swiglu(ffn, ref.mla_prefill(mla, cfg, x))
        return h[:, -1] @ head

    x = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = _dots(step, mla, ffn, head, x)
    wl = extract(cfg, ShapeConfig("p", seq, batch, "prefill"))
    assert got == _gemms(wl)
    assert got[(seq, 192, seq)] == batch * cfg.n_heads


# ------------------------------------------------- (c) expert parallelism

@pytest.mark.parametrize("ep_chips", [2, 4, 8])
def test_expert_shares_sum_to_the_uncut_layer(ep_chips):
    """(c) Each of `ep_chips` chips computes its held experts on every
    chip's tokens; their outputs, with the shared expert counted once,
    sum to the uncut MoE layer.  The sums differ from it only in their
    order (3e-8 to 4e-8 here, under a float32 epsilon); leaving one
    share out is off by 0.2 or more."""
    cfg = get_config("kimi_k2_1t", reduced=True)
    w = ref.init_moe(jax.random.PRNGKey(2), cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (16, cfg.d_model))
    held = cfg.n_experts // ep_chips
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(w, cfg, x, ROUTED_SCALING_FACTOR)
        shares = [ref.moe(w, cfg, x, ROUTED_SCALING_FACTOR,
                          experts_held=(i * held, (i + 1) * held),
                          shared=i == 0)
                  for i in range(ep_chips)]
    assert _rel(sum(shares), whole) <= 1e-5
    assert _rel(sum(shares[:-1]), whole) > 1e-2


def test_ep_share_lowering():
    """The lowering of one chip's share: n_experts / ep_chips experts,
    each on the routed tokens of all chips' batches, the full router,
    and the shared expert on this chip's batch.  A depth cut is a
    pipeline's first stage: no LM head."""
    cfg = get_config("kimi_k2_1t")
    shape = ShapeConfig("d", 4096, 32, "decode")
    by_name = {lay.name: lay for lay in
               extract(cfg, shape, ep_chips=16, depth=3).layers}
    assert by_name["router"].dims[5] == 384
    assert by_name["router"].repeat == 2
    assert by_name["expert_up"].dims[2] == 32 * 16 * 8 // 384
    assert by_name["expert_up"].repeat == 2 * (384 // 16) * 2
    assert by_name["shared_up"].dims[2] == 32
    assert by_name["ffn_up"].dims[5] == 18432
    assert "lm_head" not in by_name
    assert "lm_head" in {lay.name for lay in extract(cfg, shape).layers}
    with pytest.raises(ValueError):
        extract(cfg, shape, ep_chips=5)


# ------------------------------------------- (d) the benchmark's config

@pytest.mark.parametrize("name", ["decode32k", "decode128k"])
def test_bench_config_workloads_equal_extract(name):
    """(d) Each workload of the Kimi benchmark configuration is
    `extract` of the published config, verbatim."""
    conf = json.loads(KIMI_BENCH.read_text())
    dep = conf["deployment_shape"]
    shape = dep["workloads"][name]
    wl = extract(get_config("kimi_k2_1t"),
                 ShapeConfig(name, shape["kv_len"], shape["batch"],
                             "decode"),
                 ep_chips=dep["ep_chips"], depth=dep["depth"])
    want = [{"name": lay.name, "dims": list(lay.dims),
             "wstride": lay.wstride, "hstride": lay.hstride,
             "repeat": lay.repeat} for lay in wl.layers]
    assert conf["workloads"][name]["layers"] == want
    assert conf["num_hidden_layers"] == dep["depth"]
    assert conf["n_routed_experts"] * dep["ep_chips"] == 384


# The bucket ladder {2^k, 3 * 2^k} pads the 576-wide latent to 768, 7168
# to 8192 and the dense 18432 to 24576: the share of the MACs searched
# that padding adds (PERF.md Open questions).
PAD_MAC_SHARE = {"decode32k": 0.152869, "decode128k": 0.150831}


@pytest.mark.parametrize("name", sorted(PAD_MAC_SHARE))
def test_bucketing_pads_the_kimi_step(name):
    """The service searches each benchmark workload bucketed: a fixed
    property of the layers and the ladder, so it is pinned here rather
    than read from a run."""
    conf = json.loads(KIMI_BENCH.read_text())
    layers = conf["workloads"][name]["layers"]
    sent = sum(lay["repeat"] * math.prod(lay["dims"]) for lay in layers)
    wl = extract(get_config("kimi_k2_1t"),
                 ShapeConfig(name, *(conf["deployment_shape"]["workloads"]
                                     [name][k] for k in ("kv_len", "batch")),
                             "decode"), ep_chips=48, depth=5)
    searched = bucket_workload(wl).total_macs
    assert wl.total_macs == sent
    assert (searched - sent) / searched == pytest.approx(
        PAD_MAC_SHARE[name], abs=1e-6)
    padded = {d for lay in bucket_workload(wl).layers for d in lay.dims}
    assert {768, 8192, 24576} <= padded
    assert not {576, 7168, 18432} & padded


# ----------------------------------- (e) served whole step == sequential

@pytest.mark.parametrize("seed", [3, 2**31 + 7])
def test_served_whole_step_matches_sequential(seed):
    """(e) A whole decode step of the reduced config on TPU v5e, as one
    request through `CoSearchService`, gives the sequential driver's
    best EDP, sample count and run of best EDPs.  The sample indices in
    `history` count members in the order each driver advances them
    (one start at a time, or all at once), so those are compared with
    the direct fused driver, which the service runs."""
    cfg = get_config("kimi_k2_1t", reduced=True)
    wl = extract(cfg, ShapeConfig("d", 256, 4, "decode"), ep_chips=2,
                 depth=2)
    sc = SearchConfig(steps=20, round_every=10, n_start_points=2,
                      seed=seed, spec=TPU_V5E_SPEC)
    svc = CoSearchService(ServiceConfig(bucket_workloads=False))
    rid = svc.submit(SearchRequest(workload=wl, config=sc))
    got = svc.drain()[rid].result
    seq = dosa_search(wl, sc)
    assert got.best_edp == seq.best_edp
    assert got.n_evals == seq.n_evals
    assert [v for _, v in got.history] == [v for _, v in seq.history]
    assert got.history == dosa_search(wl, sc, population=2).history


# ------------------------------------- (f) other architectures unchanged

# sha256 of every other architecture's lowering, for every applicable
# shape, before latent attention, shared experts and the expert share.
OTHER_ARCHS_DIGEST = (
    "d4321797b6eb44aba9facb8505fd317329822fb761a08273cfe960c490a594e8")


def test_other_architectures_lower_as_before():
    """(f) Every other config lowers to the layers it lowered to before
    this lowering learned latent attention."""
    rows = []
    for arch in ARCH_IDS:
        if arch == "kimi_k2_1t":
            continue
        cfg = get_config(arch)
        for sname, shape in SHAPES.items():
            if not shape_applicable(cfg, shape)[0]:
                continue
            wl = extract(cfg, shape)
            rows.append([arch, sname, wl.name,
                         [[lay.name, list(lay.dims), lay.wstride,
                           lay.hstride, lay.repeat] for lay in wl.layers]])
    assert len(rows) == 28
    digest = hashlib.sha256(json.dumps(rows).encode()).hexdigest()
    assert digest == OTHER_ARCHS_DIGEST


def test_kimi_published_widths_and_counts():
    """The config carries the published widths; parameter counts land
    at the published 1.04T total and 32B active."""
    cfg = get_config("kimi_k2_1t")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.vocab_size) == (61, 7168, 64, 64, 163840)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim) == (1536, 512, 128, 64,
                                                      128)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.n_shared_experts,
            cfg.d_ff, cfg.first_k_dense, cfg.dense_d_ff) == (
                384, 8, 1, 2048, 1, 18432)
    assert 1.0e12 < cfg.n_params() < 1.06e12
    assert 31e9 < cfg.n_active_params() < 34e9
