"""DOSA one-loop gradient-descent co-search (paper Sec. 5).

Search strategy (Table 5): temporal + spatial tiling factors by GD
(Adam), the spatial dataflow and tensor bypass fixed by the target's
`ArchSpec` (Gemmini weight-stationary C|K by default, Table 4), loop
ordering by exhaustive enumeration — either *iterative* (re-selected
after every rounding, Sec. 5.2.1) or *softmax-weighted in the loss*
(Sec. 5.2.2, Eqs. 15-17).

The engine is architecture-generic: `SearchConfig.spec` selects any
`ArchSpec` (default Gemmini), and every stage — loss construction,
free-parameter masks, rounding sites, ordering tables, hardware
inference, CoSA seeding and oracle evaluation — reads the compiled
spec's tables.  One engine, many targets (Sec. 6.5's modularity claim).

Protocol details implemented from the paper:
* start points: random hardware + CoSA-seeded mappings (Sec. 5.1);
* start-point rejection at 10x the best seen start (Sec. 5.3.1);
* rounding to nearest-divisor valid mappings every `round_every` steps,
  innermost->outermost (Sec. 5.3.2);
* backing-store factors inferred, validity penalty sum max(1-f, 0)
  (Sec. 5.3.3, Eq. 18);
* EDP of the full network as the loss (Eq. 14) — we descend log(EDP),
  a monotone rescaling with identical minimizers that keeps fp32
  gradients well-conditioned;
* every differentiable-model step and every oracle evaluation of a
  rounded mapping counts as one sample (Sec. 6.3 treats them as
  equivalent).

Three execution engines share the protocol:

* the *sequential* reference driver (``dosa_search(..., population=None)``)
  runs each start point's Adam descent as a Python loop of jitted steps;
* the *host-batched* engine (``dosa_search(..., population=P,
  fused=False)``) carries a ``(P, L, 2, n_levels, 7)`` population of
  log-factor tensors and executes each GD segment between roundings as
  one ``jax.lax.scan`` whose body is the Adam update of a
  ``jax.vmap``-ed loss — one device program for the whole population
  instead of ``P x steps`` tiny dispatches.  Rounding, ordering
  re-selection and oracle evaluation happen population-wide on the host
  between segments;
* the *fused* device-resident engine (``dosa_search(..., population=P)``,
  the default) compiles the WHOLE segment loop into one program
  (`make_fused_runner`): an outer ``lax.scan`` whose step is (Adam
  sub-scan -> device nearest-divisor rounding over precomputed divisor
  tables -> device ordering coordinate descent -> model best-EDP
  tracking), with buffer donation on the carried population.  The host
  touches only start points and the final read-back, over which oracle
  accounting replays in host-batched order — so for a given seed all
  engines report the same ``best_edp`` with identical ``n_evals``
  (rounding snaps every engine onto the same divisor-grid candidates).

The fused engine additionally shards its population axis over a device
mesh (``SearchConfig.shards``; auto-resolved from the local device
count by default): every op in the fused segment is per-member, so the
scanned step runs under `shard_map` on a 1-D "pop" mesh
(`launch.mesh.make_pop_mesh` + `sharding.rules.member_spec`) with zero
per-segment communication — per-shard `PopulationBest` trackers are
reduced once per run by a `lax.pmin`-style argmin collective, and the
per-segment rounded read-backs are gathered once at the end.  Sharded
and single-device runs are bit-identical per seed (asserted for all
shipped specs in tests/test_sharding_multidevice.py).

Start points come from the host CoSA protocol by default
(Sec. 5.3.1); ``SearchConfig.start_points`` selects on-device seeding
instead ("random-device" / "cosa-device", `mapping.seed_population`):
a jittable generator over the spec's padded divisor tables, so a
thousand-member population never materializes on host.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .arch import GemminiHW
from .archspec import (ArchSpec, CompiledSpec, GEMMINI_SPEC, HWConfig,
                       compile_spec, resolve_spec)
from .cosa import cosa_map_workload
from .hw_infer import (minimal_hw_for, minimal_hw_from_caps,
                       random_hw_for)
from .lru import LRUCache
from .mapping import SPATIAL, TEMPORAL, Mapping, stack_mappings
from .mapping import unstack_mappings
from .model import (PopulationBest, SpecHW, capacities,
                    capacity_penalty_spec,
                    infer_hw_spec, infer_hw_population_spec,
                    layer_el_all_orderings_spec,
                    layer_el_all_orderings_population_spec,
                    population_best_init, population_best_update,
                    population_edp_spec,
                    validity_penalty, workload_eval_spec,
                    _spec_hw_from_params)
from .oracle import evaluate_population, evaluate_workload
from .problem import Workload
from .rounding import (round_all, round_population, rounding_tables,
                       _round_population_core)
from ..launch.mesh import auto_pop_shards, make_pop_mesh
from ..obs import telemetry as _obs
from ..sharding.rules import POP_AXIS, member_spec, segment_member_spec

# The default target's compiled spec, hoisted to a module constant so
# the Gemmini-default paths of `build_f` / `theta_from_mappings` touch
# no spec-cache lookup per call (they sit inside the hottest host
# loops).
_GEMMINI_CSPEC = compile_spec(GEMMINI_SPEC)

# Free optimization sites of the default (Gemmini) target: temporal
# ACC/SP for all dims, temporal REG for weight-irrelevant dims only (one
# weight register per PE on Gemmini WS), plus the two Gemmini spatial
# factors.  The backing-store temporal factor is inferred.  Generic
# targets read `compile_spec(spec).free_mask` instead.
FREE_MASK = _GEMMINI_CSPEC.free_mask
_FREE_MASK_J = _GEMMINI_CSPEC.free_mask_j

_ADAM_B1, _ADAM_B2, _ADAM_EPS = 0.9, 0.999, 1e-8


def build_f(theta: jnp.ndarray, dims: jnp.ndarray,
            free_mask=None) -> jnp.ndarray:
    """theta (L, 2, n_levels, 7) log-factors -> full factor tensor with
    inferred backing-store temporal factors (Sec. 5.3.3).
    dims: (L, 7) float."""
    mask = _FREE_MASK_J if free_mask is None else free_mask
    f = jnp.where(mask, jnp.exp(theta), 1.0)
    inner = jnp.prod(f, axis=(1, 2)) / f[:, TEMPORAL, -1, :]
    f = f.at[:, TEMPORAL, -1, :].set(dims / inner)
    return f


def theta_from_mappings(mappings: list[Mapping],
                        free_mask: np.ndarray | None = None) -> np.ndarray:
    mask = FREE_MASK if free_mask is None else free_mask
    fs, _ = stack_mappings(mappings)
    theta = np.zeros_like(fs)
    np.log(np.maximum(fs, 1.0), out=theta, where=mask[None])
    return theta


def theta_from_population(population: list[list[Mapping]],
                          free_mask: np.ndarray | None = None) -> np.ndarray:
    """(P, L, 2, n_levels, 7) log-factors for a population of workload
    mappings."""
    return np.stack([theta_from_mappings(ms, free_mask)
                     for ms in population])


def orders_from_population(population: list[list[Mapping]]) -> np.ndarray:
    """(P, L, n_levels) per-level ordering choices for a population."""
    return np.stack([np.stack([m.order for m in ms]) for ms in population])


def stack_population(population: list[list[Mapping]]
                     ) -> tuple[np.ndarray, np.ndarray]:
    """(P, L, 2, n_levels, 7) factors and (P, L, n_levels) orders of a
    population of workload mappings (`stack_mappings` per member)."""
    fs, orders = zip(*(stack_mappings(ms) for ms in population))
    return np.stack(fs), np.stack(orders)


@dataclasses.dataclass
class SearchConfig:
    steps: int = 1490
    round_every: int = 500
    n_start_points: int = 7
    lr: float = 0.01
    penalty_weight: float = 10.0
    ordering_mode: str = "iterative"   # "none" | "iterative" | "softmax"
    softmax_temp: float = 10.0
    spec: ArchSpec | None = None       # target architecture (None: Gemmini)
    fixed_hw: GemminiHW | HWConfig | None = None  # freeze PE dims (Sec. 6.5)
    fix_pe_only: bool = True           # Sec. 6.5 frees buffer sizes
    reject_factor: float = 10.0
    max_reject_tries: int = 10
    seed: int = 0
    latency_model: Callable | None = None  # (mappings, workload) -> EDP
    surrogate: object | None = None        # TrainedModel: GD descends
    #   through the DNN residual/direct latency model (Sec. 6.5).
    #   Spec-generic: the model must be calibrated for `spec`'s
    #   featurization (core.calibration), validated at engine build.
    shards: int | None = None          # fused-engine population shard
    #   count over the "pop" device mesh.  None auto-resolves to the
    #   largest divisor of the population chunk that fits the local
    #   device count (1 on a single-device host).  Sharded and
    #   single-device runs are bit-identical per seed; a host driver
    #   knob only, never part of the engine cache key.
    start_points: str = "cosa"         # "cosa": host CoSA protocol with
    #   rejection (Sec. 5.3.1); "random-device" / "cosa-device": seed
    #   the population ON DEVICE (`mapping.seed_population`) — fused
    #   engine only, no start oracle evals (start_edps stays empty), so
    #   1k-start populations never materialize on host.

    def __post_init__(self):
        """Fail fast on configurations that would otherwise die deep in
        a jit trace (or, worse, silently search the wrong protocol)."""
        if self.ordering_mode not in ("none", "iterative", "softmax"):
            raise ValueError(
                f"unknown ordering_mode {self.ordering_mode!r}; choose "
                "'none', 'iterative' or 'softmax' (Sec. 5.2)")
        for field in ("steps", "round_every", "n_start_points"):
            v = getattr(self, field)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"{field} must be a positive int, "
                                 f"got {v!r}")
        if self.lr <= 0.0:
            raise ValueError(f"lr must be positive, got {self.lr!r}")
        if self.shards is not None and (not isinstance(self.shards, int)
                                        or self.shards < 1):
            raise ValueError(f"shards must be a positive int or None "
                             f"(auto), got {self.shards!r}")
        if self.start_points not in ("cosa", "random-device",
                                     "cosa-device"):
            raise ValueError(
                f"unknown start_points {self.start_points!r}; choose "
                "'cosa' (host protocol), 'random-device' or "
                "'cosa-device' (on-device seeding)")
        # A single-target surrogate must belong to this config's target:
        # a model calibrated for another spec's physics (or feature
        # width) is rejected here with calibration's own diagnostics
        # instead of surfacing as an opaque trace failure.  Fleet
        # surrogate *dicts* are validated per target by fleet_search.
        sur = self.surrogate
        if sur is not None and not isinstance(sur, dict) \
                and hasattr(sur, "n_features") and hasattr(sur, "spec_name"):
            from .calibration import check_surrogate
            check_surrogate(sur, resolve_spec(self.spec))


@dataclasses.dataclass
class SearchResult:
    best_edp: float
    best_mappings: list[Mapping]
    best_hw: GemminiHW | HWConfig
    history: list[tuple[int, float]]   # (cumulative evals, best oracle EDP)
    n_evals: int
    start_edps: list[float]


def _cspec(cfg: SearchConfig) -> CompiledSpec:
    return resolve_spec(cfg.spec)


def _pe_cap(cfg: SearchConfig, cspec: CompiledSpec) -> float:
    """Spatial-factor bound: a frozen hardware point's array side, else
    the spec's own PE bound (fixed silicon side or search cap)."""
    return float(cfg.fixed_hw.pe_dim if cfg.fixed_hw is not None
                 else cspec.pe_cap)


def _fixed_spec_hw(cfg: SearchConfig, cspec: CompiledSpec) -> SpecHW | None:
    """The frozen SpecHW when the whole hardware point is fixed
    (Sec. 6.5 buffer-and-mapping-frozen mode), else None."""
    if cfg.fixed_hw is None or cfg.fix_pe_only:
        return None
    c_pe, cap_words = cspec.hw_words(cfg.fixed_hw)
    return SpecHW(c_pe=jnp.asarray(c_pe), cap_words=jnp.asarray(cap_words))


# ---------------------------------------------------------------------------
# Loss functions
# ---------------------------------------------------------------------------

def _spatial_cap_penalty(f: jnp.ndarray, pe_cap: float,
                         sites) -> jnp.ndarray:
    if not sites:
        return jnp.asarray(0.0)
    s = jnp.stack([f[:, SPATIAL, lvl, d] for (lvl, d) in sites])
    return jnp.sum(jnp.maximum(s / pe_cap - 1.0, 0.0))


def _make_loss_fn(workload: Workload, cfg: SearchConfig):
    """Raw (unjitted) per-start loss `(theta (L, 2, n_levels, 7), orders
    (L, n_levels)) -> scalar`, plus the workload constant arrays.  Both
    engines build on this: the sequential driver jits its
    value_and_grad directly, the batched driver lifts it one population
    axis higher with vmap."""
    cspec = _cspec(cfg)
    dims = jnp.asarray(workload.dims_array(), dtype=jnp.float32)
    strides = jnp.asarray(workload.strides_array(), dtype=jnp.float32)
    repeats = jnp.asarray(workload.repeats_array(), dtype=jnp.float32)
    pe_cap = _pe_cap(cfg, cspec)
    hw_fixed = _fixed_spec_hw(cfg, cspec)
    free_mask_j = cspec.free_mask_j
    if cfg.surrogate is not None:
        # Spec-generic calibration path: validate the trained model's
        # feature width against the target's featurization up front.
        from .calibration import check_surrogate
        check_surrogate(cfg.surrogate, cspec)

    def _surrogate_latency(theta, f, orders, hw: SpecHW, lat_analytical):
        """Per-layer latency through the learned model (differentiable:
        features are the log-factors = theta at the spec's free sites —
        `calibration.traced_features`, the in-loss twin of
        `calibration.featurize_spec`)."""
        from .calibration import traced_features
        from .surrogate import DIRECT_CLIP, RESIDUAL_CLIP, mlp_apply
        sur = cfg.surrogate
        feats = traced_features(cspec, theta, orders, jnp.log(dims), hw)
        x = (feats - jnp.asarray(sur.x_mean)) / jnp.asarray(sur.x_std)
        out = mlp_apply(sur.params, x)                        # (L,)
        if sur.kind == "residual":
            return lat_analytical * jnp.exp(
                jnp.clip(out, -RESIDUAL_CLIP, RESIDUAL_CLIP))
        return jnp.exp(jnp.clip(out, 0.0, DIRECT_CLIP))

    def edp_fixed_orders(f, orders, theta=None):
        edp, (en, lat, hw) = workload_eval_spec(cspec, f, orders, strides,
                                                repeats, hw=hw_fixed)
        if cfg.surrogate is not None and theta is not None:
            lat_a = lat / repeats
            lat_s = _surrogate_latency(theta, f, orders, hw, lat_a)
            edp = jnp.sum(en) * jnp.sum(lat_s * repeats)
        return edp, hw

    def edp_softmax(f, orders):
        hw = infer_hw_spec(cspec, f, strides) if hw_fixed is None \
            else hw_fixed
        e, lat = jax.vmap(lambda fl, s: layer_el_all_orderings_spec(
            cspec, fl, s, hw.c_pe, hw.cap_words))(f, strides)
        inv = jnp.min(e * lat, axis=1, keepdims=True) / (e * lat)
        w = jax.nn.softmax(cfg.softmax_temp * inv, axis=1)       # Eq. 16
        e_l = jnp.sum(w * e, axis=1) * repeats
        l_l = jnp.sum(w * lat, axis=1) * repeats
        return jnp.sum(e_l) * jnp.sum(l_l), hw                   # Eq. 17

    def _fixed_silicon_penalty(f):
        """Overflow of fixed-capacity levels (e.g. TPU VMEM) — active
        even in mapping-first mode, where no searched buffer grows to
        absorb the tile."""
        if not cspec.fixed_capacity:
            return 0.0
        caps = jax.vmap(capacities)(f, strides)
        pen = 0.0
        for (i, words) in cspec.fixed_capacity:
            req = sum(caps[:, i, t] for t in range(3)
                      if cspec.b_matrix[i, t])
            pen = pen + jnp.sum(jnp.maximum(req / words - 1.0, 0.0))
        return pen

    def loss(theta, orders):
        f = build_f(theta, dims, free_mask_j)
        if cfg.ordering_mode == "softmax" and cfg.surrogate is None:
            edp, _ = edp_softmax(f, orders)
        else:
            edp, _ = edp_fixed_orders(f, orders, theta=theta)
        pen = validity_penalty(f) \
            + _spatial_cap_penalty(f, pe_cap, cspec.spatial_sites)
        if hw_fixed is not None:
            pen = pen + capacity_penalty_spec(cspec, f, strides, hw_fixed)
        else:
            pen = pen + _fixed_silicon_penalty(f)
        return jnp.log(edp) + cfg.penalty_weight * pen

    return loss, dims, strides, repeats


# Compiled-engine cache.  Jitting the loss costs seconds of XLA compile
# per workload; re-deriving it on every dosa_search call would leave
# nothing warm across repeated searches of the same workload (the common
# case in benchmarks, sweeps, and the serving layer).  Keyed by the
# workload plus every config field the traced program reads; fields that
# only steer the host driver (steps, seed, rejection protocol,
# latency_model) are excluded on purpose.  The surrogate is keyed by
# identity: its parameters are baked into the trace.  Bounded LRU with
# eviction accounting: a long-lived co-search server streams unbounded
# (workload, config) variety through this cache, so it must not grow
# without limit — `engine_cache_stats()` surfaces the hit/miss/eviction
# counters (they feed `bench_results/serve_metrics.json`).
_ENGINE_CACHE = LRUCache(maxsize=16)


def _engine_key(workload: Workload, cfg: SearchConfig, kind: str):
    return (kind, workload, cfg.spec, cfg.lr, cfg.penalty_weight,
            cfg.ordering_mode, cfg.softmax_temp, cfg.fixed_hw,
            cfg.fix_pe_only,
            id(cfg.surrogate) if cfg.surrogate is not None else None)


def _cached_engine(workload: Workload, cfg: SearchConfig, kind: str, build):
    key = _engine_key(workload, cfg, kind)
    hit = _ENGINE_CACHE.get(key, None)
    if hit is not None:
        return hit
    # Cache miss: build under an `engine.build` span (obs.telemetry
    # owns the clock, so this stays ND202/OB601-clean) and keep the
    # per-entry build time on the cache for `engine_cache_stats()`.
    label = f"{kind}:{workload.name}"
    value, build_s = _obs.profile_build(build, kind=kind,
                                        cache="search", label=label)
    _ENGINE_CACHE.put(key, value)
    _ENGINE_CACHE.note_build_time(label, build_s)
    return value


def engine_cache_stats() -> dict:
    """Hit/miss/eviction counters of the compiled-engine cache — the
    serving layer's warm-engine health metric."""
    return _ENGINE_CACHE.stats()


def make_loss(workload: Workload, cfg: SearchConfig):
    def build():
        loss, dims, strides, repeats = _make_loss_fn(workload, cfg)
        return jax.jit(jax.value_and_grad(loss)), dims, strides, repeats
    return _cached_engine(workload, cfg, "sequential", build)


# ---------------------------------------------------------------------------
# Adam (pure JAX)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("lr",), donate_argnums=(0, 2, 3))
def adam_step(theta, grad, m, v, t, lr: float, b1=_ADAM_B1, b2=_ADAM_B2,
              eps=_ADAM_EPS):
    m = b1 * m + (1 - b1) * grad
    v = b2 * v + (1 - b2) * grad * grad
    mh = m / (1 - b1 ** t)
    vh = v / (1 - b2 ** t)
    return theta - lr * mh / (jnp.sqrt(vh) + eps), m, v


def _adam_scan(pop_grad, lr: float, theta, args, n_steps: int):
    """One GD segment as a `jax.lax.scan` of Adam steps over the
    population gradient — the traced core shared by the standalone
    segment runner and the fused device-resident engines.  Fresh
    momentum per segment, matching the sequential driver's reset after
    every rounding."""
    def body(carry, t):
        th, m, v = carry
        _, g = pop_grad(th, *args)
        m = _ADAM_B1 * m + (1 - _ADAM_B1) * g
        v = _ADAM_B2 * v + (1 - _ADAM_B2) * g * g
        mh = m / (1 - _ADAM_B1 ** t)
        vh = v / (1 - _ADAM_B2 ** t)
        th = th - lr * mh / (jnp.sqrt(vh) + _ADAM_EPS)
        return (th, m, v), ()
    ts = jnp.arange(1, n_steps + 1, dtype=theta.dtype)
    zeros = jnp.zeros_like(theta)
    (theta, _, _), _ = jax.lax.scan(body, (theta, zeros, zeros), ts)
    return theta


def make_segment_runner(pop_grad, lr: float):
    """Jitted Adam GD-segment executor shared by the batched population
    engine and the fleet engine (`core/fleet.py`): advance a whole
    population of log-factor tensors by `n_steps` Adam steps as a
    single `jax.lax.scan` whose body evaluates `pop_grad(theta, *args)
    -> (value, grad)`.  Extra positional `args` (orders; per-member
    spec tables for the fleet) are carried through to `pop_grad`
    unchanged; `n_steps` is keyword-only.  The incoming population
    tensor is donated: the Adam carry reuses its buffer in place, so a
    segment holds one live population + momentum set instead of two."""
    @partial(jax.jit, static_argnames=("n_steps",), donate_argnums=(0,))
    def run_segment(theta, *args, n_steps: int):
        return _adam_scan(pop_grad, lr, theta, args, n_steps)

    return run_segment


def make_population_runner(workload: Workload, cfg: SearchConfig):
    """Build the batched GD-segment executor: one jitted function that
    advances a whole (P, L, 2, n_levels, 7) population by `n_steps`
    Adam steps as a single `jax.lax.scan` over the vmapped loss
    gradient.  Cached per (workload, cfg) like `make_loss`."""
    def build():
        loss, dims, strides, repeats = _make_loss_fn(workload, cfg)
        pop_grad = jax.vmap(jax.value_and_grad(loss), in_axes=(0, 0))
        return make_segment_runner(pop_grad, cfg.lr), dims, strides, repeats

    return _cached_engine(workload, cfg, "population", build)


def _segment_lengths(steps: int, round_every: int) -> list[int]:
    """GD-step counts between consecutive rounding points: the sequential
    driver rounds at every multiple of `round_every` and at `steps`."""
    full, rem = divmod(steps, round_every)
    return [round_every] * full + ([rem] if rem else [])


def _reduce_population_best(best: PopulationBest,
                            n_shards: int) -> PopulationBest:
    """Cross-shard reduction of per-member best trackers to the single
    global winner, `lax.pmin`-style: each shard contributes only its
    local argmin, the global minimum EDP is a `pmin`, the winning shard
    is the lowest-indexed one achieving it, and the winner's payload
    (factor tensor + orders) crosses shards via a masked `psum` — one
    (best_edp, argmin payload) over the wire instead of the whole
    population.  Runs inside `shard_map`; returns a singleton
    (leading axis 1), replicated across shards."""
    i = jnp.argmin(best.edp)
    edp_l = best.edp[i]
    f_l, o_l = best.f[i], best.orders[i]
    gmin = jax.lax.pmin(edp_l, POP_AXIS)
    idx = jax.lax.axis_index(POP_AXIS)
    winner = jax.lax.pmin(
        jnp.where(edp_l == gmin, idx, jnp.int32(n_shards)), POP_AXIS)
    mine = idx == winner
    f_g = jax.lax.psum(jnp.where(mine, f_l, jnp.zeros_like(f_l)),
                       POP_AXIS)
    o_g = jax.lax.psum(jnp.where(mine, o_l, jnp.zeros_like(o_l)),
                       POP_AXIS)
    return PopulationBest(edp=gmin[None], f=f_g[None], orders=o_g[None])


def shard_population(theta, orders, shards: int):
    """Place a (P, ...) population on the "pop" mesh so the fused
    engine's donated buffers match the sharded program's layout (no
    re-layout copy, donation stays usable).  No-op at shards=1."""
    if shards == 1:
        return theta, orders
    from jax.sharding import NamedSharding
    mesh = make_pop_mesh(shards)
    theta = jax.device_put(
        theta, NamedSharding(mesh, member_spec(theta.ndim - 1)))
    orders = jax.device_put(
        orders, NamedSharding(mesh, member_spec(orders.ndim - 1)))
    return theta, orders


def make_fused_runner(workload: Workload, cfg: SearchConfig):
    """Build the fully device-resident search engine: ONE jitted program
    per (workload, cfg) whose outer `jax.lax.scan` runs the whole
    one-loop protocol — each scan step is (Adam GD sub-scan -> device
    nearest-divisor rounding -> device ordering coordinate descent ->
    model best-EDP tracking) — so the host launches a single dispatch
    per population chunk and reads back only the per-segment rounded
    candidates (for oracle accounting) and the running device best.

    `run_fused(theta, orders, *, n_full, rem, seg_len, shards=1)`
    advances a (P, L, 2, n_levels, 7) population through `n_full`
    segments of `seg_len` GD steps plus an optional `rem`-step tail
    segment (the segment schedule is static, so distinct
    `steps`/`round_every` configurations compile their own single
    program).  theta and orders are donated: the scan carry reuses
    their buffers in place.  Returns ``((f_rounded, orders, model_edp),
    best)`` with a leading per-segment axis on the first tuple.

    `shards > 1` runs the identical scanned step under `shard_map` on
    the 1-D "pop" mesh, the population split `shards` ways (`shards`
    must divide P).  Every segment op is per-member, so shards never
    communicate during the scan and the per-member numerics — hence the
    rounded read-backs — are bit-identical to `shards=1`.  Per-shard
    best trackers are reduced once after the scan by a pmin-style
    argmin collective (`_reduce_population_best`), so the sharded
    `best` is the single global winner with leading axis 1 (at
    `shards=1` it stays the per-member tracker).
    """
    def build():
        cspec = _cspec(cfg)
        loss, dims, strides, repeats = _make_loss_fn(workload, cfg)
        pop_grad = jax.vmap(jax.value_and_grad(loss), in_axes=(0, 0))
        tables = rounding_tables(workload.dims_array())
        pe_cap = int(_pe_cap(cfg, cspec))
        hw_fixed = _fixed_spec_hw(cfg, cspec)
        free_mask_j = cspec.free_mask_j
        combos = jnp.asarray(cspec.combos)
        reselect = cfg.ordering_mode in ("iterative", "softmax")

        # The four phases carry named scopes: every device op's op_name
        # metadata starts with the phase that owns it, so a profile
        # splits the program's device time by phase.
        def segment(theta, orders, best, n_steps: int):
            with jax.named_scope("gd"):
                theta = _adam_scan(pop_grad, cfg.lr, theta, (orders,),
                                   n_steps)
            with jax.named_scope("round"):
                f_cont = jax.vmap(
                    lambda th: build_f(th, dims, free_mask_j))(theta)
                f_round, theta = _round_population_core(cspec, tables,
                                                        f_cont, pe_cap)
            if reselect:
                with jax.named_scope("ordering"):
                    if hw_fixed is not None:
                        hws = jax.tree_util.tree_map(
                            lambda x: jnp.broadcast_to(
                                x, theta.shape[:1] + jnp.shape(x)),
                            hw_fixed)
                    else:
                        hws = infer_hw_population_spec(cspec, f_round,
                                                       strides)
                    e, lat = layer_el_all_orderings_population_spec(
                        cspec, f_round, strides, hws)
                    rep = repeats[None, :, None]
                    choice = jax.vmap(_cd_orderings)(e * rep, lat * rep)
                    orders = combos[choice]            # (P, L, n_levels)
            with jax.named_scope("best"):
                edp = population_edp_spec(cspec, f_round, orders, strides,
                                          repeats, hw=hw_fixed)
                best = population_best_update(best, edp, f_round, orders)
            return theta, orders, best, (f_round, orders, edp)

        def run_all(theta, orders, n_full: int, rem: int, seg_len: int):
            best = population_best_init(theta, orders)
            ys = None
            if n_full:
                def body(carry, _):
                    theta, orders, best = carry
                    theta, orders, best, out = segment(theta, orders, best,
                                                       seg_len)
                    return (theta, orders, best), out
                (theta, orders, best), ys = jax.lax.scan(
                    body, (theta, orders, best), None, length=n_full)
            if rem:
                theta, orders, best, out = segment(theta, orders, best, rem)
                tail = jax.tree_util.tree_map(lambda x: x[None], out)
                ys = tail if ys is None else jax.tree_util.tree_map(
                    lambda a, b: jnp.concatenate([a, b]), ys, tail)
            return ys, best

        @partial(jax.jit,
                 static_argnames=("n_full", "rem", "seg_len", "shards"),
                 donate_argnums=(0, 1))
        def run_fused(theta, orders, *, n_full: int, rem: int,
                      seg_len: int, shards: int = 1):
            if shards == 1:
                return run_all(theta, orders, n_full, rem, seg_len)
            mesh = make_pop_mesh(shards)

            def sharded(theta, orders):
                ys, best = run_all(theta, orders, n_full, rem, seg_len)
                return ys, _reduce_population_best(best, shards)

            from jax.sharding import PartitionSpec as _P
            ys_specs = (segment_member_spec(4),   # f_round (S, P, L, 2, nl, 7)
                        segment_member_spec(2),   # orders  (S, P, L, nl)
                        segment_member_spec(0))   # edp     (S, P)
            best_specs = PopulationBest(edp=_P(), f=_P(), orders=_P())
            return jax.shard_map(
                sharded, mesh=mesh,
                in_specs=(member_spec(theta.ndim - 1),
                          member_spec(orders.ndim - 1)),
                out_specs=(ys_specs, best_specs))(theta, orders)

        return run_fused, dims, strides, repeats

    return _cached_engine(workload, cfg, "fused", build)


# ---------------------------------------------------------------------------
# Loop-ordering selection (Sec. 5.2.1): coordinate descent over the
# 3**(n_levels-1) per-layer combos against network EDP (Eq. 14).
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("n_passes",))
def _cd_orderings(e: jnp.ndarray, lat: jnp.ndarray,
                  n_passes: int = 2) -> jnp.ndarray:
    """Coordinate descent over per-layer ordering choices as a pure
    jittable program — ONE implementation (and therefore one float /
    tie-breaking semantics) shared by the host helpers and the fused
    device-resident engines.  e, lat: (L, n_combos) repeat-scaled
    energies/latencies.  Returns (L,) int32 combo indices minimizing
    (sum e) * (sum l); each pass re-derives the totals then sweeps the
    layers in order, exactly the original host algorithm."""
    L = e.shape[0]

    def one_pass(choice, _):
        e_tot = jnp.sum(jnp.take_along_axis(e, choice[:, None], axis=1))
        l_tot = jnp.sum(jnp.take_along_axis(lat, choice[:, None],
                                            axis=1))

        def layer_step(carry, xs):
            choice, e_tot, l_tot = carry
            i, ei, li = xs
            c0 = choice[i]
            e_rest = e_tot - ei[c0]
            l_rest = l_tot - li[c0]
            c = jnp.argmin((e_rest + ei) * (l_rest + li)).astype(choice.dtype)
            choice = choice.at[i].set(c)
            return (choice, e_rest + ei[c], l_rest + li[c]), ()

        (choice, _, _), _ = jax.lax.scan(
            layer_step, (choice, e_tot, l_tot), (jnp.arange(L), e, lat))
        return choice, ()

    # Built from `e` (not `jnp.zeros(L)`) so that under `shard_map`
    # the carry is varying over the "pop" axis like the scan's output.
    choice0 = jnp.zeros_like(e[:, 0], dtype=jnp.int32)
    choice, _ = jax.lax.scan(one_pass, choice0, None, length=n_passes)
    return choice


def select_orderings_spec(cspec: CompiledSpec, fs: np.ndarray,
                          strides: np.ndarray, repeats: np.ndarray,
                          hw: SpecHW, n_passes: int = 2) -> np.ndarray:
    combos = cspec.combos                            # (n_combos, n_levels)
    e, lat = jax.vmap(lambda f, s: layer_el_all_orderings_spec(
        cspec, f, s, hw.c_pe, hw.cap_words))(
        jnp.asarray(fs), jnp.asarray(strides))
    rep = jnp.asarray(repeats, dtype=e.dtype)[:, None]
    choice = _cd_orderings(e * rep, lat * rep, n_passes=n_passes)
    return combos[np.asarray(choice)]                # (L, n_levels)


def select_orderings(fs: np.ndarray, strides: np.ndarray,
                     repeats: np.ndarray, hw, n_passes: int = 2) -> np.ndarray:
    """Legacy Gemmini entry point (`hw`: model.HWParams)."""
    return select_orderings_spec(compile_spec(GEMMINI_SPEC), fs, strides,
                                 repeats, _spec_hw_from_params(hw),
                                 n_passes)


def select_orderings_population_spec(cspec: CompiledSpec,
                                     fs_pop: np.ndarray, strides: np.ndarray,
                                     repeats: np.ndarray, hws: SpecHW,
                                     n_passes: int = 2) -> np.ndarray:
    """Population-wide iterative ordering re-selection: one batched
    device computation of all (P, L, n_combos) energy/latency tables,
    then per-member host coordinate descent.  hws carries (P,)/(P,
    n_levels) leaves (one inferred/fixed hardware per member).  Returns
    (P, L, n_levels)."""
    combos = cspec.combos
    e, lat = layer_el_all_orderings_population_spec(
        cspec, jnp.asarray(fs_pop), jnp.asarray(strides), hws)
    rep = jnp.asarray(repeats, dtype=e.dtype)[None, :, None]
    choice = jax.vmap(
        lambda ep, lp: _cd_orderings(ep, lp, n_passes=n_passes))(
        e * rep, lat * rep)
    return combos[np.asarray(choice)]                # (P, L, n_levels)


def select_orderings_population(fs_pop: np.ndarray, strides: np.ndarray,
                                repeats: np.ndarray, hws,
                                n_passes: int = 2) -> np.ndarray:
    """Legacy Gemmini entry point (`hws`: model.HWParams, (P,) leaves)."""
    shw = SpecHW(c_pe=jnp.asarray(hws.c_pe),
                 cap_words=jnp.stack([
                     jnp.full_like(jnp.asarray(hws.acc_words), jnp.inf),
                     jnp.asarray(hws.acc_words),
                     jnp.asarray(hws.sp_words),
                     jnp.full_like(jnp.asarray(hws.acc_words), jnp.inf)],
                     axis=-1))
    return select_orderings_population_spec(
        compile_spec(GEMMINI_SPEC), fs_pop, strides, repeats, shw, n_passes)


# ---------------------------------------------------------------------------
# Oracle accounting shared by both engines
# ---------------------------------------------------------------------------

def _oracle_candidates(path: str, n: int) -> None:
    """Count `n` replayed candidates by replay path into the global
    registry: `oracle_batch_candidates_total` (one batched oracle call
    over a read-back) or `oracle_scalar_candidates_total` (one
    `_Recorder.record` each)."""
    _obs.get_metrics().counter(
        f"oracle_{path}_candidates_total",
        f"rounded candidates replayed on the {path} oracle path").inc(n)


def _oracle_edp(mappings, workload, cfg, cspec: CompiledSpec) -> float:
    if cfg.latency_model is not None:
        return cfg.latency_model(mappings, workload)
    hw = cfg.fixed_hw
    if hw is not None and cfg.fix_pe_only:
        # Sec. 6.5 protocol: PE dims frozen, buffers re-derived minimally.
        derived = minimal_hw_for(cspec, mappings, list(workload.layers))
        hw = dataclasses.replace(derived, pe_dim=cfg.fixed_hw.pe_dim)
    edp, _ = evaluate_workload(mappings, workload.layers,
                               hw=hw if hw is not None else None,
                               spec=cspec)
    return float(edp)


class _Recorder:
    """Sample accounting shared by the sequential and batched drivers:
    every differentiable-model step and every oracle evaluation counts
    as one sample (Sec. 6.3).  `improved` counts the recorded candidates
    that set a new best (and so paid for their mappings and hardware)."""

    def __init__(self, workload: Workload, cfg: SearchConfig,
                 cspec: CompiledSpec):
        self.workload, self.cfg, self.cspec = workload, cfg, cspec
        self.evals = 0
        self.improved = 0
        if cspec.spec is GEMMINI_SPEC:
            hw0 = GemminiHW(1, 1.0, 1.0)
        else:
            hw0 = HWConfig(1, (1.0,) * len(cspec.searched_levels))
        self.best = SearchResult(best_edp=float("inf"), best_mappings=[],
                                 best_hw=hw0, history=[], n_evals=0,
                                 start_edps=[])

    def count(self, n: int = 1) -> None:
        self.evals += n

    @property
    def oracle_path(self) -> str:
        """How `record_batch` replays: "scalar" (one `record` a
        candidate) where the configuration prices a candidate by more
        than the mappings alone — a learned latency model, or Sec. 6.5's
        buffers re-derived per candidate — else "batch"."""
        cfg = self.cfg
        if cfg.latency_model is not None or (cfg.fixed_hw is not None
                                             and cfg.fix_pe_only):
            return "scalar"
        return "batch"

    def record_batch(self, fs: np.ndarray, orders: np.ndarray) -> None:
        """Oracle-evaluate B rounded candidates — `fs` (B, L, 2, n_levels,
        7), `orders` (B, L, n_levels) — and update the running best
        exactly as B calls to `record` in member order would: one
        `evaluate_population` call, and `Mapping` objects and hardware
        only for a member that sets a new best."""
        if self.oracle_path == "scalar":
            for f, o in zip(fs, orders):
                self.record(unstack_mappings(f, o))
            return
        cfg, best, cspec = self.cfg, self.best, self.cspec
        edps, caps = evaluate_population(fs, orders, self.workload.layers,
                                         hw=cfg.fixed_hw, spec=cspec)
        _oracle_candidates("batch", len(edps))
        for b, edp in enumerate(edps.tolist()):
            self.evals += 1
            if edp < best.best_edp:
                self.improved += 1
                best.best_edp = edp
                best.best_mappings = [
                    m.copy() for m in unstack_mappings(fs[b], orders[b])]
                best.best_hw = (cfg.fixed_hw if cfg.fixed_hw is not None
                                else minimal_hw_from_caps(cspec, caps[b],
                                                          fs[b]))
            best.history.append((self.evals, best.best_edp))

    def record(self, mappings: list[Mapping]) -> float:
        """Oracle-evaluate a rounded candidate, update the running best."""
        cfg, best = self.cfg, self.best
        edp = _oracle_edp(mappings, self.workload, cfg, self.cspec)
        _oracle_candidates("scalar", 1)
        self.evals += 1
        if edp < best.best_edp:
            self.improved += 1
            best.best_edp = edp
            best.best_mappings = [m.copy() for m in mappings]
            hw = minimal_hw_for(self.cspec, mappings,
                                list(self.workload.layers))
            if cfg.fixed_hw is not None and cfg.fix_pe_only:
                hw = dataclasses.replace(hw, pe_dim=cfg.fixed_hw.pe_dim)
            elif cfg.fixed_hw is not None:
                hw = cfg.fixed_hw
            best.best_hw = hw
        best.history.append((self.evals, best.best_edp))
        return edp

    def finish(self) -> SearchResult:
        self.best.n_evals = self.evals
        return self.best


# ---------------------------------------------------------------------------
# Start-point generation with rejection (Sec. 5.3.1)
# ---------------------------------------------------------------------------

def _generate_start_point(workload: Workload, cfg: SearchConfig,
                          rng: np.random.Generator, best_start_edp: float,
                          rec: _Recorder):
    """One random-hardware + CoSA-seeded start point, rejected (up to
    `max_reject_tries` times) while its EDP exceeds `reject_factor` x the
    best start seen so far.  Returns (mappings, edp0, best_start_edp)."""
    cspec = rec.cspec
    mappings = None
    for _ in range(cfg.max_reject_tries):
        hw0 = cfg.fixed_hw if cfg.fixed_hw is not None \
            else random_hw_for(cspec, rng)
        cand = cosa_map_workload(list(workload.layers), hw0, spec=cspec)
        edp0 = _oracle_edp(cand, workload, cfg, cspec)
        rec.count()
        if edp0 <= cfg.reject_factor * best_start_edp:
            mappings = cand
            best_start_edp = min(best_start_edp, edp0)
            break
    if mappings is None:
        mappings = cand
    return mappings, edp0, best_start_edp


def generate_start_points(workload: Workload, cfg: SearchConfig,
                          rng: np.random.Generator | None = None):
    """All `cfg.n_start_points` start points, generated with the running
    population-wide rejection rule.  Returns (population, start_edps,
    n_evals_spent) — the standalone entry point used by the batched
    engine's tests; both search drivers consume the same per-start
    helper, so the RNG stream (and therefore the start points) are
    identical across engines for a given seed."""
    rng = np.random.default_rng(cfg.seed) if rng is None else rng
    rec = _Recorder(workload, cfg, _cspec(cfg))
    population, best_start_edp = [], float("inf")
    for _ in range(cfg.n_start_points):
        mappings, edp0, best_start_edp = _generate_start_point(
            workload, cfg, rng, best_start_edp, rec)
        rec.best.start_edps.append(edp0)
        population.append(mappings)
    return population, rec.best.start_edps, rec.evals


# ---------------------------------------------------------------------------
# Main search
# ---------------------------------------------------------------------------

def dosa_search(workload: Workload, cfg: SearchConfig,
                population: int | None = None,
                fused: bool = True) -> SearchResult:
    """Run DOSA co-search.  `population=None` is the sequential reference
    driver; `population=P` advances the start points P at a time through
    the batched scan/vmap engine (same protocol, same sample counting,
    same start points for a given seed).

    `fused` selects the population engine flavour: True (default) runs
    the device-resident fused engine — one compiled program per chunk
    containing every GD segment, rounding and ordering re-selection,
    with the host touching only start points and final read-back;
    False runs the host-batched reference engine, which returns to the
    host at every rounding point.  Both are seeded-identical on divisor
    grids (same rounded candidates => same oracle accounting).

    Since the `repro.api` façade redesign this entry point is a thin
    wrapper: it builds a single-target `api.SearchRequest` and runs it
    synchronously, bit-identical to the pre-façade driver (pinned by
    seeded golden tests in tests/test_api.py)."""
    from ..api import SearchRequest, run_request
    return run_request(SearchRequest(
        workload=workload, config=cfg, population=population,
        fused=fused)).result


def execute_search(workload: Workload, cfg: SearchConfig,
                   population: int | None = None,
                   fused: bool = True) -> SearchResult:
    """Engine dispatch shared by `dosa_search` and the `repro.api`
    executor — the pre-façade driver, unchanged."""
    if cfg.start_points != "cosa" and (population is None or not fused):
        raise ValueError(
            f"start_points={cfg.start_points!r} seeds the population on "
            "device and only the fused engine consumes it; pass "
            "population=P with fused=True")
    if population is not None:
        if population < 1:
            raise ValueError(f"population must be >= 1, got {population}")
        if fused:
            return _dosa_search_fused(workload, cfg, int(population))
        return _dosa_search_batched(workload, cfg, int(population))
    return _dosa_search_sequential(workload, cfg)


def _ordering_hw(cfg: SearchConfig, cspec: CompiledSpec,
                 fs: np.ndarray, strides: np.ndarray) -> SpecHW:
    """Hardware point against which rounded candidates re-select their
    loop orderings: the frozen config when fully fixed, else inferred
    minimal hardware."""
    fixed = _fixed_spec_hw(cfg, cspec)
    if fixed is not None:
        return fixed
    return infer_hw_spec(cspec, jnp.asarray(fs), jnp.asarray(strides))


def _dosa_search_sequential(workload: Workload,
                            cfg: SearchConfig) -> SearchResult:
    cspec = _cspec(cfg)
    rng = np.random.default_rng(cfg.seed)
    loss_grad, dims_j, strides_j, repeats_j = make_loss(workload, cfg)
    dims = workload.dims_array()
    strides = workload.strides_array().astype(float)
    repeats = workload.repeats_array().astype(float)
    free_mask_j = cspec.free_mask_j
    pe_cap = int(_pe_cap(cfg, cspec))

    rec = _Recorder(workload, cfg, cspec)
    best_start_edp = float("inf")

    for sp_i in range(cfg.n_start_points):
        # ---- start-point generation with rejection (Sec. 5.3.1)
        mappings, edp0, best_start_edp = _generate_start_point(
            workload, cfg, rng, best_start_edp, rec)
        rec.best.start_edps.append(edp0)
        rec.record(mappings)

        theta = jnp.asarray(theta_from_mappings(mappings, cspec.free_mask),
                            dtype=jnp.float32)
        orders = jnp.asarray(np.stack([m.order for m in mappings]))
        m_t = jnp.zeros_like(theta)
        v_t = jnp.zeros_like(theta)
        t = 0

        for step in range(1, cfg.steps + 1):
            t += 1
            val, grad = loss_grad(theta, orders)
            theta, m_t, v_t = adam_step(theta, grad, m_t, v_t, float(t),
                                        lr=cfg.lr)
            rec.count()
            if step % cfg.round_every == 0 or step == cfg.steps:
                f_cont = np.asarray(build_f(theta, dims_j, free_mask_j))
                rounded = round_all(f_cont, np.asarray(orders), dims,
                                    pe_cap=pe_cap, spec=cspec)
                if cfg.ordering_mode in ("iterative", "softmax"):
                    fs_r, _ = stack_mappings(rounded)
                    hwp = _ordering_hw(cfg, cspec, fs_r, strides)
                    new_orders = select_orderings_spec(cspec, fs_r, strides,
                                                       repeats, hwp)
                    for mp, o in zip(rounded, new_orders):
                        mp.order = o
                    orders = jnp.asarray(new_orders)
                rec.record(rounded)
                # Continue GD from the rounded point, fresh momentum.
                theta = jnp.asarray(
                    theta_from_mappings(rounded, cspec.free_mask),
                    dtype=jnp.float32)
                m_t = jnp.zeros_like(theta)
                v_t = jnp.zeros_like(theta)
                t = 0

    return rec.finish()


def _dosa_search_batched(workload: Workload, cfg: SearchConfig,
                         population: int) -> SearchResult:
    """Batched multi-start engine: the GD inner loop over every start
    point in a chunk runs as a single scanned, vmapped device program;
    the host only intervenes at rounding points (Sec. 5.3.2), where the
    whole chunk is rounded, re-ordered and oracle-evaluated at once."""
    cspec = _cspec(cfg)
    rng = np.random.default_rng(cfg.seed)
    run_segment, dims_j, strides_j, repeats_j = \
        make_population_runner(workload, cfg)
    dims = workload.dims_array()
    strides = workload.strides_array().astype(float)
    repeats = workload.repeats_array().astype(float)
    free_mask_j = cspec.free_mask_j
    pe_cap = int(_pe_cap(cfg, cspec))

    rec = _Recorder(workload, cfg, cspec)

    # ---- population-wide start generation with rejection (Sec. 5.3.1).
    # Start points consume the RNG in the same order as the sequential
    # driver, so both engines descend from identical populations.
    starts, best_start_edp = [], float("inf")
    for _ in range(cfg.n_start_points):
        mappings, edp0, best_start_edp = _generate_start_point(
            workload, cfg, rng, best_start_edp, rec)
        rec.best.start_edps.append(edp0)
        starts.append(mappings)

    segments = _segment_lengths(cfg.steps, cfg.round_every)
    hw_fixed = _fixed_spec_hw(cfg, cspec)

    for lo in range(0, len(starts), population):
        chunk = starts[lo:lo + population]
        n_real = len(chunk)
        rec.record_batch(*stack_population(chunk))
        # Pad a ragged final chunk to `population` with replicas of the
        # last member: every population op is per-member, so padding
        # never perturbs the real slices, and ONE program shape covers
        # every chunk (no second XLA compile for the tail).  Padded
        # members are masked out of oracle accounting below.
        chunk = chunk + [chunk[-1]] * (population - n_real)
        P = len(chunk)

        theta = jnp.asarray(theta_from_population(chunk, cspec.free_mask),
                            dtype=jnp.float32)
        orders = jnp.asarray(orders_from_population(chunk))

        tracer = _obs.get_tracer()
        for seg, n_steps in enumerate(segments):
            with tracer.span("search.gd_segment", segment=seg,
                             n_steps=n_steps, population=P):
                theta = run_segment(theta, orders, n_steps=n_steps)
                rec.count(n_steps * n_real)  # one sample per GD step

            with tracer.span("search.rounding", segment=seg):
                f_cont = np.asarray(jax.vmap(
                    lambda th: build_f(th, dims_j, free_mask_j))(theta))
                rounded_pop = round_population(
                    f_cont, np.asarray(orders), dims,
                    pe_cap=pe_cap, spec=cspec)
            if cfg.ordering_mode in ("iterative", "softmax"):
                with tracer.span("search.ordering", segment=seg):
                    fs_pop = np.stack(
                        [stack_mappings(ms)[0] for ms in rounded_pop])
                    if hw_fixed is not None:
                        hws = jax.tree_util.tree_map(
                            lambda x: jnp.broadcast_to(
                                x, (P,) + jnp.shape(x)),
                            hw_fixed)
                    else:
                        hws = infer_hw_population_spec(
                            cspec, jnp.asarray(fs_pop),
                            jnp.asarray(strides))
                    new_orders = select_orderings_population_spec(
                        cspec, fs_pop, strides, repeats, hws)
                    for ms, no in zip(rounded_pop, new_orders):
                        for mp, o in zip(ms, no):
                            mp.order = o
            with tracer.span("search.oracle", segment=seg,
                             path=rec.oracle_path) as sp:
                improved = rec.improved
                rec.record_batch(*stack_population(rounded_pop[:n_real]))
                sp.set(candidates=n_real, improved=rec.improved - improved)
            # Continue GD from the rounded points, fresh momentum.
            theta = jnp.asarray(
                theta_from_population(rounded_pop, cspec.free_mask),
                dtype=jnp.float32)
            orders = jnp.asarray(orders_from_population(rounded_pop))

    return rec.finish()


def _dosa_search_fused(workload: Workload, cfg: SearchConfig,
                       population: int) -> SearchResult:
    """Device-resident engine driver: per population chunk the host
    dispatches ONE compiled program (every GD segment + rounding +
    ordering re-selection fused into a single scan, `make_fused_runner`)
    and reads back the per-segment rounded candidates once at the end.
    Oracle accounting then replays over the read-back in exactly the
    host-batched engine's order, so `best_edp` / `n_evals` / `history`
    are identical whenever both engines round to the same divisor-grid
    candidates (GD float drift between the two compiled forms is
    absorbed by the nearest-divisor snap; theta restarts from the same
    integer logs each segment, so drift never accumulates).

    The population axis is sharded over the "pop" device mesh
    (`cfg.shards`; auto-resolved by default) — a per-member engine, so
    the read-back, and with it every reported number, is bit-identical
    at any shard count.  Ragged final chunks are padded to `population`
    with replicated members (one compiled shape) and the padding masked
    out of oracle accounting.  `cfg.start_points` in {"random-device",
    "cosa-device"} seeds each chunk on device (`mapping.seed_population`
    keyed on fold_in(seed, chunk)) instead of the host CoSA protocol."""
    cspec = _cspec(cfg)
    run_fused = make_fused_runner(workload, cfg)[0]
    rec = _Recorder(workload, cfg, cspec)
    device_seeded = cfg.start_points != "cosa"

    # ---- start generation: identical RNG stream to the other drivers
    # (host protocol), or deferred to per-chunk device kernels.
    starts = []
    if not device_seeded:
        with _obs.get_tracer().span("search.starts",
                                    n=cfg.n_start_points,
                                    layers=len(workload.layers)) as sp:
            rng = np.random.default_rng(cfg.seed)
            best_start_edp = float("inf")
            for _ in range(cfg.n_start_points):
                mappings, edp0, best_start_edp = _generate_start_point(
                    workload, cfg, rng, best_start_edp, rec)
                rec.best.start_edps.append(edp0)
                starts.append(mappings)
            sp.set(tries=rec.evals)     # one count per oracle-checked try

    seg_lens = _segment_lengths(cfg.steps, cfg.round_every)
    n_full, rem = divmod(cfg.steps, cfg.round_every)
    shards = auto_pop_shards(population, cfg.shards)

    for lo in range(0, cfg.n_start_points, population):
        n_real = min(population, cfg.n_start_points - lo)
        if device_seeded:
            # On-device seeding: the chunk never exists on host.  Keyed
            # by chunk index, so draws are independent of `population`
            # chunking of the same seed only across whole chunks — and
            # independent of `shards` entirely (the seeding program is
            # its own unsharded dispatch).
            from .mapping import seed_population
            mode = ("cosa" if cfg.start_points == "cosa-device"
                    else "random")
            _, theta, orders = seed_population(
                workload.dims_array(), population,
                jax.random.fold_in(jax.random.PRNGKey(cfg.seed), lo),
                spec=cspec, pe_cap=int(_pe_cap(cfg, cspec)), mode=mode)
        else:
            chunk = starts[lo:lo + population]
            rec.record_batch(*stack_population(chunk))
            # Satellite fix: pad the ragged final chunk to `population`
            # with replicas of its last member — per-member ops make
            # padding inert, one program shape serves every chunk.
            chunk = chunk + [chunk[-1]] * (population - n_real)
            theta = jnp.asarray(
                theta_from_population(chunk, cspec.free_mask),
                dtype=jnp.float32)
            orders = jnp.asarray(orders_from_population(chunk))
        if not seg_lens:
            continue

        tracer = _obs.get_tracer()
        # Async submission of the one fused program (GD + rounding +
        # ordering for every segment); the device work drains inside
        # the readback span below, where np.asarray blocks.
        with tracer.span("search.fused_dispatch", chunk=lo,
                         population=population, shards=shards,
                         n_full=n_full, rem=rem):
            theta, orders = shard_population(theta, orders, shards)
            (f_seg, o_seg, _), _best = run_fused(
                theta, orders, n_full=n_full, rem=rem,
                seg_len=cfg.round_every, shards=shards)

        # ---- final read-back + oracle replay (host-batched order);
        # gathered across shards once here, padded members skipped.
        with tracer.span("search.readback", chunk=lo):
            f_seg = np.asarray(f_seg, dtype=float)  # (S, P, L, 2, nl, 7)
            o_seg = np.asarray(o_seg)               # (S, P, L, n_levels)
        for s, n_steps in enumerate(seg_lens):
            with tracer.span("search.oracle", segment=s, chunk=lo,
                             path=rec.oracle_path) as sp:
                rec.count(n_steps * n_real)  # one sample per GD step
                improved = rec.improved
                rec.record_batch(f_seg[s, :n_real], o_seg[s, :n_real])
                sp.set(candidates=n_real, improved=rec.improved - improved)

    return rec.finish()
