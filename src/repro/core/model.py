"""DOSA's closed-form differentiable performance model (paper Sec. 4).

Implements, in pure `jax.numpy` (differentiable w.r.t. the tiling
factors `f`):

* per-level capacity requirements  (Eqs. 2-5),
* traffic: writes / updates / reads with spatial broadcast and
  reduction discounts                (Eqs. 6-11),
* roofline latency                   (Eq. 12),
* event-based energy with capacity-dependent SRAM energy-per-access
  (Eq. 13, Table 2),
* network EDP                        (Eq. 14),
* mapping-first minimal-hardware inference (Eq. 1, Fig. 3).

The model is *architecture-generic*: every function is parameterized by
a `CompiledSpec` (see `archspec.py`) carrying the memory-level chains,
tensor bindings, EPA/bandwidth models and ordering tables of the
target.  The original Gemmini-fixed entry points (`layer_metrics`,
`infer_hw`, `workload_eval`, ...) remain as thin wrappers over the
generic `*_spec` core specialized to `GEMMINI_SPEC`, so legacy call
sites and tests are unchanged — and are bit-for-bit the pre-spec
implementation.

Exact semantics (validated against the paper's Fig. 3 worked example and
mirrored by the independent iterative oracle in `oracle.py`):

  capacity   C[i,t] = prod_{d in size-dims(t)} ext(i,d)
             ext(i,d) = prod_{j<=i} f[T,j,d] * prod_{all j} f[S,j,d]
             (temporal loops at-or-below the level set the resident tile;
              spatial loops at *any* level multiply instances/banks);
             inputs use sliding-window extents
             Pin = wstride*(ext(P)-1)+ext(R), Qin likewise (Eq. 3).

  fills(t,i) = C[i,t] * prod of temporal factors at levels j>i that are
             at-or-outer-to the innermost t-relevant loop with factor>1,
             per the level loop orderings (Eq. 6).  No relevant outer
             loop => the tile is loaded exactly once.

  reads(t,i) = MACs / F_S,t(i)            at t's innermost level
             = fills(t, prev)/F_S,t(i)    above it          (Eqs. 10-11)
             F_S,t(i) = prod of spatial factors at level i of dims
             irrelevant to t (broadcast / spatial-reduction discount).

  outputs    updates(acc) = MACs / F_S,O(acc); a *residency* count
             Nres = fills(O, acc); read-modify-write reads =
             updates - Nres (first update of a residency hits a fresh
             slot); each residency drains once (backing updates = Nres,
             accumulator drain reads = Nres); partial-sum refetch
             traffic = Nres - |O| (zero when reduction loops stay inner)
             (Eqs. 8-9 plus Timeloop's first-touch correction).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .arch import ACC, NLEVELS, SP
from .archspec import (CompiledSpec, GEMMINI_SPEC, compile_spec,
                       ordering_combos_for)
from .mapping import ORDER_TABLE, SPATIAL, TEMPORAL
from .problem import C, K, N, P, Q, R, S, REL, I_T, O_T, W_T

_ORDER_TABLE_J = jnp.asarray(ORDER_TABLE)
_REL_J = jnp.asarray(REL.astype(np.float32))

_EPS = 1e-6


def _gemmini() -> CompiledSpec:
    return compile_spec(GEMMINI_SPEC)


# Tensor -> storage levels (from Table 4's B matrix), innermost first.
# Legacy constant; the generic path reads `cspec.tensor_levels`.
TENSOR_LEVELS = {W_T: (0, 2, 3), I_T: (2, 3), O_T: (1, 3)}


class LayerMetrics(NamedTuple):
    latency: jnp.ndarray          # cycles
    energy: jnp.ndarray           # pJ
    accesses: jnp.ndarray         # (n_levels,) per-level word accesses
    caps: jnp.ndarray             # (n_levels, 3) capacity requirement words
    macs: jnp.ndarray             # scalar
    compute_latency: jnp.ndarray  # cycles
    mem_latency: jnp.ndarray      # (n_levels,) per-level cycles


class SpecHW(NamedTuple):
    """Spec-generic hardware parameters: total PEs plus one capacity per
    memory level (entries of non-searched, unconstrained levels are
    +inf and never read — their EPA slope is zero)."""

    c_pe: jnp.ndarray       # total PEs (pe_dim^2)
    cap_words: jnp.ndarray  # (n_levels,) capacity words per level


# ---------------------------------------------------------------------------
# Capacities (architecture-independent: level count comes from f)
# ---------------------------------------------------------------------------

def _extents(f: jnp.ndarray) -> jnp.ndarray:
    """ext[i, d]: dimension-d extent of the tile resident at level i.
    f: (2, n_levels, 7)."""
    tcum = jnp.cumprod(f[TEMPORAL], axis=0)        # (n_levels, 7) j<=i
    sall = jnp.prod(f[SPATIAL], axis=0)            # (7,)   spatial all j
    return tcum * sall[None, :]


def capacities(f: jnp.ndarray, strides: jnp.ndarray) -> jnp.ndarray:
    """(n_levels, 3) words of tensor t resident at level i (Eqs. 2-5)."""
    ext = _extents(f)                              # (n_levels, 7)
    c_w = ext[:, R] * ext[:, S] * ext[:, C] * ext[:, K]
    pin = strides[0] * (ext[:, P] - 1.0) + ext[:, R]
    qin = strides[1] * (ext[:, Q] - 1.0) + ext[:, S]
    c_i = ext[:, C] * ext[:, N] * pin * qin
    c_o = ext[:, P] * ext[:, Q] * ext[:, K] * ext[:, N]
    return jnp.stack([c_w, c_i, c_o], axis=1)      # (n_levels, 3)


# ---------------------------------------------------------------------------
# Traffic
# ---------------------------------------------------------------------------

def _nest_above(f: jnp.ndarray, order: jnp.ndarray, level: int):
    """Flattened temporal loop nest strictly above `level`, innermost
    first.  Returns (factors, rel) with shapes (n, ) and (3, n)."""
    n_levels = f.shape[1]
    fs, rels = [], []
    for j in range(level + 1, n_levels):
        perm = jnp.take(_ORDER_TABLE_J, order[j], axis=0)      # (7,)
        fs.append(jnp.take(f[TEMPORAL, j], perm))              # (7,)
        rels.append(jnp.take(_REL_J, perm, axis=1))            # (3, 7)
    if not fs:
        return jnp.zeros((0,)), jnp.zeros((3, 0))
    return jnp.concatenate(fs), jnp.concatenate(rels, axis=1)


def _fill_multiplier(nest_f: jnp.ndarray, nest_rel: jnp.ndarray):
    """Masked product over the flattened nest (Eq. 6 reuse rule).
    nest_f: (n,), nest_rel: (n,) in {0,1}.  A loop's factor multiplies the
    fills iff the loop is relevant, or some relevant loop with factor > 1
    lies strictly inner to it."""
    active = nest_rel * (nest_f > 1.0 + _EPS)                  # (n,)
    seen_excl = jnp.cumsum(active) - active                    # strictly inner
    include = jnp.maximum(nest_rel, (seen_excl > 0.0))
    return jnp.prod(jnp.where(include > 0.0, nest_f, 1.0))


def spatial_discount(f: jnp.ndarray, tensor: int, level: int) -> jnp.ndarray:
    """F_S,t(i): product of spatial factors at `level` of dims irrelevant
    to `tensor` (Eqs. 8, 10)."""
    irrel = 1.0 - _REL_J[tensor]                               # (7,)
    return jnp.prod(jnp.where(irrel > 0.0, f[SPATIAL, level], 1.0))


def fills_spec(cspec: CompiledSpec, f: jnp.ndarray, order: jnp.ndarray,
               caps: jnp.ndarray) -> jnp.ndarray:
    """(n_levels, 3) fill (write-from-above) traffic per level/tensor."""
    out = jnp.zeros((cspec.n_levels, 3))
    for t, levels in cspec.tensor_levels.items():
        for i in levels:
            nest_f, nest_rel = _nest_above(f, order, i)
            mult = _fill_multiplier(nest_f, nest_rel[t]) if nest_f.shape[0] \
                else jnp.asarray(1.0)
            out = out.at[i, t].set(caps[i, t] * mult)
    return out


def fills(f: jnp.ndarray, order: jnp.ndarray, strides: jnp.ndarray,
          caps: jnp.ndarray) -> jnp.ndarray:
    """Legacy Gemmini entry point (`strides` kept for signature compat)."""
    return fills_spec(_gemmini(), f, order, caps)


class Traffic(NamedTuple):
    reads: jnp.ndarray      # (n_levels,) word reads per level
    writes: jnp.ndarray     # (n_levels,) word writes (fills + updates)
    accesses: jnp.ndarray   # (n_levels,) reads + writes


def traffic_spec(cspec: CompiledSpec, f: jnp.ndarray, order: jnp.ndarray,
                 caps: jnp.ndarray, macs: jnp.ndarray) -> Traffic:
    """Per-level read/write word traffic (Eqs. 6-11 + first-touch)."""
    fl = fills_spec(cspec, f, order, caps)
    n_levels, backing = cspec.n_levels, cspec.backing
    reads = jnp.zeros(n_levels)
    writes = jnp.zeros(n_levels)

    # --- read-only tensors W, I: fills go down the chain as reads above.
    for t in (W_T, I_T):
        levels = cspec.tensor_levels[t]
        inner = levels[0]
        reads = reads.at[inner].add(macs / spatial_discount(f, t, inner))
        for pos in range(1, len(levels)):
            i, prev = levels[pos], levels[pos - 1]
            reads = reads.at[i].add(fl[prev, t] / spatial_discount(f, t, i))
        for i in levels:
            if i != backing:            # data is born in DRAM; no fill there
                writes = writes.at[i].add(fl[i, t])

    # --- outputs: accumulate at `acc`, drain/refetch against backing.
    acc, top = cspec.tensor_levels[O_T]
    upd_acc = macs / spatial_discount(f, O_T, acc)   # Eq. 9, innermost
    nres = fl[acc, O_T]                              # residencies (words)
    osize = caps[top, O_T]                           # distinct output words
    refetch = jnp.maximum(nres - osize, 0.0)
    writes = writes.at[acc].add(upd_acc + refetch)   # updates + refetch fill
    reads = reads.at[acc].add((upd_acc - nres) + nres)  # RMW reads + drains
    writes = writes.at[top].add(nres)                # backing output updates
    reads = reads.at[top].add(refetch)               # backing partial refetch

    return Traffic(reads=reads, writes=writes, accesses=reads + writes)


def traffic(f: jnp.ndarray, order: jnp.ndarray, strides: jnp.ndarray,
            caps: jnp.ndarray, macs: jnp.ndarray) -> Traffic:
    """Legacy Gemmini entry point (`strides` kept for signature compat)."""
    return traffic_spec(_gemmini(), f, order, caps, macs)


# ---------------------------------------------------------------------------
# Latency / energy / EDP
# ---------------------------------------------------------------------------

def utilized_pes(f: jnp.ndarray) -> jnp.ndarray:
    return jnp.prod(f[SPATIAL])


def layer_c_pe_spec(cspec: CompiledSpec, f: jnp.ndarray) -> jnp.ndarray:
    """Eq. 1: square array sized by the largest free spatial factor."""
    if not cspec.spatial_sites:
        return jnp.asarray(1.0)
    side = f[SPATIAL, cspec.spatial_sites[0][0], cspec.spatial_sites[0][1]]
    for (lvl, d) in cspec.spatial_sites[1:]:
        side = jnp.maximum(side, f[SPATIAL, lvl, d])
    return side ** 2


def layer_c_pe(f: jnp.ndarray) -> jnp.ndarray:
    return layer_c_pe_spec(_gemmini(), f)


def layer_metrics_spec(cspec: CompiledSpec, f: jnp.ndarray,
                       order: jnp.ndarray, strides: jnp.ndarray,
                       c_pe: jnp.ndarray, cap_words) -> LayerMetrics:
    """Latency (Eq. 12) and energy (Eq. 13) of one layer's mapping given
    hardware parameters (which may be shared across layers).
    `cap_words` is indexable by level (array or list)."""
    caps = capacities(f, strides)
    macs = jnp.prod(f)
    tr = traffic_spec(cspec, f, order, caps, macs)
    n_levels = cspec.n_levels

    bw = cspec.bandwidth(c_pe)
    mem_lat = jnp.stack([tr.accesses[i] / bw[i] for i in range(n_levels)])
    compute_lat = macs / utilized_pes(f)
    latency = jnp.maximum(compute_lat, jnp.max(mem_lat))

    epa = cspec.epa(c_pe, cap_words)
    energy = macs * cspec.spec.epa_mac + sum(tr.accesses[i] * epa[i]
                                             for i in range(n_levels))
    return LayerMetrics(latency=latency, energy=energy,
                        accesses=tr.accesses, caps=caps, macs=macs,
                        compute_latency=compute_lat, mem_latency=mem_lat)


def layer_metrics(f: jnp.ndarray, order: jnp.ndarray, strides: jnp.ndarray,
                  c_pe: jnp.ndarray, acc_words: jnp.ndarray,
                  sp_words: jnp.ndarray) -> LayerMetrics:
    """Legacy Gemmini entry point."""
    return layer_metrics_spec(_gemmini(), f, order, strides, c_pe,
                              [0.0, acc_words, sp_words, 0.0])


class HWParams(NamedTuple):
    """Legacy Gemmini hardware parameters (see `SpecHW` for the
    spec-generic form)."""

    c_pe: jnp.ndarray       # total PEs (pe_dim^2)
    acc_words: jnp.ndarray  # accumulator capacity requirement, words
    sp_words: jnp.ndarray   # scratchpad capacity requirement, words


def _spec_hw_from_params(hw: HWParams) -> SpecHW:
    return SpecHW(c_pe=jnp.asarray(hw.c_pe),
                  cap_words=jnp.stack([
                      jnp.asarray(jnp.inf), jnp.asarray(hw.acc_words),
                      jnp.asarray(hw.sp_words), jnp.asarray(jnp.inf)]))


def _params_from_spec_hw(hw: SpecHW) -> HWParams:
    return HWParams(c_pe=hw.c_pe, acc_words=hw.cap_words[ACC],
                    sp_words=hw.cap_words[SP])


def infer_hw_spec(cspec: CompiledSpec, fs: jnp.ndarray,
                  strides: jnp.ndarray) -> SpecHW:
    """Mapping-first minimal hardware (Fig. 3): per-parameter max over
    layers.  Differentiable (max is subdifferentiable).
    fs: (L, 2, n_levels, 7), strides: (L, 2)."""
    caps = jax.vmap(capacities)(fs, strides)        # (L, n_levels, 3)
    if cspec.spec.fixed_pe_dim is not None:
        c_pe = jnp.asarray(float(cspec.spec.fixed_pe_dim) ** 2)
    else:
        c_pe = jnp.max(jax.vmap(lambda f: layer_c_pe_spec(cspec, f))(fs))
        c_pe = jnp.minimum(c_pe, float(cspec.spec.max_pe_dim) ** 2)
    cap_words = []
    fixed = dict(cspec.fixed_capacity)
    for i in range(cspec.n_levels):
        if i in cspec.searched_levels:
            req = sum(caps[:, i, t]
                      for t in range(3) if cspec.b_matrix[i, t])
            cap_words.append(jnp.max(req))          # B-masked (Eq. 5)
        elif i in fixed:
            cap_words.append(jnp.asarray(fixed[i]))
        else:
            cap_words.append(jnp.asarray(jnp.inf))
    return SpecHW(c_pe=c_pe, cap_words=jnp.stack(cap_words))


def infer_hw(fs: jnp.ndarray, strides: jnp.ndarray) -> HWParams:
    """Legacy Gemmini entry point."""
    return _params_from_spec_hw(infer_hw_spec(_gemmini(), fs, strides))


def workload_eval_spec(cspec: CompiledSpec, fs: jnp.ndarray,
                       orders: jnp.ndarray, strides: jnp.ndarray,
                       repeats: jnp.ndarray, hw: SpecHW | None = None):
    """Evaluate a whole network (Eq. 14).

    fs: (L, 2, n_levels, 7); orders: (L, n_levels); strides: (L, 2);
    repeats: (L,).  `hw=None` => mapping-first co-search mode (hardware
    inferred from the mappings, Eq. 1/Fig. 3).  Returns
    (edp, (energies, latencies, hw))."""
    if hw is None:
        hw = infer_hw_spec(cspec, fs, strides)
    metrics = jax.vmap(
        lambda f, o, s: layer_metrics_spec(cspec, f, o, s, hw.c_pe,
                                           hw.cap_words))(fs, orders, strides)
    energies = metrics.energy * repeats
    latencies = metrics.latency * repeats
    edp = jnp.sum(energies) * jnp.sum(latencies)
    return edp, (energies, latencies, hw)


def workload_eval(fs: jnp.ndarray, orders: jnp.ndarray, strides: jnp.ndarray,
                  repeats: jnp.ndarray, hw: HWParams | None = None):
    """Legacy Gemmini entry point (hardware in/out as `HWParams`)."""
    shw = _spec_hw_from_params(hw) if hw is not None else None
    edp, (en, lat, shw) = workload_eval_spec(_gemmini(), fs, orders, strides,
                                             repeats, hw=shw)
    return edp, (en, lat, _params_from_spec_hw(shw))


def workload_edp_spec(cspec, fs, orders, strides, repeats,
                      hw: SpecHW | None = None):
    return workload_eval_spec(cspec, fs, orders, strides, repeats, hw)[0]


def workload_edp(fs, orders, strides, repeats, hw: HWParams | None = None):
    return workload_eval(fs, orders, strides, repeats, hw)[0]


# ---------------------------------------------------------------------------
# Population-axis entry points (batched multi-start search): the same
# closed-form model lifted one axis higher with vmap, so a whole
# population of candidate workload mappings evaluates as one device
# program.
# ---------------------------------------------------------------------------

def infer_hw_population_spec(cspec: CompiledSpec, fs: jnp.ndarray,
                             strides: jnp.ndarray) -> SpecHW:
    """Mapping-first minimal hardware for each population member.
    fs: (P, L, 2, n_levels, 7).  Returns SpecHW with (P,)/(P, n_levels)
    leaves."""
    return jax.vmap(lambda f: infer_hw_spec(cspec, f, strides))(fs)


def infer_hw_population(fs: jnp.ndarray, strides: jnp.ndarray) -> HWParams:
    """Legacy Gemmini entry point: HWParams with (P,) leaves."""
    return jax.vmap(infer_hw, in_axes=(0, None))(fs, strides)


def population_eval_spec(cspec: CompiledSpec, fs: jnp.ndarray,
                         orders: jnp.ndarray, strides: jnp.ndarray,
                         repeats: jnp.ndarray, hw: SpecHW | None = None):
    """Evaluate a population of workload mappings (Eq. 14 per member).

    fs: (P, L, 2, n_levels, 7); orders: (P, L, n_levels).  `hw=None`
    infers minimal hardware per member (co-search mode); a scalar-leaf
    SpecHW is shared across the population."""
    return jax.vmap(
        lambda f, o: workload_eval_spec(cspec, f, o, strides, repeats,
                                        hw=hw))(fs, orders)


def population_eval(fs: jnp.ndarray, orders: jnp.ndarray,
                    strides: jnp.ndarray, repeats: jnp.ndarray,
                    hw: HWParams | None = None):
    """Legacy Gemmini entry point.  Returns (edps (P,), (energies (P, L),
    latencies (P, L), hw with (P,) leaves))."""
    return jax.vmap(
        lambda f, o: workload_eval(f, o, strides, repeats, hw=hw))(fs, orders)


def population_edp_spec(cspec, fs, orders, strides, repeats,
                        hw: SpecHW | None = None) -> jnp.ndarray:
    return population_eval_spec(cspec, fs, orders, strides, repeats, hw)[0]


def population_edp(fs, orders, strides, repeats,
                   hw: HWParams | None = None) -> jnp.ndarray:
    """(P,) network EDPs of a population of candidate mappings."""
    return population_eval(fs, orders, strides, repeats, hw=hw)[0]


class PopulationBest(NamedTuple):
    """Per-member running best of a population search, carried through a
    device-resident scan (the fused engine's best-EDP tracking): the
    lowest model EDP seen so far plus the candidate that achieved it."""

    edp: jnp.ndarray      # (P,) best model EDP per member
    f: jnp.ndarray        # (P, L, 2, n_levels, 7) best factor tensors
    orders: jnp.ndarray   # (P, L, n_levels) best ordering choices


def population_best_init(f: jnp.ndarray,
                         orders: jnp.ndarray) -> PopulationBest:
    """Empty best-tracking state shaped like one population candidate
    (+inf EDP, so the first update always takes)."""
    # Every leaf is derived from `f`/`orders` so that under `shard_map`
    # the state is varying over the member axis, as the scan carry
    # that updates it is.
    return PopulationBest(edp=jnp.full_like(f, jnp.inf, shape=f.shape[:1]),
                          f=jnp.zeros_like(f),
                          orders=jnp.zeros_like(orders))


def population_best_update(best: PopulationBest, edp: jnp.ndarray,
                           f: jnp.ndarray,
                           orders: jnp.ndarray) -> PopulationBest:
    """Elementwise best-EDP tracking: keep each member's incumbent
    unless the new candidate strictly improves it.  Pure/jittable — the
    fused engine folds this over its rounding points so the running
    best lives on device for the whole search."""
    take = edp < best.edp                                  # (P,)

    def sel(new, old, t):
        return jnp.where(
            t.reshape(t.shape + (1,) * (new.ndim - 1)), new, old)
    return PopulationBest(edp=jnp.where(take, edp, best.edp),
                          f=sel(f, best.f, take),
                          orders=sel(orders, best.orders, take))


# ---------------------------------------------------------------------------
# Validity penalty (Eq. 18) and fixed-hardware capacity penalties
# ---------------------------------------------------------------------------

def validity_penalty(fs: jnp.ndarray) -> jnp.ndarray:
    """sum max(1 - f, 0) over all factors (Sec. 5.3.3)."""
    return jnp.sum(jnp.maximum(1.0 - fs, 0.0))


def capacity_penalty_spec(cspec: CompiledSpec, fs: jnp.ndarray,
                          strides: jnp.ndarray, hw: SpecHW) -> jnp.ndarray:
    """Relative overflow of fixed buffers — used when hardware is frozen
    (Sec. 6.5: buffer-size/mapping-only search)."""
    caps = jax.vmap(capacities)(fs, strides)
    constrained = tuple(cspec.searched_levels) + tuple(
        i for (i, _) in cspec.fixed_capacity)
    pe = jax.vmap(lambda f: layer_c_pe_spec(cspec, f))(fs)
    over = jnp.maximum(pe / hw.c_pe - 1.0, 0.0)
    for i in constrained:
        req = sum(caps[:, i, t] for t in range(3) if cspec.b_matrix[i, t])
        over = over + jnp.maximum(req / hw.cap_words[i] - 1.0, 0.0)
    return jnp.sum(over)


def capacity_penalty(fs: jnp.ndarray, strides: jnp.ndarray,
                     hw: HWParams) -> jnp.ndarray:
    """Legacy Gemmini entry point."""
    return capacity_penalty_spec(_gemmini(), fs, strides,
                                 _spec_hw_from_params(hw))


# ---------------------------------------------------------------------------
# Loop-ordering enumeration helpers (Sec. 5.2)
# ---------------------------------------------------------------------------

def ordering_combos() -> np.ndarray:
    """(27, 4) all per-level ordering choices for levels ACC/SP/DRAM
    (the register level's ordering never affects traffic).  The array
    is cached and READ-ONLY — copy before mutating."""
    return ordering_combos_for(NLEVELS)


def layer_el_all_orderings_spec(cspec: CompiledSpec, f, strides, c_pe,
                                cap_words):
    """Energy & latency of one layer under all 3**(n_levels-1) ordering
    combos.  Returns (energies, latencies), each (n_combos,)."""
    combos = jnp.asarray(cspec.combos)
    m = jax.vmap(lambda o: layer_metrics_spec(cspec, f, o, strides, c_pe,
                                              cap_words))(combos)
    return m.energy, m.latency


def layer_el_all_orderings(f, strides, c_pe, acc_words, sp_words):
    """Legacy Gemmini entry point: all 27 combos."""
    return layer_el_all_orderings_spec(_gemmini(), f, strides, c_pe,
                                       [0.0, acc_words, sp_words, 0.0])


def layer_el_all_orderings_population_spec(cspec: CompiledSpec,
                                           fs_pop: jnp.ndarray,
                                           strides: jnp.ndarray,
                                           hws: SpecHW):
    """Energy & latency of every layer of every population member under
    all ordering combos, as one batched computation.  fs_pop:
    (P, L, 2, n_levels, 7); hws: SpecHW with (P,)/(P, n_levels) leaves.
    Returns (energies, latencies), each (P, L, n_combos)."""
    def per_member(fs, s, c, w):
        return jax.vmap(
            lambda f, st_: layer_el_all_orderings_spec(
                cspec, f, st_, c, w))(fs, s)
    return jax.vmap(per_member, in_axes=(0, None, 0, 0))(
        fs_pop, strides, hws.c_pe, hws.cap_words)


def layer_el_all_orderings_population(fs_pop: jnp.ndarray,
                                      strides: jnp.ndarray, hws: HWParams):
    """Legacy Gemmini entry point.  hws: HWParams with (P,) leaves.
    Returns (energies, latencies), each (P, L, 27)."""
    def per_member(fs, s, c, a, w):
        return jax.vmap(
            lambda f, st_: layer_el_all_orderings(f, st_, c, a, w))(fs, s)
    return jax.vmap(per_member, in_axes=(0, None, 0, 0, 0))(
        fs_pop, strides, hws.c_pe, hws.acc_words, hws.sp_words)
