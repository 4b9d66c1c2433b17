"""Plain reference for the co-search answers: an independent oracle.

A search answer is a claim: "these integer mappings tile the workload's
layers on this accelerator, and their EDP is `best_edp`".  This module
checks such a claim from the configuration file alone.  It imports
nothing of the program: the accelerator's tables (levels, tensor
bindings, energy per access, bandwidth, spatial sites) are read from
`bench/configs/<config>.json`, and the loop-nest cost model is written
out here from its description (DOSA Sec. 4; Timeloop-style reuse by a
walk over each level's loop order; backing-store traffic in blocks).

`dtype` selects the arithmetic: float64 is the reference, float32 is
the control (the nearest precision below what the configuration
states), which a sound comparison has to tell apart from it.
"""
from __future__ import annotations

import math

import numpy as np

DIMS = ("R", "S", "P", "Q", "C", "K", "N")
R, S, P, Q, C, K, N = range(7)
TENSORS = ("W", "I", "O")
W_T, I_T, O_T = range(3)
SPATIAL, TEMPORAL = 0, 1

# Which dims index which tensor (weights R,S,C,K; inputs R,S,P,Q,C,N;
# outputs P,Q,K,N).
RELEVANT = {W_T: {R, S, C, K}, I_T: {R, S, P, Q, C, N}, O_T: {P, Q, K, N}}

# The three loop orders of a level, innermost dim first: weight-,
# input- and output-stationary (DOSA Sec. 5.2).
LOOP_ORDERS = ((P, Q, N, R, S, C, K),
               (K, R, S, P, Q, C, N),
               (R, S, C, P, Q, K, N))


class Spec:
    """The accelerator tables of a configuration file's `spec`."""

    def __init__(self, d: dict):
        self.levels = d["levels"]
        self.n = len(self.levels)
        self.backing = self.n - 1
        self.binds = [[t in lvl["tensors"] for t in TENSORS]
                      for lvl in self.levels]
        self.chain = {t: [i for i in range(self.n) if self.binds[i][t]]
                      for t in range(3)}
        self.sites = [(int(lvl), DIMS.index(dim))
                      for lvl, dim in d["spatial_sites"]]
        self.level0_dims = {DIMS.index(x) for x in d["level0_temporal_dims"]}
        self.epa_mac = d["epa_mac"]
        self.max_pe = d["max_pe_dim"]
        self.fixed_pe = d["fixed_pe_dim"]
        self.block = d["dram_block_words"]


def _extent(f, level: int, dim: int) -> int:
    """Extent of `dim` in the tile held at `level`: temporal factors at
    or below it times every spatial factor."""
    ext = 1
    for j in range(level + 1):
        ext *= int(f[TEMPORAL][j][dim])
    for j in range(len(f[SPATIAL])):
        ext *= int(f[SPATIAL][j][dim])
    return ext


def _tile_words(f, level: int, layer: dict) -> tuple[int, int, int]:
    ext = [_extent(f, level, d) for d in range(7)]
    w = ext[R] * ext[S] * ext[C] * ext[K]
    rows = layer["wstride"] * (ext[P] - 1) + ext[R]
    cols = layer["hstride"] * (ext[Q] - 1) + ext[S]
    i = ext[C] * ext[N] * rows * cols
    o = ext[P] * ext[Q] * ext[K] * ext[N]
    return w, i, o


def _refills(f, order, level: int, tensor: int) -> int:
    """How often the tile of `tensor` at `level` is filled: the product
    of the temporal loops above it that are relevant to the tensor, and
    of irrelevant loops outside a relevant loop of factor > 1."""
    mult = 1
    inside_relevant = False
    for j in range(level + 1, len(f[TEMPORAL])):
        for dim in LOOP_ORDERS[int(order[j])]:
            fac = int(f[TEMPORAL][j][dim])
            if dim in RELEVANT[tensor]:
                mult *= fac
                inside_relevant = inside_relevant or fac > 1
            elif inside_relevant:
                mult *= fac
    return mult


def _broadcast(f, level: int, tensor: int) -> int:
    """Spatial fan-out at `level` over dims the tensor does not index:
    one read there feeds that many PEs."""
    out = 1
    for dim in range(7):
        if dim not in RELEVANT[tensor]:
            out *= int(f[SPATIAL][level][dim])
    return out


def tiled_dims(f) -> list[int]:
    """Per dim, the product of a mapping's factors: the extent it tiles."""
    arr = np.asarray(f, dtype=np.float64)
    return [int(np.prod(arr[:, :, d])) for d in range(7)]


def check_mapping(spec: Spec, f, dims, at_least: bool = False) -> str:
    """'' if `f` (2, levels, 7) is a valid integer mapping of `dims` on
    `spec`, else the first rule it breaks.  The factors of each dim
    multiply to the dim exactly, or with `at_least` to the dim or more
    (a problem padded up before it was searched)."""
    arr = np.asarray(f, dtype=np.float64)
    if arr.shape != (2, spec.n, 7):
        return f"shape {arr.shape}"
    if np.any(arr != np.round(arr)) or np.any(arr < 1):
        return "a factor is not a positive integer"
    site_set = set(spec.sites)
    for lvl in range(spec.n):
        for d in range(7):
            if arr[SPATIAL, lvl, d] != 1 and (lvl, d) not in site_set:
                return f"spatial factor off the dataflow's sites ({lvl},{d})"
    tiled = tiled_dims(arr)
    for d in range(7):
        if d not in spec.level0_dims and arr[TEMPORAL, 0, d] != 1:
            return f"temporal factor of {DIMS[d]} at the registers"
        short = tiled[d] < int(dims[d])
        if short or (tiled[d] != int(dims[d]) and not at_least):
            return (f"factors of {DIMS[d]} multiply to {tiled[d]}, "
                    f"not {dims[d]}")
    side = max([int(arr[SPATIAL, lvl, d]) for lvl, d in spec.sites],
               default=1)
    if side > spec.max_pe:
        return f"PE array side {side} over {spec.max_pe}"
    return ""


def layer_cost(spec: Spec, f, order, layer: dict, dtype=np.float64):
    """(energy, latency) of one layer's mapping, each layer on the
    smallest hardware its own mapping needs; None where the mapping
    overflows a fixed-capacity level."""
    fl = dtype
    f = np.asarray(f).astype(np.int64).tolist()
    tiles = [_tile_words(f, i, layer) for i in range(spec.n)]
    side = max([f[SPATIAL][lvl][d] for lvl, d in spec.sites], default=1)
    side = spec.fixed_pe or side
    c_pe = fl(side * side)
    words = []
    for i, lvl in enumerate(spec.levels):
        need = sum(tiles[i][t] for t in range(3) if spec.binds[i][t])
        if lvl["size_words"] is not None:
            if need > lvl["size_words"]:
                return None
            words.append(fl(lvl["size_words"]))
        elif lvl["searched"]:
            words.append(fl(need))
        else:
            words.append(None)

    macs = fl(math.prod(int(x) for x in layer["dims"]))
    acc = [fl(0.0)] * spec.n
    dram = []
    for t in (W_T, I_T):
        chain = spec.chain[t]
        acc[chain[0]] += macs / fl(_broadcast(f, chain[0], t))
        for pos in range(1, len(chain)):
            lo, hi = chain[pos - 1], chain[pos]
            moved = fl(tiles[lo][t] * _refills(f, order, lo, t)) \
                / fl(_broadcast(f, hi, t))
            acc[hi] += moved
            if hi == spec.backing:
                dram.append(moved)
        for i in chain:
            if i != spec.backing:
                acc[i] += fl(tiles[i][t] * _refills(f, order, i, t))
    inner, top = spec.chain[O_T]
    updates = macs / fl(_broadcast(f, inner, O_T))
    spills = fl(tiles[inner][O_T] * _refills(f, order, inner, O_T))
    refetch = max(spills - fl(tiles[top][O_T]), fl(0.0))
    # accumulator: every update written, read back on all but the first
    # write of each spill; refetched partial sums written again.
    acc[inner] += (updates + refetch) + updates
    acc[top] += spills + refetch
    dram += [spills, refetch]
    block = spec.block
    acc[spec.backing] = fl(sum(math.ceil(float(x) / block) * block
                               for x in dram if x > 0))

    latency = macs / fl(math.prod(f[SPATIAL][lvl][d]
                                  for lvl, d in spec.sites))
    energy = macs * fl(spec.epa_mac)
    for i, lvl in enumerate(spec.levels):
        bw = lvl["bandwidth"]
        if bw["kind"] == "pe_linear":
            rate = fl(bw["coeff"]) * c_pe
        elif bw["kind"] == "pe_sqrt":
            rate = fl(bw["coeff"]) * np.sqrt(c_pe)
        else:
            rate = fl(bw["coeff"])
        latency = max(latency, acc[i] / rate)
        epa = lvl["epa"]
        pj = fl(epa["base"])
        if epa["slope"]:
            kb = words[i] * fl(lvl["word_bytes"]) / fl(1024.0)
            scale = np.sqrt(c_pe) if epa["pe_scaled"] else fl(1.0)
            pj = pj + fl(epa["slope"]) * kb / scale
        energy = energy + acc[i] * pj
    return energy, latency


def network_edp(spec: Spec, mappings, layers, dtype=np.float64) -> float:
    """EDP of a network: energies and latencies summed over layers,
    each scaled by its repeat count, then multiplied; inf where any
    layer's mapping is invalid."""
    fl = dtype
    e_tot, l_tot = fl(0.0), fl(0.0)
    for (f, order), layer in zip(mappings, layers):
        if check_mapping(spec, f, layer["dims"]):
            return math.inf
        cost = layer_cost(spec, f, order, layer, dtype)
        if cost is None:
            return math.inf
        e_tot = e_tot + cost[0] * fl(layer["repeat"])
        l_tot = l_tot + cost[1] * fl(layer["repeat"])
    return float(e_tot * l_tot)


def tiled_layers(mappings, layers: list[dict]) -> list[dict]:
    """The layers as the mappings tile them: each dim the product of its
    factors (the sent dim, or more where the problem was padded)."""
    return [dict(lay, dims=tiled_dims(f))
            for (f, _), lay in zip(mappings, layers)]


def judge(spec: Spec, answer: dict, layers: list[dict],
          at_least: bool = False) -> dict:
    """Compare one answer against the reference.

    `answer` holds `best_edp`, `n_evals`, `history` ([[evals, edp]...]),
    `mappings` ([(f, order)] per layer) and the request's `protocol`
    (steps, round_every, n_start_points, max_reject_tries, device
    seeded or not); `layers` are the layers as the request sent them.
    With `at_least` a mapping may tile a dim larger than sent, and is
    priced on the dims it tiles.  Returns the numbers compared:

    * `invalid`: layers whose mapping breaks the spec (limit 0);
    * `edp_gap`: |best_edp - reference EDP| / reference EDP;
    * `accounting`: count of broken sample-accounting rules (limit 0).
    """
    proto = answer["protocol"]
    maps = answer["mappings"]
    invalid = sum(1 for (f, _), lay in zip(maps, layers)
                  if check_mapping(spec, f, lay["dims"], at_least))
    invalid += abs(len(maps) - len(layers))
    ref = network_edp(spec, maps, tiled_layers(maps, layers))
    got = float(answer["best_edp"])
    if invalid == 0 and math.isfinite(ref) and ref > 0 \
            and math.isfinite(got):
        gap = abs(got - ref) / ref
    else:
        gap = math.inf
    return {"invalid": invalid, "edp_gap": gap,
            "accounting": accounting_faults(answer, proto)}


def accounting_faults(answer: dict, proto: dict) -> int:
    """Rules of DOSA's sample count (Sec. 6.3): every GD member-step
    and every oracle evaluation is one sample; the history has one
    entry per oracle-evaluated candidate, its sample index rising and
    its best EDP never rising, ending at `best_edp`."""
    steps, every = proto["steps"], proto["round_every"]
    starts = proto["n_start_points"]
    segments = -(-steps // every)
    hist = answer["history"]
    faults = 0
    records = starts * segments + (0 if proto["device_seeded"] else starts)
    if len(hist) != records:
        faults += 1
    base = starts * steps + records
    n = int(answer["n_evals"])
    if proto["device_seeded"]:
        faults += n != base
    else:
        tries = proto["max_reject_tries"]
        faults += not (base + starts <= n <= base + starts * tries)
    evals = [int(e) for e, _ in hist]
    best = [float(v) for _, v in hist]
    faults += any(b <= a for a, b in zip(evals, evals[1:]))
    faults += any(b > a for a, b in zip(best, best[1:]))
    faults += bool(hist) and (best[-1] != float(answer["best_edp"])
                              or evals[-1] > n)
    return int(faults)
