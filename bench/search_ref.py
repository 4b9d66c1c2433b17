"""Plain reference of the search itself: DOSA's one loop, in float64.

`reference.py` checks that an answer is what it claims to be (valid
mappings, their true EDP, the sample count).  This module checks what
the search found: it re-runs a device-seeded search from its request
(workload, protocol, seed) and returns the best oracle EDP that the
one loop reaches, for the answer's `best_edp` to be compared with.

It imports nothing of the program.  Everything comes from the
configuration file (the accelerator's tables, the layers, the
protocol's constants) and from DOSA's description:

* start points (device CoSA seeding): per (member, layer, dim) the
  factor sites are walked innermost first; a spatial site takes the
  largest divisor of the remaining quotient within the PE bound, a
  temporal site the floor(u * n)-th of the n divisors of the
  remaining quotient, u a float32 uniform drawn from the request's
  seed with JAX's threefry (key `fold_in(PRNGKey(seed), 0)`, split in
  two: factor and ordering draws);
* gradient descent (Sec. 5.3): Adam over the log tiling factors at the
  free sites, the backing store's factor inferred, on log EDP of the
  differentiable model (Sec. 4, Eqs. 2-14) under mapping-first minimal
  hardware (per-parameter max over layers, Eq. 1 / Fig. 3), plus the
  penalty weight times the validity (Eq. 18), PE-bound and
  fixed-capacity overflow terms; fresh moments after every rounding;
* rounding (Sec. 5.3.2): each factor to the nearest divisor of the
  remaining quotient (ties to the smaller; spatial ones within the PE
  bound), innermost first, the backing store taking the rest;
* loop orderings (Sec. 5.2.1): two passes of coordinate descent over
  each layer's per-level orderings against the network's EDP;
* every rounded candidate is priced by the oracle
  (`reference.network_edp`); the best is the lowest.

The descent runs in float64 on the host's CPU through JAX's autodiff
(`jax.enable_x64`), after the measured window; a gradient written out
by hand for this model would be a second program to check.
"""
from __future__ import annotations

import functools
import itertools
import math

import numpy as np

import reference as ref

R, S, P, Q, C, K, N = range(7)
SPATIAL, TEMPORAL = ref.SPATIAL, ref.TEMPORAL
REL = np.array([[d in ref.RELEVANT[t] for d in range(7)] for t in range(3)])
ORDERS = np.array(ref.LOOP_ORDERS)          # (3, 7) innermost dim first
FACTOR_ABOVE_ONE = 1e-6     # a loop counts as a real loop above this


class Tables:
    """The spec's static tables as the one loop uses them."""

    def __init__(self, spec: dict):
        self.s = ref.Spec(spec)
        s = self.s
        n = s.n
        self.n = n
        self.cap = int(s.fixed_pe or s.max_pe)
        self.sites_per_dim = []
        site_set = set(s.sites)
        for d in range(7):
            sites = []
            for lvl in range(s.backing):
                if (lvl, d) in site_set:
                    sites.append((SPATIAL, lvl))
                if lvl > 0 or d in s.level0_dims:
                    sites.append((TEMPORAL, lvl))
            self.sites_per_dim.append(sites)
        self.s_max = max(len(x) for x in self.sites_per_dim)
        free = np.zeros((2, n, 7), dtype=bool)
        free[TEMPORAL, 1:s.backing, :] = True
        free[TEMPORAL, 0, sorted(s.level0_dims)] = True
        for lvl, d in s.sites:
            free[SPATIAL, lvl, d] = True
        self.free = free
        self.combos = np.array([(0,) + rest for rest in
                                itertools.product(range(3), repeat=n - 1)])
        self.searched = [i for i, lvl in enumerate(s.levels)
                         if lvl["searched"]]
        self.fixed = [(i, float(lvl["size_words"]))
                      for i, lvl in enumerate(s.levels)
                      if lvl["size_words"] is not None]


@functools.lru_cache(maxsize=None)
def _divisors(n: int) -> list[int]:
    small = [d for d in range(1, math.isqrt(n) + 1) if n % d == 0]
    return small + [n // d for d in reversed(small) if d * d != n]


def seed_uniforms(seed: int, members: int, n_layers: int, tab: Tables):
    """The float32 uniforms the request's seed gives: one per (member,
    layer, dim, site) for the factors, one per (member, layer, level)
    for the orderings."""
    import jax
    import jax.numpy as jnp
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 0)
    kf, ko = jax.random.split(key)
    u_f = jax.random.uniform(kf, (members, n_layers, 7, tab.s_max),
                             dtype=jnp.float32)
    u_o = jax.random.uniform(ko, (members, n_layers, tab.n),
                             dtype=jnp.float32)
    return np.asarray(u_f), np.asarray(u_o)


def cosa_starts(dims: np.ndarray, u_f, u_o, tab: Tables):
    """Start points: integer factors (M, L, 2, n, 7) and orderings."""
    m_count, n_layers = u_f.shape[0], u_f.shape[1]
    f = np.ones((m_count, n_layers, 2, tab.n, 7))
    for li in range(n_layers):
        for d in range(7):
            for m in range(m_count):
                rest = int(dims[li, d])
                for si, (k, lvl) in enumerate(tab.sites_per_dim[d]):
                    divs = _divisors(rest)
                    if k == SPATIAL:
                        pick = [x for x in divs if x <= tab.cap][-1]
                    else:
                        j = int(u_f[m, li, d, si] * np.float32(len(divs)))
                        pick = divs[min(j, len(divs) - 1)]
                    f[m, li, k, lvl, d] = pick
                    rest //= pick
                f[m, li, TEMPORAL, tab.s.backing, d] = rest
    orders = np.minimum((u_o * np.float32(3)).astype(np.int64), 2)
    return f, orders


def round_population(f_cont: np.ndarray, dims: np.ndarray, tab: Tables):
    """Nearest-divisor rounding of (M, L, 2, n, 7) continuous factors."""
    m_count, n_layers = f_cont.shape[0], f_cont.shape[1]
    out = np.ones_like(f_cont)
    for li in range(n_layers):
        for d in range(7):
            divs = np.array(_divisors(int(dims[li, d])), dtype=np.int64)
            rest = np.full(m_count, int(dims[li, d]), dtype=np.int64)
            for k, lvl in tab.sites_per_dim[d]:
                ok = rest[:, None] % divs[None, :] == 0
                if k == SPATIAL:
                    ok &= divs[None, :] <= tab.cap
                dist = np.abs(divs[None, :] - f_cont[:, li, k, lvl, d, None])
                idx = np.argmin(np.where(ok, dist, np.inf), axis=1)
                val = divs[idx]
                out[:, li, k, lvl, d] = val
                rest //= val
            out[:, li, TEMPORAL, tab.s.backing, d] = rest
    return out


# ------------------------------------------------------------ the model

def _model(tab: Tables, strides, repeats):
    """Functions of the differentiable model, over one member's
    (L, 2, n, 7) factors and (L, n) orderings, in jax.numpy."""
    import jax
    import jax.numpy as jnp
    s = tab.s
    n = tab.n
    rel = jnp.asarray(REL.astype(np.float64))
    order_tab = jnp.asarray(ORDERS)

    def caps(f):                                   # (L, n, 3)
        ext = jnp.cumprod(f[:, TEMPORAL], axis=1) \
            * jnp.prod(f[:, SPATIAL], axis=1)[:, None, :]
        w = ext[..., R] * ext[..., S] * ext[..., C] * ext[..., K]
        rows = strides[:, 0, None] * (ext[..., P] - 1.0) + ext[..., R]
        cols = strides[:, 1, None] * (ext[..., Q] - 1.0) + ext[..., S]
        i = ext[..., C] * ext[..., N] * rows * cols
        o = ext[..., P] * ext[..., Q] * ext[..., K] * ext[..., N]
        return jnp.stack([w, i, o], axis=-1)

    def fill_mult(f, orders, level, t):            # (L,)
        fs, rs = [], []
        for j in range(level + 1, n):
            perm = order_tab[orders[:, j]]                   # (L, 7)
            fs.append(jnp.take_along_axis(f[:, TEMPORAL, j], perm, axis=1))
            rs.append(rel[t][perm])
        nest, nrel = jnp.concatenate(fs, 1), jnp.concatenate(rs, 1)
        active = nrel * (nest > 1.0 + FACTOR_ABOVE_ONE)
        inner = jnp.cumsum(active, axis=1) - active
        take = (nrel > 0) | (inner > 0)
        return jnp.prod(jnp.where(take, nest, 1.0), axis=1)

    def discount(f, t, level):                     # (L,)
        irr = REL[t] == 0
        return jnp.prod(jnp.where(jnp.asarray(irr), f[:, SPATIAL, level],
                                  1.0), axis=1)

    def accesses(f, orders, cp):                   # (L, n)
        macs = jnp.prod(f, axis=(1, 2, 3))
        fills = {}
        for t in range(3):
            for i in s.chain[t]:
                fills[i, t] = cp[:, i, t] * (fill_mult(f, orders, i, t)
                                             if i < n - 1 else 1.0)
        acc = [jnp.zeros_like(macs) for _ in range(n)]
        for t in (ref.W_T, ref.I_T):
            chain = s.chain[t]
            acc[chain[0]] = acc[chain[0]] + macs / discount(f, t, chain[0])
            for pos in range(1, len(chain)):
                lo, hi = chain[pos - 1], chain[pos]
                acc[hi] = acc[hi] + fills[lo, t] / discount(f, t, hi)
            for i in chain:
                if i != s.backing:
                    acc[i] = acc[i] + fills[i, t]
        inner, top = s.chain[ref.O_T]
        upd = macs / discount(f, ref.O_T, inner)
        nres = fills[inner, ref.O_T]
        refetch = jnp.maximum(nres - cp[:, top, ref.O_T], 0.0)
        acc[inner] = acc[inner] + (upd + refetch) + upd
        acc[top] = acc[top] + nres + refetch
        return jnp.stack(acc, axis=1), macs

    def side(f):                                   # (L,)
        return jnp.max(jnp.stack([f[:, SPATIAL, lvl, d]
                                  for lvl, d in s.sites]), axis=0)

    def infer_hw(f, cp):
        if s.fixed_pe:
            c_pe = jnp.asarray(float(s.fixed_pe) ** 2)
        else:
            c_pe = jnp.minimum(jnp.max(side(f)) ** 2, float(s.max_pe) ** 2)
        words = []
        fixed = dict(tab.fixed)
        for i in range(n):
            if i in tab.searched:
                need = sum(cp[:, i, t] for t in range(3) if s.binds[i][t])
                words.append(jnp.max(need))
            elif i in fixed:
                words.append(jnp.asarray(fixed[i]))
            else:
                words.append(None)
        return c_pe, words

    def energy_latency(f, orders, c_pe, words):    # (L,), (L,)
        cp = caps(f)
        acc, macs = accesses(f, orders, cp)
        lat = macs / jnp.prod(f[:, SPATIAL], axis=(1, 2))
        energy = macs * s.epa_mac
        for i, lvl in enumerate(s.levels):
            bw = lvl["bandwidth"]
            if bw["kind"] == "pe_linear":
                rate = bw["coeff"] * c_pe
            elif bw["kind"] == "pe_sqrt":
                rate = bw["coeff"] * jnp.sqrt(c_pe)
            else:
                rate = bw["coeff"]
            lat = jnp.maximum(lat, acc[:, i] / rate)
            epa = lvl["epa"]
            pj = epa["base"]
            if epa["slope"]:
                kb = words[i] * lvl["word_bytes"] / 1024.0
                pj = pj + epa["slope"] * kb / (jnp.sqrt(c_pe)
                                               if epa["pe_scaled"] else 1.0)
            energy = energy + acc[:, i] * pj
        return energy, lat

    def loss(f, orders, penalty_weight):
        cp = caps(f)
        c_pe, words = infer_hw(f, cp)
        e, lat = energy_latency(f, orders, c_pe, words)
        edp = jnp.sum(e * repeats) * jnp.sum(lat * repeats)
        pen = jnp.sum(jnp.maximum(1.0 - f, 0.0))
        for lvl, d in s.sites:
            pen = pen + jnp.sum(jnp.maximum(
                f[:, SPATIAL, lvl, d] / tab.cap - 1.0, 0.0))
        for i, size in tab.fixed:
            need = sum(cp[:, i, t] for t in range(3) if s.binds[i][t])
            pen = pen + jnp.sum(jnp.maximum(need / size - 1.0, 0.0))
        return jnp.log(edp) + penalty_weight * pen

    def all_orderings(f):
        """Repeat-scaled energy and latency of every layer under every
        ordering combo, on the hardware the mappings need."""
        c_pe, words = infer_hw(f, caps(f))
        combos = jnp.asarray(tab.combos)

        def one(combo):
            orders = jnp.broadcast_to(combo, (f.shape[0], n))
            return energy_latency(f, orders, c_pe, words)
        e, lat = jax.vmap(one)(combos)              # (n_combos, L)
        return e.T * repeats[:, None], lat.T * repeats[:, None]

    return loss, all_orderings


@functools.lru_cache(maxsize=8)
def _programs(spec_key: str, layers_key: str, lr: float, pw: float,
              betas: tuple):
    """The jitted float64 pieces of one (spec, workload, constants)."""
    import json

    import jax
    import jax.numpy as jnp
    tab = Tables(json.loads(spec_key))
    layers = json.loads(layers_key)
    dims = jnp.asarray([lay["dims"] for lay in layers], dtype=jnp.float64)
    strides = jnp.asarray([[lay["wstride"], lay["hstride"]]
                           for lay in layers], dtype=jnp.float64)
    repeats = jnp.asarray([lay["repeat"] for lay in layers],
                          dtype=jnp.float64)
    loss, all_orderings = _model(tab, strides, repeats)
    free = jnp.asarray(tab.free)
    b1, b2, eps = betas

    def build_f(f0, delta):
        """Factors at log-distance `delta` from the integer mapping `f0`
        the segment starts at (exactly `f0` where delta is 0), the
        backing store's factor inferred."""
        f = jnp.where(free, f0 * jnp.exp(delta), 1.0)
        inner = jnp.prod(f, axis=(1, 2))
        return f.at[:, TEMPORAL, tab.s.backing, :].set(dims / inner)

    grad = jax.vmap(jax.grad(lambda d, f0, o: loss(build_f(f0, d), o, pw)))

    @functools.partial(jax.jit, static_argnames=("steps",))
    def descend(f0, orders, steps: int):
        """`steps` Adam steps over the log factors from the integer
        mappings f0 (M, L, 2, n, 7), with fresh moments; returns the
        continuous factors reached."""
        def body(carry, t):
            d, m, v = carry
            g = grad(d, f0, orders)
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mh = m / (1 - b1 ** t)
            vh = v / (1 - b2 ** t)
            return (d - lr * mh / (jnp.sqrt(vh) + eps), m, v), ()
        zeros = jnp.zeros_like(f0)
        ts = jnp.arange(1, steps + 1, dtype=jnp.float64)
        (delta, _, _), _ = jax.lax.scan(body, (zeros, zeros, zeros), ts)
        return jax.vmap(build_f)(f0, delta)

    tables = jax.jit(jax.vmap(all_orderings))
    return tab, descend, tables


def coordinate_descent(e: np.ndarray, lat: np.ndarray,
                       passes: int = 2) -> np.ndarray:
    """(M, L) ordering combos minimising (sum e) * (sum lat), per member:
    each pass re-totals, then sweeps the layers in order, each taking
    the first combo of least network EDP given the others."""
    m_count, n_layers, _ = e.shape
    rows = np.arange(m_count)
    choice = np.zeros((m_count, n_layers), dtype=np.int64)
    for _ in range(passes):
        e_tot = np.take_along_axis(e, choice[..., None], 2)[..., 0].sum(1)
        l_tot = np.take_along_axis(lat, choice[..., None], 2)[..., 0].sum(1)
        for i in range(n_layers):
            c0 = choice[:, i]
            e_rest = e_tot - e[rows, i, c0]
            l_rest = l_tot - lat[rows, i, c0]
            c = np.argmin((e_rest[:, None] + e[:, i])
                          * (l_rest[:, None] + lat[:, i]), axis=1)
            choice[:, i] = c
            e_tot = e_rest + e[rows, i, c]
            l_tot = l_rest + lat[rows, i, c]
    return choice


def best_edp(spec: dict, layers: list[dict], protocol: dict, seed: int,
             members: int) -> float:
    """The best oracle EDP of the one loop from a request's seed, with
    `members` device-seeded start points."""
    import json

    import jax
    import jax.numpy as jnp
    cpu = jax.devices("cpu")[0]
    tab0 = Tables(spec)
    dims = np.array([lay["dims"] for lay in layers], dtype=np.int64)
    u_f, u_o = seed_uniforms(seed, members, len(layers), tab0)
    f, orders = cosa_starts(dims, u_f, u_o, tab0)
    steps, every = protocol["steps"], protocol["round_every"]
    segs = [every] * (steps // every) + ([steps % every]
                                         if steps % every else [])
    best = math.inf
    with jax.enable_x64(True), jax.default_device(cpu):
        tab, descend, tables = _programs(
            json.dumps(spec, sort_keys=True), json.dumps(layers),
            float(protocol["lr"]), float(protocol["penalty_weight"]),
            tuple(protocol["adam_betas_eps"]))
        for n_steps in segs:
            f_cont = np.asarray(descend(jnp.asarray(f), jnp.asarray(orders),
                                        n_steps))
            f = round_population(f_cont, dims, tab)
            e, lat = tables(jnp.asarray(f))
            orders = tab.combos[coordinate_descent(np.asarray(e),
                                                   np.asarray(lat))]
            for m in range(members):
                pairs = [(f[m, li], orders[m, li])
                         for li in range(len(layers))]
                best = min(best, ref.network_edp(tab.s, pairs, layers))
    return best
