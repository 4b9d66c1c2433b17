"""DOSA's differentiable model retargeted at the TPU v5e memory
hierarchy (DESIGN.md Sec. 5 — the hardware adaptation).

Since the ArchSpec refactor this module holds **no traffic or capacity
math of its own**: the TPU v5e is `archspec.TPU_V5E_SPEC` (HBM -> VMEM
-> VREG/MXU with *fixed* capacities), and `matmul_latency` /
`vmem_footprint` below are thin adapters that express a Pallas-style
matmul tile schedule (bm, bn, bk) as a mapping tensor for the shared
differentiable core in `model.py` — the same `capacities` (Eqs. 2-5)
and `traffic` (Eqs. 6-11) code that models Gemmini.

What stays TPU-specific here:

* `mxu_utilization` — fractional occupancy of the 128x128 systolic
  array under (8, 128) tiling: DOSA's "spatial factor" term with the
  spatial sizes frozen by silicon (a compute model, not traffic);
* the seconds-domain roofline `latency = max(compute, memory)` against
  `peak_flops` / `hbm_bw` (plus `step_roofline`'s ICI collective term);
* one convention: each output tile is written once *and read back by
  the downstream op* (+M*N words of HBM traffic) — DOSA models a layer
  in isolation and stops at the write.

The matmul dims map onto DOSA's 7-space as P=M, C=K_contract, K=N
(`problem.Layer.matmul`), with the K-innermost output-stationary
ordering of `kernels/matmul` at HBM level.  The ceil-shaped grid terms
use a smooth-ceil (exact forward, pass-through gradient), the same
trick as the paper's factor>1 mask.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .arch import TPU_V5E, TPUTarget
from .archspec import TPU_V5E_SPEC, compile_spec
from .mapping import OS_ORD, TEMPORAL
from .model import capacities, traffic_spec
from .problem import C as C_D, K as K_D, P as P_D, I_T, O_T, W_T

_STRIDES = (1.0, 1.0)


def smooth_ceil(x):
    """ceil with pass-through gradient of identity (ceil(x) >= x)."""
    return x + jax.lax.stop_gradient(jnp.ceil(x) - x)


def mxu_utilization(bm, bn, bk, target: TPUTarget = TPU_V5E):
    """Fractional MXU occupancy of a (bm, bk) x (bk, bn) tile: last dim
    packs into 128 lanes, second-to-last into 8 sublanes; the MXU
    contracts 128 at a time."""
    lane = target.mxu_dim
    util_n = bn / (smooth_ceil(bn / lane) * lane)
    util_k = bk / (smooth_ceil(bk / lane) * lane)
    util_m = bm / (smooth_ceil(bm / 8.0) * 8.0)
    return util_m * util_n * util_k


def _tile_factors(m, n, k, bm, bn, bk):
    """(2, 3, 7) factor tensor of the (bm, bn, bk) schedule on the TPU
    spec's VREG/VMEM/HBM hierarchy: VMEM holds one (possibly clamped)
    tile per operand, HBM carries the smooth-ceil grid loops."""
    grid_m = smooth_ceil(m / bm)
    grid_n = smooth_ceil(n / bn)
    grid_k = smooth_ceil(k / bk)
    f = jnp.ones((2, 3, 7))
    f = f.at[TEMPORAL, 1, P_D].set(m / grid_m)
    f = f.at[TEMPORAL, 1, K_D].set(n / grid_n)
    f = f.at[TEMPORAL, 1, C_D].set(k / grid_k)
    f = f.at[TEMPORAL, 2, P_D].set(grid_m)
    f = f.at[TEMPORAL, 2, K_D].set(grid_n)
    f = f.at[TEMPORAL, 2, C_D].set(grid_k)
    return f


def matmul_latency(m, n, k, bm, bn, bk, dtype_bytes: float = 2.0,
                   target: TPUTarget = TPU_V5E):
    """Differentiable latency (seconds) + aux terms for one matmul tile
    schedule on one chip.  HBM traffic comes from the shared DOSA
    traffic model (Eqs. 6-11) evaluated on the TPU spec's hierarchy;
    compute comes from the MXU occupancy model."""
    cspec = compile_spec(TPU_V5E_SPEC)
    f = _tile_factors(m, n, k, bm, bn, bk)
    # K-innermost output-stationary HBM loop order (kernels/matmul).
    order = jnp.array([0, 0, OS_ORD])
    caps = capacities(f, jnp.asarray(_STRIDES))
    macs = jnp.asarray(float(m) * float(n) * float(k))
    tr = traffic_spec(cspec, f, order, caps, macs)
    hbm_words = tr.accesses[cspec.backing] + m * n   # + downstream read
    hbm_bytes = hbm_words * dtype_bytes
    compute_s = 2.0 * m * n * k / (
        target.peak_flops * mxu_utilization(bm, bn, bk, target))
    memory_s = hbm_bytes / target.hbm_bw
    latency = jnp.maximum(compute_s, memory_s)
    return latency, {"compute_s": compute_s, "memory_s": memory_s,
                     "hbm_bytes": hbm_bytes}


# Scoped VMEM on TPU v5e: what the compiler grants a Pallas kernel
# unless the kernel asks for more (`vmem_limit_bytes`), and the most a
# kernel of this repo asks for — physical VMEM less room the compiler
# keeps for itself.  On top of a kernel's declared buffers the compiler
# adds temporaries (operand casts, dot staging).  Compiling matmul tiles
# for v5e at shrinking limits, the most any needed was 1.28x its
# buffers, at (bm, bk, bn) = (256, 3584, 512); 1.5x leaves headroom.
SCOPED_VMEM_DEFAULT = 16 * 1024 ** 2
SCOPED_VMEM_MAX = 96 * 1024 ** 2
SCOPED_VMEM_SLACK = 1.5


def scoped_vmem_limit(footprint_bytes: float) -> int:
    """`vmem_limit_bytes` for a kernel whose buffers take
    `footprint_bytes` of VMEM: the buffers plus slack, never below the
    default grant nor above `SCOPED_VMEM_MAX`."""
    want = int(SCOPED_VMEM_SLACK * footprint_bytes)
    return min(max(SCOPED_VMEM_DEFAULT, want), SCOPED_VMEM_MAX)


def vmem_footprint(bm, bn, bk, dtype_bytes: float = 2.0):
    """Double-buffered input and output tiles + f32 accumulator (bytes),
    from the shared capacity model (Eqs. 2-5) at the VMEM level — the
    buffers `kernels/matmul` declares."""
    f = jnp.ones((2, 3, 7))
    f = f.at[TEMPORAL, 1, P_D].set(bm)
    f = f.at[TEMPORAL, 1, K_D].set(bn)
    f = f.at[TEMPORAL, 1, C_D].set(bk)
    caps = capacities(f, jnp.asarray(_STRIDES))
    return (2.0 * (caps[1, W_T] + caps[1, I_T] + caps[1, O_T])
            * dtype_bytes + caps[1, O_T] * 4.0)


def vmem_penalty(bm, bn, bk, dtype_bytes: float = 2.0,
                 target: TPUTarget = TPU_V5E):
    """Relative overflow of the scoped VMEM a kernel can request — the
    inverted Eq. 2-5 constraint."""
    budget = min(target.vmem_bytes, SCOPED_VMEM_MAX)
    return jnp.maximum(
        SCOPED_VMEM_SLACK * vmem_footprint(bm, bn, bk, dtype_bytes)
        / budget - 1.0, 0.0)


# ---------------------------------------------------------------------------
# Step-level three-term roofline (Sec. Roofline of EXPERIMENTS.md)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def bound(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)


def step_roofline(flops_per_dev: float, bytes_per_dev: float,
                  coll_bytes_per_dev: float,
                  target: TPUTarget = TPU_V5E) -> RooflineTerms:
    """Three roofline terms from the dry-run's per-device HLO stats.

      compute    = HLO_FLOPs / peak
      memory     = HLO_bytes / HBM_bw
      collective = collective_bytes / link_bw
    """
    return RooflineTerms(
        compute_s=flops_per_dev / target.peak_flops,
        memory_s=bytes_per_dev / target.hbm_bw,
        collective_s=coll_bytes_per_dev / target.ici_bw,
    )


def model_flops(n_active_params: float, tokens: float,
                train: bool) -> float:
    """6*N*D (train) / 2*N*D (inference) useful-FLOPs accounting."""
    per_tok = 6.0 * n_active_params if train else 2.0 * n_active_params
    return per_tok * tokens
