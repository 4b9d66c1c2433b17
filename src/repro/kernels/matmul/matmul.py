"""Pallas TPU tiled matmul with DOSA-tunable BlockSpecs.

The (bm, bk, bn) VMEM tile shape is the *mapping* in DOSA terms: it
determines the HBM<->VMEM traffic and the MXU utilization exactly the
way Gemmini's scratchpad tiling factors do (DESIGN.md Sec. 5).
`repro.core.autotune` runs the paper's one-loop gradient search over
these block shapes against the TPU-adapted analytical model; this
kernel consumes the result.

Grid: (M/bm, N/bn, K/bk), K innermost so the f32 accumulator tile stays
resident in VMEM across the contraction (output-stationary at the VMEM
level — the K loop is the DOSA "temporal K factor" at memory level 1).
Validated on CPU with interpret=True against ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.tpu_model import scoped_vmem_limit


def _matmul_kernel(x_ref, y_ref, o_ref, acc_ref, *, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...].astype(jnp.float32), y_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn",
                                             "interpret"))
def matmul(x: jax.Array, y: jax.Array, *, bm: int = 256, bk: int = 512,
           bn: int = 256, interpret: bool = False) -> jax.Array:
    """x: (M, K) @ y: (K, N) -> (M, N).  Block shapes must divide the
    problem (the caller pads; `repro.core.autotune.round_block` rounds
    DOSA's continuous factors to divisors, exactly like the paper's
    Sec. 5.3.2 rounding)."""
    m, k = x.shape
    k2, n = y.shape
    assert k == k2, (x.shape, y.shape)
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    assert m % bm == 0 and k % bk == 0 and n % bn == 0, \
        (m, k, n, bm, bk, bn)
    n_k = k // bk
    kernel = functools.partial(_matmul_kernel, n_k=n_k)
    # Double-buffered x, y and output tiles plus the f32 accumulator
    # (`core.tpu_model.vmem_footprint`, which the tuner bounds).
    itemsize = jnp.dtype(x.dtype).itemsize
    footprint = (2 * (bm * bk + bk * bn + bm * bn) * itemsize
                 + 4 * bm * bn)
    return pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=scoped_vmem_limit(footprint)),
        interpret=interpret,
    )(x, y)
