"""Jit'd public wrapper: DOSA-tuned default block shapes, divisor-safe
block rounding."""
from __future__ import annotations

import jax

from ...core.autotune import round_block  # DOSA Sec. 5.3.2-style rounding
from .matmul import matmul
from .ref import matmul_ref  # noqa: F401  (public kernel surface)


def tuned_matmul(x: jax.Array, y: jax.Array,
                 blocks: tuple[int, int, int] | None = None,
                 interpret: bool = False) -> jax.Array:
    """Matmul through the Pallas kernel with blocks (bm, bn, bk) chosen
    by the DOSA-TPU autotuner (`TuneResult.blocks` order) or supplied
    by the caller.  `interpret=True` runs the kernel body in the Pallas
    interpreter (for backends without a TPU)."""
    m, k = x.shape
    _, n = y.shape
    if blocks is None:
        from ...core.autotune import default_blocks
        blocks = default_blocks(m, n, k)
    bm = round_block(m, blocks[0])
    bn = round_block(n, blocks[1])
    bk = round_block(k, blocks[2])
    return matmul(x, y, bm=bm, bk=bk, bn=bn, interpret=interpret)
