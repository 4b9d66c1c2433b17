"""Jit'd wrapper with GQA head handling."""
from __future__ import annotations

import jax.numpy as jnp

from .flash_attention import flash_attention
from .ref import attention_ref  # noqa: F401  (public kernel surface)


def gqa_flash_attention(q, k, v, *, causal: bool = True,
                        bq: int = 512, bkv: int = 512,
                        interpret: bool = False):
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D) with Hq % Hkv == 0.
    `interpret=True` runs the kernel in the Pallas interpreter."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    group = hq // hkv
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    out = flash_attention(
        q.reshape(b * hq, s, d), k.reshape(b * hq, s, d),
        v.reshape(b * hq, s, d), causal=causal, bq=bq, bkv=bkv,
        interpret=interpret)
    return out.reshape(b, hq, s, d)
