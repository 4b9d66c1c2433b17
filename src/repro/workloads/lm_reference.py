"""Plain JAX reference of the layers `lm_extract` lowers for latent
attention and routed experts (Kimi-K2, DeepSeek-V3 family), written to
check the lowering: float32, every matmul under
`jax.default_matmul_precision("highest")` (callers enter it).

* `mla_prefill`: un-absorbed multi-head latent attention, causal: the
  kv latent is up-projected (`kv_b`) and each head attends at qk width
  nope + rope, v width `v_head_dim`.
* `mla_decode_absorbed`: one decode step, absorbed (DeepSeek-V2 Sec.
  2.1.2): W_UK folds into the query and W_UV into the output, so every
  head reads the one latent cache.  Its matmuls are exactly the GEMMs
  `lm_extract` emits for decode.
* `moe`: sigmoid scores, top-k on the scores plus the selection bias,
  the unbiased scores of the chosen experts normalized and scaled, and
  the shared expert; `experts_held` computes only one expert-parallel
  share.

Departures from the published model, none of which changes a GEMM:

* the softmax scale is 1 / sqrt(qk width): yarn's mscale is left out;
* RoPE is the rotate-half form at plain `rope_theta`, without yarn's
  frequency interpolation;
* the q and kv latents are RMS-normalized without a learned scale, and
  a layer is one sub-layer on its input: no block norms or residuals;
* `moe` runs each held expert on every token, weighted by its gate (0
  where the token did not choose it): the same output as routing, but
  not the routed GEMM shapes, which the lowering takes at mean load.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig

EPS = 1e-6


def _normal(key, shape, fan_in, dtype):
    return jax.random.normal(key, shape, dtype) / np.sqrt(fan_in)


def init_mla(key, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    """Seeded random weights of one latent attention layer, 2-D, (in,
    out); `kv_b`'s columns are per head (nope, v)."""
    d, h, lat, q_lat = (cfg.d_model, cfg.n_heads, cfg.kv_lora_rank,
                        cfg.q_lora_rank)
    k = jax.random.split(key, 5)
    return {
        "q_a": _normal(k[0], (d, q_lat), d, dtype),
        "q_b": _normal(k[1], (q_lat, h * cfg.qk_head_dim), q_lat, dtype),
        "kv_a": _normal(k[2], (d, lat + cfg.qk_rope_head_dim), d, dtype),
        "kv_b": _normal(k[3], (lat, h * (cfg.qk_nope_head_dim
                                         + cfg.v_head_dim)), lat, dtype),
        "o": _normal(k[4], (h * cfg.v_head_dim, d), h * cfg.v_head_dim,
                     dtype)}


def rmsnorm(x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)


def rope(x, pos, theta: float):
    """Rotate-half RoPE on the last axis; `pos` broadcasts against
    x.shape[:-1]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) * 2 / x.shape[-1])
    ang = jnp.asarray(pos, jnp.float32)[..., None] * freqs
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _query(w, cfg: ArchConfig, x, pos):
    """(..., d) -> q_nope (..., h, nope), q_rope (..., h, rope)."""
    q = rmsnorm(x @ w["q_a"]) @ w["q_b"]
    q = q.reshape(*q.shape[:-1], cfg.n_heads, cfg.qk_head_dim)
    nope = cfg.qk_nope_head_dim
    return q[..., :nope], rope(q[..., nope:], pos, cfg.rope_theta)


def latent(w, cfg: ArchConfig, x, pos):
    """The cache entries of x (..., S, d): the normalized kv latent
    (..., S, lat) and the RoPE key (..., S, rope), shared by all
    heads."""
    kv = x @ w["kv_a"]
    lat = cfg.kv_lora_rank
    return rmsnorm(kv[..., :lat]), rope(kv[..., lat:], pos, cfg.rope_theta)


def _softmax_scale(cfg: ArchConfig) -> float:
    return 1.0 / np.sqrt(cfg.qk_head_dim)


def mla_prefill(w, cfg: ArchConfig, x):
    """Un-absorbed causal attention over x (B, S, d) -> (B, S, d)."""
    b, s, _ = x.shape
    h, nope = cfg.n_heads, cfg.qk_nope_head_dim
    pos = jnp.arange(s)
    q_nope, q_rope = _query(w, cfg, x, pos[:, None])
    c, kr = latent(w, cfg, x, pos)
    kv = (c @ w["kv_b"]).reshape(b, s, h, nope + cfg.v_head_dim)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(kr[:, :, None], (b, s, h, cfg.qk_rope_head_dim))],
        -1)
    q = jnp.concatenate([q_nope, q_rope], -1)
    # per (sequence, head): (S x qk) @ (qk x S), then (S x S) @ (S x v)
    qh, kh, vh = (t.transpose(0, 2, 1, 3) for t in (q, k, kv[..., nope:]))
    scores = jnp.matmul(qh, kh.swapaxes(-1, -2)) * _softmax_scale(cfg)
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    ctx = jnp.matmul(probs, vh).transpose(0, 2, 1, 3)
    return ctx.reshape(b, s, h * cfg.v_head_dim) @ w["o"]


def mla_decode_absorbed(w, cfg: ArchConfig, x, cache_c, cache_kr,
                        pos: int):
    """One decode step of x (B, d) at position `pos`, absorbed, against
    a latent cache (B, pos, lat) and RoPE keys (B, pos, rope) of the
    positions before it; x's own entry is appended -> (B, d)."""
    b = x.shape[0]
    h, nope, lat = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q_nope, q_rope = _query(w, cfg, x, pos)                  # (B, h, .)
    c_new, kr_new = latent(w, cfg, x[:, None], pos)
    cache_c = jnp.concatenate([cache_c, c_new], 1)           # (B, L, lat)
    cache_kr = jnp.concatenate([cache_kr, kr_new], 1)
    kv_b = w["kv_b"].reshape(lat, h, nope + cfg.v_head_dim)
    w_uk = kv_b[..., :nope].transpose(1, 2, 0)               # (h, nope, lat)
    w_uv = kv_b[..., nope:].transpose(1, 0, 2)               # (h, lat, v)
    # per head: (B x nope) @ (nope x lat)
    q_lat = jnp.matmul(q_nope.swapaxes(0, 1), w_uk).swapaxes(0, 1)
    q_full = jnp.concatenate([q_lat, q_rope], -1)            # (B, h, 576)
    cache = jnp.concatenate([cache_c, cache_kr], -1)         # (B, L, 576)
    # per sequence: (h x lat+rope) @ (lat+rope x L), (h x L) @ (L x lat)
    scores = jnp.matmul(q_full, cache.swapaxes(1, 2)) * _softmax_scale(cfg)
    ctx_lat = jnp.matmul(jax.nn.softmax(scores, -1), cache_c)
    # per head: (B x lat) @ (lat x v)
    out = jnp.matmul(ctx_lat.swapaxes(0, 1), w_uv).swapaxes(0, 1)
    return out.reshape(b, h * cfg.v_head_dim) @ w["o"]


# ------------------------------------------------------------------ FFN

def init_mlp(key, d: int, f: int, dtype=jnp.float32) -> dict:
    k = jax.random.split(key, 3)
    return {"gate": _normal(k[0], (d, f), d, dtype),
            "up": _normal(k[1], (d, f), d, dtype),
            "down": _normal(k[2], (f, d), f, dtype)}


def swiglu(w, x):
    return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]


def init_moe(key, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    """Router, selection bias, the routed experts and one shared MLP of
    width n_shared_experts * d_ff."""
    d, e = cfg.d_model, cfg.n_experts
    k = jax.random.split(key, e + 3)
    return {"router": _normal(k[0], (d, e), d, dtype),
            "bias": 0.1 * jax.random.normal(k[1], (e,), dtype),
            "experts": [init_mlp(k[3 + i], d, cfg.d_ff, dtype)
                        for i in range(e)],
            "shared": init_mlp(k[2], d, cfg.n_shared_experts * cfg.d_ff,
                               dtype)}


def moe(w, cfg: ArchConfig, x, routed_scale: float,
        experts_held: tuple[int, int] | None = None,
        shared: bool = True):
    """x (T, d) -> (T, d): the routed experts with ids in
    [experts_held) (all of them by default) and, where `shared`, the
    shared expert."""
    t, e = x.shape[0], cfg.n_experts
    scores = jax.nn.sigmoid(x @ w["router"])                 # (T, E)
    _, idx = jax.lax.top_k(scores + w["bias"], cfg.experts_per_token)
    chosen = jnp.take_along_axis(scores, idx, -1)
    gates = chosen / chosen.sum(-1, keepdims=True) * routed_scale
    dense = jnp.zeros((t, e), x.dtype).at[jnp.arange(t)[:, None],
                                          idx].set(gates)
    lo, hi = experts_held if experts_held is not None else (0, e)
    out = jnp.zeros_like(x)
    for i in range(lo, hi):
        out = out + dense[:, i:i + 1] * swiglu(w["experts"][i], x)
    if shared:
        out = out + swiglu(w["shared"], x)
    return out
