"""Core transformer building blocks — pure-functional JAX (no flax).

Every module is a pair (init_fn -> params pytree, apply fn) plus a
parallel `specs` pytree of PartitionSpecs built from the logical rules
in `repro.sharding.rules`.  Compute dtype is configurable (bf16 for the
production configs); parameters live in `param_dtype`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from ..sharding.rules import (ACT_KV_GATHERED, ACT_Q_ULYSSES, constrain,
                              spec)


def dtype_of(name: str):
    return {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# Parameter initialization helpers
# ---------------------------------------------------------------------------

def dense_init(key, shape, dtype, scale: float | None = None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[0]
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

def rmsnorm_init(cfg: ArchConfig, width: int | None = None):
    width = width or cfg.d_model
    params = {"scale": jnp.ones((width,), dtype_of(cfg.param_dtype))}
    specs = {"scale": spec(None)}
    return params, specs

def rmsnorm(params, x, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return out.astype(dt) * params["scale"].astype(dt)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------

def rope(x, positions, theta: float = 10000.0):
    """x: (..., S, D) with D even; positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # (...,S,half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked (flash-style) attention — O(chunk) memory, exact softmax.
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool, chunk: int = 1024,
                    q_offset: int = 0, unroll: bool = False):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D); Hq % Hkv == 0.
    Blockwise streaming softmax over K/V chunks (the flash algorithm in
    pure jnp; the Pallas twin lives in repro/kernels/flash_attention).
    `q_offset`: absolute position of q[0] for causal masking."""
    b, hq, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = hq // hkv
    qg = q.reshape(b, hkv, group, sq, d)
    scale = 1.0 / np.sqrt(d)
    n_chunks = max(sk // chunk, 1)
    chunk = sk // n_chunks
    kc = k.reshape(b, hkv, n_chunks, chunk, d)
    vc = v.reshape(b, hkv, n_chunks, chunk, d)

    q_pos = q_offset + jnp.arange(sq)

    def step(carry, inputs):
        acc, m, lse = carry
        kb, vb, c_idx = inputs
        s = jnp.einsum("bhgqd,bhcd->bhgqc", qg, kb,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            k_pos = c_idx * chunk + jnp.arange(chunk)
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        # guard fully-masked rows
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        lse_new = lse * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhgqc,bhcd->bhgqd", p, vb.astype(jnp.float32))
        return (acc_new, m_safe, lse_new), None

    acc0 = jnp.zeros((b, hkv, group, sq, d), jnp.float32)
    m0 = jnp.full((b, hkv, group, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hkv, group, sq), jnp.float32)
    (acc, m, lse), _ = jax.lax.scan(
        step, (acc0, m0, l0),
        (jnp.moveaxis(kc, 2, 0), jnp.moveaxis(vc, 2, 0),
         jnp.arange(n_chunks)), unroll=unroll)
    out = acc / jnp.maximum(lse[..., None], 1e-20)
    return out.reshape(b, hq, sq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention block (self / cross, GQA, qk-norm, biases, rope)
# ---------------------------------------------------------------------------

def attention_init(key, cfg: ArchConfig, cross: bool = False):
    pdt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    params = {
        "wq": dense_init(ks[0], (d, qd), pdt),
        "wk": dense_init(ks[1], (d, kvd), pdt),
        "wv": dense_init(ks[2], (d, kvd), pdt),
        "wo": dense_init(ks[3], (qd, d), pdt,
                         scale=1.0 / np.sqrt(qd * 2 * cfg.n_layers)),
    }
    # Flat projection dims sharded over "model" (always divisible, any
    # head count); FSDP over "data" on the other dim.
    specs = {
        "wq": spec("embed", "embed_tp"),
        "wk": spec("embed", "embed_tp"),
        "wv": spec("embed", "embed_tp"),
        "wo": spec("embed_tp", "embed"),
    }
    if cfg.qkv_bias:
        params.update(bq=jnp.zeros((qd,), pdt), bk=jnp.zeros((kvd,), pdt),
                      bv=jnp.zeros((kvd,), pdt))
        specs.update(bq=spec("heads"), bk=spec("kv_heads"),
                     bv=spec("kv_heads"))
    if cfg.qk_norm:
        params["q_norm"], specs["q_norm"] = \
            rmsnorm_init(cfg, cfg.head_dim)
        params["k_norm"], specs["k_norm"] = \
            rmsnorm_init(cfg, cfg.head_dim)
    return params, specs


def _split_heads(x, n_heads, head_dim):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, head_dim).swapaxes(1, 2)


def attention_qkv(params, cfg: ArchConfig, x, kv_x, positions,
                  kv_positions, use_rope: bool = True):
    """Project to (q, k, v) head tensors."""
    cdt = dtype_of(cfg.compute_dtype)
    q = x @ params["wq"].astype(cdt)
    k = kv_x @ params["wk"].astype(cdt)
    v = kv_x @ params["wv"].astype(cdt)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(cdt)
        k = k + params["bk"].astype(cdt)
        v = v + params["bv"].astype(cdt)
    q = _split_heads(q, cfg.n_heads, cfg.head_dim)
    k = _split_heads(k, cfg.n_kv_heads, cfg.head_dim)
    v = _split_heads(v, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    if use_rope:
        q = rope(q, positions[:, None, :], cfg.rope_theta)
        k = rope(k, kv_positions[:, None, :], cfg.rope_theta)
    # Ulysses resharding: q sequence-sharded over "model" (all-to-all
    # from the D-sharded projection), K/V gathered.
    q = constrain(q, ACT_Q_ULYSSES)
    k = constrain(k, ACT_KV_GATHERED)
    v = constrain(v, ACT_KV_GATHERED)
    return q, k, v


def attention_apply(params, cfg: ArchConfig, x, positions, *,
                    kv_x=None, kv_positions=None, causal=None,
                    chunk: int = 1024, unroll: bool = False):
    """Full attention block (no cache): returns (B, S, D)."""
    causal = cfg.causal if causal is None else causal
    cross = kv_x is not None
    kv_x = x if kv_x is None else kv_x
    kv_positions = positions if kv_positions is None else kv_positions
    q, k, v = attention_qkv(params, cfg, x, kv_x, positions, kv_positions,
                            use_rope=not cross)
    out = flash_attention(q, k, v, causal=causal and not cross,
                          chunk=min(chunk, k.shape[2]), unroll=unroll)
    b, h, s, hd = out.shape
    out = out.swapaxes(1, 2).reshape(b, s, h * hd)
    return out @ params["wo"].astype(dtype_of(cfg.compute_dtype))


def attention_decode(params, cfg: ArchConfig, x, cache_k, cache_v,
                     position):
    """Single-token decode against a KV cache.
    x: (B, 1, D); cache_k/v: (B, Hkv, S_max, hd); position: scalar int
    (same position for the whole batch).  Returns (out, new_k, new_v)."""
    cdt = dtype_of(cfg.compute_dtype)
    b = x.shape[0]
    pos = jnp.full((b, 1), position, dtype=jnp.int32)
    q, k, v = attention_qkv(params, cfg, x, x, pos, pos)
    cache_k = jax.lax.dynamic_update_slice(
        cache_k, k.astype(cache_k.dtype), (0, 0, position, 0))
    cache_v = jax.lax.dynamic_update_slice(
        cache_v, v.astype(cache_v.dtype), (0, 0, position, 0))
    s_max = cache_k.shape[2]
    group = cfg.n_heads // cfg.n_kv_heads
    qg = q.reshape(b, cfg.n_kv_heads, group, 1, cfg.head_dim)
    scores = jnp.einsum("bhgqd,bhsd->bhgqs", qg,
                        cache_k.astype(cdt),
                        preferred_element_type=jnp.float32)
    scores = scores / np.sqrt(cfg.head_dim)
    mask = jnp.arange(s_max) <= position
    scores = jnp.where(mask[None, None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgqs,bhsd->bhgqd", probs,
                     cache_v.astype(jnp.float32))
    out = out.reshape(b, 1, cfg.q_dim).astype(cdt)
    return out @ params["wo"].astype(cdt), cache_k, cache_v


# ---------------------------------------------------------------------------
# Dense MLP (SwiGLU / GeGLU / ReLU^2 / GELU)
# ---------------------------------------------------------------------------

def mlp_init(key, cfg: ArchConfig):
    pdt = dtype_of(cfg.param_dtype)
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.dense_width
    gated = cfg.activation in ("swiglu", "geglu")
    params = {"w_up": dense_init(ks[0], (d, f), pdt),
              "w_down": dense_init(ks[1], (f, d), pdt,
                                   scale=1.0 / np.sqrt(f * 2 * cfg.n_layers))}
    specs = {"w_up": spec("embed", "mlp"), "w_down": spec("mlp", "embed")}
    if gated:
        params["w_gate"] = dense_init(ks[2], (d, f), pdt)
        specs["w_gate"] = spec("embed", "mlp")
    return params, specs


def _activate(name: str, u, g=None):
    if name == "swiglu":
        return jax.nn.silu(g) * u
    if name == "geglu":
        return jax.nn.gelu(g) * u
    if name == "relu2":
        return jnp.square(jax.nn.relu(u))
    return jax.nn.gelu(u)


def mlp_apply(params, cfg: ArchConfig, x):
    cdt = dtype_of(cfg.compute_dtype)
    u = x @ params["w_up"].astype(cdt)
    g = x @ params["w_gate"].astype(cdt) if "w_gate" in params else None
    h = _activate(cfg.activation, u, g)
    return h @ params["w_down"].astype(cdt)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def embedding_init(key, cfg: ArchConfig):
    from jax.sharding import PartitionSpec as P

    from ..sharding.rules import MODEL_AXIS_SIZE
    pdt = dtype_of(cfg.param_dtype)
    k1, k2 = jax.random.split(key)
    params = {
        "tok": dense_init(k1, (cfg.vocab_size, cfg.d_model), pdt,
                          scale=1.0),
        "unembed": dense_init(k2, (cfg.d_model, cfg.vocab_size), pdt),
    }
    if cfg.vocab_size % MODEL_AXIS_SIZE == 0:
        specs = {"tok": spec("vocab", "embed"),
                 "unembed": spec("embed", "vocab")}
    else:
        # odd vocabularies (50280, 504): shard d_model over the full
        # (data, model) plane instead
        specs = {"tok": P(None, ("data", "model")),
                 "unembed": P(("data", "model"), None)}
    return params, specs


def embed(params, cfg: ArchConfig, tokens):
    cdt = dtype_of(cfg.compute_dtype)
    return params["tok"].astype(cdt)[tokens]


def unembed(params, cfg: ArchConfig, x):
    # logits in f32 for a stable softmax-xent
    return (x @ params["unembed"].astype(x.dtype)).astype(jnp.float32)
