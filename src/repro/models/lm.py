"""Full language-model assembly for all 10 assigned architectures.

Layer stacking uses `lax.scan` over *periods*: the smallest repeating
block pattern (1 for homogeneous stacks; 8 for Jamba's 1:7
mamba:attention interleave; 5 for the VLM's cross-attention cadence).
Parameters for each position within the period are stacked over a
leading `n_periods` axis, keeping the HLO size O(period), not
O(n_layers) — essential for compiling the 96/100-layer giants.

Three entry points per model:
  * `train_loss(params, batch)`      — causal LM (or encoder) loss,
  * `prefill(params, batch)`         — forward + KV/SSM cache build,
  * `decode_step(params, cache, tok, pos)` — one-token serve step.
"""
from __future__ import annotations

import dataclasses
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from ..sharding.rules import ACT_TOKENS, constrain
from . import layers as L
from . import moe as M
from . import ssm as S


# ---------------------------------------------------------------------------
# Period structure
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SlotSpec:
    kind: str          # "attn" | "ssm"
    moe: bool
    cross: bool


def period_layout(cfg: ArchConfig) -> list[SlotSpec]:
    if cfg.family == "ssm":
        period = 1
    elif cfg.family == "hybrid":
        period = cfg.attn_layer_period
    elif cfg.cross_attn_period:
        period = cfg.cross_attn_period
    else:
        period = 1
    assert cfg.n_layers % period == 0, (cfg.n_layers, period)
    slots = []
    for i in range(period):
        kind = "attn" if cfg.is_attn_layer(i) else "ssm"
        # the stack builds no leading dense layer: a slot's FFN follows
        # the layers past them
        moe = cfg.is_moe_layer(i + period * cfg.first_k_dense)
        slots.append(SlotSpec(kind=kind, moe=moe,
                              cross=cfg.is_cross_attn_layer(i)))
    return slots


def _slot_init(key, cfg: ArchConfig, slot: SlotSpec):
    ks = jax.random.split(key, 6)
    p, s = {}, {}
    p["ln1"], s["ln1"] = L.rmsnorm_init(cfg)
    if slot.kind == "attn":
        p["attn"], s["attn"] = L.attention_init(ks[0], cfg)
    else:
        p["ssm"], s["ssm"] = S.ssm_init(ks[0], cfg)
    if slot.cross:
        p["lnx"], s["lnx"] = L.rmsnorm_init(cfg)
        p["xattn"], s["xattn"] = L.attention_init(ks[1], cfg, cross=True)
    if slot.kind == "attn" or cfg.family == "hybrid":
        p["ln2"], s["ln2"] = L.rmsnorm_init(cfg)
        if slot.moe:
            p["moe"], s["moe"] = M.moe_init(ks[2], cfg)
        elif cfg.d_ff > 0:
            p["mlp"], s["mlp"] = L.mlp_init(ks[2], cfg)
    return p, s


def _slot_apply(p, cfg: ArchConfig, slot: SlotSpec, x, positions,
                image_embeds, causal, unroll: bool = False):
    """One layer's forward (training/prefill path).  Returns
    (x, aux_loss, kv)."""
    aux = 0.0
    kv = None
    h = L.rmsnorm(p["ln1"], x)
    if slot.kind == "attn":
        q, k, v = L.attention_qkv(p["attn"], cfg, h, h, positions,
                                  positions)
        out = L.flash_attention(q, k, v, causal=causal,
                                chunk=min(1024, k.shape[2]),
                                unroll=unroll)
        bs, hh, ss, hd = out.shape
        out = out.swapaxes(1, 2).reshape(bs, ss, hh * hd)
        x = x + out @ p["attn"]["wo"].astype(h.dtype)
        kv = (k, v)
    else:
        x = x + S.ssd_forward(p["ssm"], cfg, h, unroll=unroll)
    if slot.cross:
        hx = L.rmsnorm(p["lnx"], x)
        x = x + L.attention_apply(
            p["xattn"], cfg, hx, positions, kv_x=image_embeds,
            kv_positions=jnp.zeros(
                (image_embeds.shape[0], image_embeds.shape[1]),
                jnp.int32), unroll=unroll)
    if "mlp" in p or "moe" in p:
        h2 = L.rmsnorm(p["ln2"], x)
        if "moe" in p:
            out, a = M.moe_apply(p["moe"], cfg, h2)
            x = x + out
            aux = aux + a
        else:
            x = x + L.mlp_apply(p["mlp"], cfg, h2)
    x = constrain(x, ACT_TOKENS)
    return x, aux, kv


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------

class LM:
    def __init__(self, cfg: ArchConfig, unroll: bool = False):
        self.cfg = cfg
        self.slots = period_layout(cfg)
        self.n_periods = cfg.n_layers // len(self.slots)
        # unroll=True emits straight-line HLO instead of a while loop —
        # used by the dry-run's depth-1/2 cost lowerings (XLA's
        # cost_analysis counts a loop body once regardless of trips).
        self.unroll = unroll

    # ---- init ------------------------------------------------------------
    def init(self, key):
        cfg = self.cfg
        k_embed, k_blocks = jax.random.split(key)
        params, specs = {}, {}
        params["embed"], specs["embed"] = L.embedding_init(k_embed, cfg)
        params["final_norm"], specs["final_norm"] = L.rmsnorm_init(cfg)

        blocks, bspecs = {}, {}
        for si, slot in enumerate(self.slots):
            keys = jax.random.split(
                jax.random.fold_in(k_blocks, si), self.n_periods)
            stacked = [ _slot_init(keys[j], cfg, slot)[0]
                        for j in range(self.n_periods) ]
            _, sspec = _slot_init(keys[0], cfg, slot)
            blocks[f"slot{si}"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *stacked)
            bspecs[f"slot{si}"] = jax.tree.map(
                lambda sp: P(None, *sp), sspec,
                is_leaf=lambda v: isinstance(v, P))
        params["blocks"] = blocks
        specs["blocks"] = bspecs
        return params, specs

    def abstract_init(self, key):
        """(ShapeDtypeStruct params, specs) without allocating — for the
        dry-run of 340B/1T-class configs.  The specs tree is captured
        during the abstract trace (it is data-independent Python)."""
        captured = {}

        def f(k):
            p, s = self.init(k)
            captured["specs"] = s
            return p

        shapes = jax.eval_shape(f, key)
        return shapes, captured["specs"]

    # ---- embedding of batch inputs ----------------------------------------
    def _embed_inputs(self, params, batch):
        cfg = self.cfg
        cdt = L.dtype_of(cfg.compute_dtype)
        if cfg.modality == "audio":
            x = batch["frames"].astype(cdt)        # stub frontend
        else:
            x = L.embed(params["embed"], cfg, batch["tokens"])
        img = batch.get("image_embeds")
        if img is not None:
            img = img.astype(cdt)
        return constrain(x, ACT_TOKENS), img

    # ---- forward over the stack -------------------------------------------
    def _stack(self, params, x, positions, image_embeds, causal,
               collect_kv: bool):
        cfg = self.cfg

        def period_body(carry, block_params):
            x, aux = carry
            kvs = []
            for si, slot in enumerate(self.slots):
                x, a, kv = _slot_apply(block_params[f"slot{si}"], cfg,
                                       slot, x, positions, image_embeds,
                                       causal, unroll=self.unroll)
                aux = aux + a
                if collect_kv and kv is not None:
                    kvs.append(kv)
            out = tuple(kvs) if collect_kv else None
            return (x, aux), out

        body = period_body
        if cfg.remat:
            body = jax.checkpoint(period_body,
                                  prevent_cse=False)
        (x, aux), kv_stacks = jax.lax.scan(body, (x, 0.0),
                                           params["blocks"],
                                           unroll=self.unroll)
        return x, aux, kv_stacks

    # ---- training loss ----------------------------------------------------
    def train_loss(self, params, batch):
        cfg = self.cfg
        x, img = self._embed_inputs(params, batch)
        b, s = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32),
                                     (b, s))
        x, aux, _ = self._stack(params, x, positions, img,
                                causal=cfg.causal, collect_kv=False)
        x = L.rmsnorm(params["final_norm"], x)
        logits = L.unembed(params["embed"], cfg, x)
        if cfg.causal:
            targets = batch["tokens"][:, 1:]
            logits = logits[:, :-1]
        else:                       # encoder: per-position classification
            targets = batch["labels"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None],
                                   axis=-1)[..., 0]
        loss = nll.mean() + 0.01 * aux / max(cfg.n_layers, 1)
        return loss, {"nll": nll.mean(), "aux": aux}

    # ---- prefill ------------------------------------------------------------
    def prefill(self, params, batch):
        """Forward pass building the serve cache.  Returns
        (last_logits, cache)."""
        cfg = self.cfg
        x, img = self._embed_inputs(params, batch)
        b, s = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32),
                                     (b, s))
        x, _, kv_stacks = self._stack(params, x, positions, img,
                                      causal=cfg.causal, collect_kv=True)
        x = L.rmsnorm(params["final_norm"], x)
        logits = L.unembed(params["embed"], cfg, x[:, -1:])
        cache = {"kv": kv_stacks, "ssm": None}
        return logits, cache

    # ---- serve cache --------------------------------------------------------
    def init_cache(self, batch_size: int, max_seq: int,
                   dtype=jnp.bfloat16):
        """Zeroed decode cache: per attention slot a stacked
        (n_periods, B, Hkv, S_max, hd) K/V pair; per SSM slot a stacked
        (n_periods, B, nh, ds, hd) state."""
        cfg = self.cfg
        cache = {}
        for si, slot in enumerate(self.slots):
            if slot.kind == "attn":
                shape = (self.n_periods, batch_size, cfg.n_kv_heads,
                         max_seq, cfg.head_dim)
                cache[f"slot{si}"] = {
                    "k": jnp.zeros(shape, dtype),
                    "v": jnp.zeros(shape, dtype),
                }
            else:
                cache[f"slot{si}"] = {
                    "h": jnp.zeros((self.n_periods, batch_size,
                                    cfg.ssm_heads, cfg.ssm_state,
                                    cfg.ssm_head_dim), jnp.float32),
                }
        return cache

    def cache_specs(self, batch_shardable: bool = True):
        """Decode-cache shardings: KV cache sequence-sharded over
        "model" (context parallelism — works for any kv-head count);
        SSM state head-sharded over "model".  When the batch is too
        small to cover ("pod","data") (long_500k B=1), the sequence
        dim takes ("data","model") instead and batch is replicated."""
        bspec = ("pod", "data") if batch_shardable else None
        sspec = "model" if batch_shardable else ("data", "model")
        specs = {}
        for si, slot in enumerate(self.slots):
            if slot.kind == "attn":
                kv = P(None, bspec, None, sspec, None)
                specs[f"slot{si}"] = {"k": kv, "v": kv}
            else:
                specs[f"slot{si}"] = {
                    "h": P(None, bspec, "model", None, None)}
        return specs

    # ---- decode step --------------------------------------------------------
    def decode_step(self, params, cache, tokens, position,
                    image_embeds=None):
        """tokens: (B, 1) int32; position: int32 scalar.  Returns
        (logits (B,1,V), new_cache)."""
        cfg = self.cfg
        cdt = L.dtype_of(cfg.compute_dtype)
        x = L.embed(params["embed"], cfg, tokens)
        img = image_embeds.astype(cdt) if image_embeds is not None else None

        def period_body(carry, scanned):
            x = carry
            block_params, cache_p = scanned
            new_cache_p = {}
            for si, slot in enumerate(self.slots):
                p = block_params[f"slot{si}"]
                c = cache_p[f"slot{si}"]
                h = L.rmsnorm(p["ln1"], x)
                if slot.kind == "attn":
                    out, nk, nv = L.attention_decode(
                        p["attn"], cfg, h, c["k"], c["v"], position)
                    x = x + out
                    new_cache_p[f"slot{si}"] = {"k": nk, "v": nv}
                else:
                    out, nh = S.ssd_decode(p["ssm"], cfg, h, c["h"])
                    x = x + out
                    new_cache_p[f"slot{si}"] = {"h": nh}
                if slot.cross:
                    hx = L.rmsnorm(p["lnx"], x)
                    b = x.shape[0]
                    pos1 = jnp.zeros((b, 1), jnp.int32)
                    q, k, v = L.attention_qkv(
                        p["xattn"], cfg, hx, img, pos1,
                        jnp.zeros((b, img.shape[1]), jnp.int32),
                        use_rope=False)
                    o = L.flash_attention(q, k, v, causal=False,
                                          chunk=min(1024, k.shape[2]))
                    bs, hh, ss, hd = o.shape
                    o = o.swapaxes(1, 2).reshape(bs, ss, hh * hd)
                    x = x + o @ p["xattn"]["wo"].astype(cdt)
                if "mlp" in p or "moe" in p:
                    h2 = L.rmsnorm(p["ln2"], x)
                    if "moe" in p:
                        out, _ = M.moe_apply(p["moe"], cfg, h2)
                        x = x + out
                    else:
                        x = x + L.mlp_apply(p["mlp"], cfg, h2)
            return x, new_cache_p

        x, new_cache = jax.lax.scan(period_body, x,
                                    (params["blocks"], cache),
                                    unroll=self.unroll)
        x = L.rmsnorm(params["final_norm"], x)
        logits = L.unembed(params["embed"], cfg, x)
        return logits, new_cache


def build_model(cfg: ArchConfig, unroll: bool = False) -> LM:
    return LM(cfg, unroll=unroll)
