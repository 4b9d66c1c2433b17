"""Sharding rules: logical axis names -> mesh PartitionSpecs.

Mesh axes (launch/mesh.py):
  * "pod"   — data parallelism across pods (DCN domain),
  * "data"  — data parallelism + FSDP/ZeRO within a pod,
  * "model" — tensor/expert parallelism within a pod,
  * "pop"   — co-search population / fleet-member axis (its own 1-D
    mesh, `launch.mesh.make_pop_mesh`): the fused one-loop engines
    shard their embarrassingly-parallel member axis over it, with
    best-tracking reduced by pmin-style collectives.

Parallelism map (DESIGN.md Sec. 8):
  * batch:       ("pod", "data")
  * TP:          attention heads / d_ff / vocab over "model"
  * FSDP:        parameter d_model (or widest non-TP) dim over "data";
                 optimizer state inherits parameter sharding (ZeRO)
  * EP:          MoE experts over "model"
  * SP:          long-context activations over "data" (sequence dim)

Logical axis vocabulary used by the model zoo:
  "batch", "seq", "vocab", "embed" (d_model), "heads", "kv_heads",
  "head_dim", "mlp" (d_ff), "experts", "expert_mlp", "ssm_inner",
  "ssm_state", "ssm_heads", "image", null (replicated)
"""
from __future__ import annotations

from jax.sharding import PartitionSpec as P

# Production mesh axis widths (launch/mesh.py) — used at init time to
# pick divisibility-safe parameter shardings.
POD_AXIS_SIZE = 2
DATA_AXIS_SIZE = 16
MODEL_AXIS_SIZE = 16

# logical name -> mesh axes (None = replicated)
LOGICAL_RULES: dict[str, tuple[str, ...] | str | None] = {
    "batch": ("pod", "data"),
    "batch_data": "data",
    "seq": None,
    "seq_sp": "data",          # sequence-parallel variant
    "vocab": "model",
    "embed": "data",           # FSDP shard of d_model
    "embed_tp": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "experts": "model",
    "expert_mlp": None,
    "ssm_inner": "model",
    "ssm_state": None,
    "ssm_heads": "model",
    "image": None,
    "layers": None,            # stacked-scan leading axis
    None: None,
}


def spec(*logical: str | None) -> P:
    """PartitionSpec from logical axis names, e.g.
    spec("embed", "mlp") -> P("data", "model")."""
    axes = []
    for name in logical:
        rule = LOGICAL_RULES[name]
        axes.append(rule)
    return P(*axes)


def batch_spec(extra_dims: int = 1) -> P:
    return P(("pod", "data"), *([None] * extra_dims))


# --- population ("pop") axis specs for the sharded co-search engines.
POP_AXIS = "pop"
LOGICAL_RULES["members"] = POP_AXIS     # population / fleet-member axis

def member_spec(extra_dims: int = 0) -> P:
    """(P, ...) member-leading tensors: theta, orders, SpecParams
    leaves.  `extra_dims` trailing dims stay unsharded."""
    return P(POP_AXIS, *([None] * extra_dims))


def segment_member_spec(extra_dims: int = 0) -> P:
    """(S, P, ...) per-segment stacked outputs of the fused scan: the
    segment axis leads, the member axis is sharded."""
    return P(None, POP_AXIS, *([None] * extra_dims))


# Activation constraint specs.  Attention uses Ulysses-style sequence
# parallelism over "model" (all-to-all between D-sharded projections and
# S-sharded attention core) — uniform across head counts (28-head qwen2,
# kv=4/8 GQA) with zero replicated compute.
ACT_TOKENS = P(("pod", "data"), None, None)          # (B, S, D)
ACT_TOKENS_TP = P(("pod", "data"), None, "model")    # (B, S, D_tp)
ACT_Q_ULYSSES = P(("pod", "data"), None, "model", None)  # (B,H,S_tp,hd)
ACT_KV_GATHERED = P(("pod", "data"), None, None, None)   # (B,Hkv,S,hd)
ACT_KV_DECODE = P(("pod", "data"), None, "model", None)  # cache: S_tp
ACT_GROUPS = P(("pod", "data"), None, None)          # MoE (G, T, D)


# Parallelism mode: "tp" (default: TP/EP over "model") or "dp" (pure
# data parallelism: "model" joins the batch axes; weights replicated
# across it).  The Sec. Perf hillclimb flips this for small models
# whose activation collectives dominate under 16-way TP.
_PARALLELISM = "tp"


def set_parallelism(mode: str) -> None:
    global _PARALLELISM
    assert mode in ("tp", "dp"), mode
    _PARALLELISM = mode


def _apply_mode(pspec: P) -> P:
    if _PARALLELISM == "tp":
        return pspec
    out = []
    for e in pspec:
        if e == "model":
            out.append(None)
        elif (isinstance(e, (tuple, list)) and "data" in e
              and "model" not in e):
            out.append(tuple(e) + ("model",))
        else:
            out.append(e)
    return P(*out)


def sanitize_spec(pspec: P, axis_names) -> P:
    """Apply the parallelism mode, then drop mesh-axis names not present
    in the active mesh (e.g. "pod" on the single-pod mesh)."""
    out = []
    for entry in _apply_mode(pspec):
        if entry is None:
            out.append(None)
        elif isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in axis_names)
            out.append(kept if len(kept) > 1 else
                       (kept[0] if kept else None))
        else:
            out.append(entry if entry in axis_names else None)
    return P(*out)


def constrain(x, pspec: P):
    """with_sharding_constraint that no-ops outside a mesh context (so
    single-device smoke tests run the same code) and tolerates meshes
    without the "pod" axis."""
    import jax
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty or not mesh.axis_names:
            return x
        clean = sanitize_spec(pspec, set(mesh.axis_names))
        return jax.lax.with_sharding_constraint(x, clean)
    # no-op fallbacks only for the expected shapes of "no usable mesh
    # here": older jax without get_abstract_mesh (AttributeError), or
    # a constraint rejected outside a mesh context (Type/Value/
    # RuntimeError).  Anything else is a real bug and propagates.
    except (AttributeError, TypeError, ValueError, RuntimeError):
        return x
