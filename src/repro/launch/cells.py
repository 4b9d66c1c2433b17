"""Dry-run cells: (architecture x input shape x mesh) lowering.

`run_cell` builds ShapeDtypeStruct stand-ins for every input (weights,
optimizer state, batch or KV cache — no allocation), lowers the
train/serve step under the production mesh with full shardings,
compiles it, and extracts:

  * memory_analysis()      — bytes per device (proves it fits),
  * cost_analysis()        — per-device HLO FLOPs / bytes accessed,
  * collective bytes       — parsed from the partitioned HLO text
                             (all-gather / all-reduce / reduce-scatter /
                             all-to-all / collective-permute),

which EXPERIMENTS.md Sec. Roofline consumes.  This module performs NO
device-count manipulation — `dryrun.py` owns XLA_FLAGS.
"""
from __future__ import annotations

import dataclasses
import re

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs import get_config
from ..configs.base import SHAPES, ArchConfig, ShapeConfig, \
    shape_applicable
from ..models.lm import build_model
from ..train.optimizer import OptConfig
from ..train.train_step import (TrainConfig, make_train_step,
                                opt_state_specs)
from .mesh import make_production_mesh

DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
               "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8,
               "u16": 2, "s16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
               "c64": 8, "u64": 8}

# bytes moved on the wire per element, ring algorithms
COLLECTIVE_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0,
                     "reduce-scatter": 1.0, "all-to-all": 1.0,
                     "collective-permute": 1.0}

_HLO_RE = re.compile(
    r"=\s*(?:\()?((?:f|bf|s|u|pred|c)[\w\d]*)\[([\d,]*)\][^)]*?\)?\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)\(")


def parse_collective_bytes(hlo_text: str) -> dict[str, float]:
    """Sum per-device collective bytes by op kind from partitioned HLO."""
    out: dict[str, float] = {k: 0.0 for k in COLLECTIVE_FACTOR}
    count = 0
    for m in _HLO_RE.finditer(hlo_text):
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        elems = 1
        for d in dims.split(","):
            if d:
                elems *= int(d)
        nbytes = elems * DTYPE_BYTES.get(dtype, 4)
        out[kind] += nbytes * COLLECTIVE_FACTOR[kind]
        count += 1
    out["n_ops"] = count
    out["total"] = sum(v for k, v in out.items()
                       if k in COLLECTIVE_FACTOR)
    return out


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins)
# ---------------------------------------------------------------------------

def batch_struct(cfg: ArchConfig, shape: ShapeConfig) -> dict:
    b, s = shape.global_batch, shape.seq_len
    if cfg.modality == "audio":
        return {"frames": jax.ShapeDtypeStruct((b, s, cfg.d_model),
                                               jnp.bfloat16),
                "labels": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    out = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
    if cfg.modality == "vision+text":
        out["image_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.n_image_tokens, cfg.d_model), jnp.bfloat16)
    return out


def batch_specs(cfg: ArchConfig, shape: ShapeConfig,
                batch_shardable: bool) -> dict:
    bspec = ("pod", "data") if batch_shardable else None
    if cfg.modality == "audio":
        return {"frames": P(bspec, None, None), "labels": P(bspec, None)}
    out = {"tokens": P(bspec, None)}
    if cfg.modality == "vision+text":
        out["image_embeds"] = P(bspec, None, None)
    return out


def input_specs(arch: str, shape_name: str):
    """Public helper: ShapeDtypeStructs for an (arch, shape) cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    if shape.mode == "decode":
        model = build_model(cfg)
        cache = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len))
        return {"tokens": jax.ShapeDtypeStruct(
                    (shape.global_batch, 1), jnp.int32),
                "position": jax.ShapeDtypeStruct((), jnp.int32),
                "cache": cache}
    return batch_struct(cfg, shape)


# ---------------------------------------------------------------------------
# Cell lowering
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CellResult:
    arch: str
    shape: str
    mesh: str
    mode: str
    ok: bool
    skip_reason: str = ""
    error: str = ""
    flops: float = 0.0
    bytes_accessed: float = 0.0
    collectives: dict | None = None
    memory: dict | None = None
    n_params: float = 0.0
    lower_s: float = 0.0
    compile_s: float = 0.0

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _named(mesh, spec_tree):
    from ..sharding.rules import sanitize_spec
    names = set(mesh.axis_names)
    return jax.tree.map(
        lambda sp: NamedSharding(mesh, sanitize_spec(sp, names)),
        spec_tree, is_leaf=lambda x: isinstance(x, P))


def _mem_analysis(compiled):
    try:
        ma = compiled.memory_analysis()
        if ma is None:
            return None
        return {k: float(getattr(ma, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(ma, k)}
    # memory_analysis is backend-dependent: absent attribute surfaces
    # as AttributeError, unsupported backends raise these two.
    except (AttributeError, NotImplementedError, RuntimeError):
        return None


def _lower_cell(cfg: ArchConfig, shape: ShapeConfig, mesh, mode: str,
                unroll: bool, train_overrides: dict | None = None):
    """Lower one step function under `mesh`; returns the Lowered."""
    model = build_model(cfg, unroll=unroll)
    batch_shardable = shape.global_batch % (
        mesh.devices.size // mesh.shape["model"]) == 0
    param_shapes, param_specs = model.abstract_init(
        jax.random.PRNGKey(0))
    p_shard = _named(mesh, param_specs)

    if mode == "train":
        tcfg = TrainConfig(**{"opt": OptConfig(),
                              **(train_overrides or {})})
        train_step, init_opt = make_train_step(model, tcfg)
        opt_shapes = jax.eval_shape(
            lambda p: init_opt(tcfg.opt, p), param_shapes)
        o_specs = opt_state_specs(param_specs, cfg.optimizer)
        o_shard = _named(mesh, o_specs)
        b_struct = batch_struct(cfg, shape)
        b_shard = _named(mesh, batch_specs(cfg, shape, batch_shardable))
        fn = jax.jit(train_step,
                     in_shardings=(p_shard, o_shard, b_shard),
                     out_shardings=(p_shard, o_shard, None))
        return fn.lower(param_shapes, opt_shapes, b_struct)
    if mode == "prefill":
        b_struct = batch_struct(cfg, shape)
        b_shard = _named(mesh, batch_specs(cfg, shape, batch_shardable))
        fn = jax.jit(model.prefill, in_shardings=(p_shard, b_shard))
        return fn.lower(param_shapes, b_struct)
    # decode
    cache_shapes = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len))
    c_specs = model.cache_specs(batch_shardable=batch_shardable)
    c_shard = _named(mesh, c_specs)
    tok = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    args = [param_shapes, cache_shapes, tok, pos]
    bspec = P(("pod", "data") if batch_shardable else None, None)
    in_sh = [p_shard, c_shard, _named(mesh, bspec),
             NamedSharding(mesh, P())]
    if cfg.modality == "vision+text":
        args.append(jax.ShapeDtypeStruct(
            (shape.global_batch, cfg.n_image_tokens, cfg.d_model),
            jnp.bfloat16))
        in_sh.append(_named(
            mesh, P(("pod", "data") if batch_shardable else None,
                    None, None)))
    fn = jax.jit(model.decode_step, in_shardings=tuple(in_sh),
                 out_shardings=(None, c_shard))
    return fn.lower(*args)


def _analyze(compiled):
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    try:
        hlo = compiled.as_text()
    # as_text is best-effort on some backends (the collective census
    # then degrades to zero, which run_cell reports as-is).
    except (NotImplementedError, RuntimeError, UnicodeDecodeError):
        hlo = ""
    coll = parse_collective_bytes(hlo)
    return flops, nbytes, coll


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             extrapolate: bool | None = None,
             cfg_overrides: dict | None = None,
             train_overrides: dict | None = None,
             parallelism: str = "tp") -> CellResult:
    """Full-depth lowering+compile (the proof + memory analysis), plus
    — on the single-pod mesh — unrolled depth-1/depth-2 lowerings whose
    cost difference gives the exact per-period FLOPs/bytes/collectives
    (XLA cost_analysis counts a while-loop body once regardless of trip
    count, so scanned stacks must be extrapolated)."""
    import dataclasses as dc

    from ..obs import telemetry as _obs

    from ..sharding.rules import set_parallelism
    set_parallelism(parallelism)
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dc.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    mode = shape.mode
    res = CellResult(arch=arch, shape=shape_name, mesh=mesh_name,
                     mode=mode, ok=False)
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        res.skip_reason = why
        return res
    if extrapolate is None:
        extrapolate = not multi_pod

    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    res.n_params = float(cfg.n_params())

    with jax.sharding.set_mesh(mesh):
        # Trace/lower/compile timed as engine.build-family telemetry
        # spans (visible when a tracer is enabled) on the shared clock.
        tracer = _obs.get_tracer()
        t0 = _obs.default_clock()
        with tracer.span("engine.lower", arch=arch, shape=shape_name):
            lowered = _lower_cell(cfg, shape, mesh, mode, unroll=False,
                                  train_overrides=train_overrides)
        t1 = _obs.default_clock()
        res.lower_s = t1 - t0
        with tracer.span("engine.compile", arch=arch, shape=shape_name):
            compiled = lowered.compile()
        res.compile_s = _obs.default_clock() - t1
        res.memory = _mem_analysis(compiled)
        res.flops, res.bytes_accessed, res.collectives = \
            _analyze(compiled)

        if extrapolate:
            period = len(model.slots)
            n_periods = model.n_periods
            costs = []
            for depth in (period, 2 * period):
                dcfg = dc.replace(cfg, n_layers=depth)
                low_d = _lower_cell(dcfg, shape, mesh, mode,
                                    unroll=True,
                                    train_overrides=train_overrides)
                costs.append(_analyze(low_d.compile()))
            (f1, b1, c1), (f2, b2, c2) = costs
            # clamp to the full-depth measurement: fusion differences
            # between depth-1/2 can make tiny deltas noisy (decode)
            res.flops = max(f1 + (n_periods - 1) * (f2 - f1), res.flops)
            res.bytes_accessed = max(
                b1 + (n_periods - 1) * (b2 - b1), res.bytes_accessed)
            res.collectives = {
                k: max(c1.get(k, 0.0) + (n_periods - 1)
                       * (c2.get(k, 0.0) - c1.get(k, 0.0)),
                       res.collectives.get(k, 0.0))
                for k in c1}
    res.ok = True
    return res


def all_cells():
    from ..configs import ARCH_IDS
    for arch in ARCH_IDS:
        for shape_name in SHAPES:
            yield arch, shape_name
