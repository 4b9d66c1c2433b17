"""Production training entry point.

    python -m repro.launch.train --arch qwen3_0_6b --steps 200 \
        --batch 8 --seq 512 [--reduced] [--ckpt-dir ckpts] [--resume]

On a real TPU slice this runs under the production mesh
(launch/mesh.py) with the shardings from the model's spec tree; on CPU
(tests/examples) it runs single-device with identical code — sharding
constraints no-op outside a mesh.  Fault tolerance (checkpoint/restart,
straggler logging) comes from repro.runtime.fault_tolerance.
"""
from __future__ import annotations

import argparse

import jax

from ..configs import get_config
from ..data.pipeline import DataConfig
from ..models.lm import build_model
from ..runtime.fault_tolerance import DriverConfig, train_with_recovery
from ..train.optimizer import OptConfig
from ..train.train_step import TrainConfig, make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_0_6b")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized variant of the architecture")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from ..runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    model = build_model(cfg)
    params, specs = model.init(jax.random.PRNGKey(args.seed))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"[train] {cfg.name}{' (reduced)' if args.reduced else ''}: "
          f"{n_params/1e6:.1f}M params, {len(jax.devices())} device(s)")

    tcfg = TrainConfig(opt=OptConfig(lr=args.lr, warmup_steps=20),
                       microbatches=args.microbatches)
    train_step, init_opt = make_train_step(model, tcfg)
    opt_state = init_opt(tcfg.opt, params)

    data_cfg = DataConfig(seed=args.seed, vocab_size=cfg.vocab_size,
                          seq_len=args.seq, global_batch=args.batch,
                          modality=cfg.modality, d_model=cfg.d_model,
                          n_image_tokens=cfg.n_image_tokens)
    dcfg = DriverConfig(total_steps=args.steps,
                        ckpt_every=args.ckpt_every,
                        ckpt_dir=args.ckpt_dir)
    params, opt_state, report = train_with_recovery(
        jax.jit(train_step), params, opt_state, data_cfg, dcfg)
    print(f"[train] done: {report.steps_run} steps, "
          f"{report.restarts} restarts, "
          f"loss {report.losses[0]:.3f} -> {report.losses[-1]:.3f}")


if __name__ == "__main__":
    main()
