"""Serving entry point: prefill + batched greedy decode.

    python -m repro.launch.serve --arch qwen3_0_6b --reduced \
        --batch 4 --prompt-len 16 --gen 32 [--ckpt-dir ckpts]
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..models.lm import build_model
from ..obs import telemetry as _obs
from ..serve.serve_step import make_serve_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_0_6b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    from ..runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(args.seed))
    if args.ckpt_dir:
        from ..checkpoint import checkpoint as ckpt
        step, state = ckpt.restore(args.ckpt_dir)
        params = state["params"]
        print(f"[serve] restored step {step} from {args.ckpt_dir}")

    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(
        rng.integers(1, cfg.vocab_size,
                     (args.batch, args.prompt_len)), jnp.int32)
    max_seq = args.prompt_len + args.gen
    cache = model.init_cache(args.batch, max_seq)
    step_fn = jax.jit(make_serve_step(model))

    tok = prompts[:, :1]
    out = [tok]
    t0 = _obs.default_clock()
    for pos in range(max_seq - 1):
        nxt, cache = step_fn(params, cache, tok, jnp.int32(pos))
        tok = (prompts[:, pos + 1:pos + 2]
               if pos + 1 < args.prompt_len else nxt)
        out.append(tok)
    seq = jnp.concatenate(out, axis=1)
    dt = _obs.default_clock() - t0
    print(f"[serve] {args.batch} seqs x {max_seq} tokens in {dt:.1f}s "
          f"({args.batch*max_seq/dt:.1f} tok/s)")
    print("[serve] sample:", np.asarray(seq[0, :32]).tolist())


if __name__ == "__main__":
    main()
