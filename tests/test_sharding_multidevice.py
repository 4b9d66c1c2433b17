"""Multi-device sharding tests — run in a SUBPROCESS with
--xla_force_host_platform_device_count=8 so the main test process keeps
its single real device (per the assignment's XLA_FLAGS rule)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.models.lm import build_model
    from repro.train.optimizer import OptConfig
    from repro.train.train_step import TrainConfig, make_train_step
    from repro.sharding.rules import sanitize_spec

    assert len(jax.devices()) == 8
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)

    cfg = get_config("qwen3_0_6b", reduced=True)
    import dataclasses
    cfg = dataclasses.replace(cfg, compute_dtype="float32")
    model = build_model(cfg)

    with jax.sharding.set_mesh(mesh):
        params, specs = model.init(jax.random.PRNGKey(0))
        names = set(mesh.axis_names)
        shardings = jax.tree.map(
            lambda sp: NamedSharding(mesh, sanitize_spec(sp, names)),
            specs, is_leaf=lambda x: isinstance(x, P))
        params = jax.tree.map(jax.device_put, params, shardings)

        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (4, 64)), jnp.int32)}
        batch = {k: jax.device_put(
            v, NamedSharding(mesh, P(("pod", "data"), None)))
            for k, v in batch.items()}

        tcfg = TrainConfig(opt=OptConfig(lr=1e-3, warmup_steps=1))
        train_step, init_opt = make_train_step(model, tcfg)
        opt_state = init_opt(tcfg.opt, params)
        p2, o2, metrics = jax.jit(train_step)(params, opt_state, batch)
        sharded_loss = float(metrics["loss"])

    # single-device replica for comparison (same params, same batch)
    params_r = jax.tree.map(lambda x: np.asarray(x), params)
    batch_r = {k: np.asarray(v) for k, v in batch.items()}
    loss_r, _ = jax.jit(model.train_loss)(
        jax.tree.map(jnp.asarray, params_r),
        {k: jnp.asarray(v) for k, v in batch_r.items()})
    print(json.dumps({"sharded": sharded_loss,
                      "replicated": float(loss_r)}))
""")


@pytest.mark.slow
def test_sharded_train_step_matches_single_device(tmp_path):
    script = tmp_path / "sharded.py"
    script.write_text(_SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath("src")
    r = subprocess.run([sys.executable, str(script)],
                       capture_output=True, text=True, env=env,
                       timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert abs(out["sharded"] - out["replicated"]) < 2e-2, out


# The sharded co-search engines must be BIT-identical to the
# single-device ones: the population/member axis only carries
# per-member ops, so sharding it is pure parallelism.  Asserted per
# seed on best_edp / n_evals / history for every shipped spec, for
# on-device seeding, and for a fleet group sharded over members.
_SEARCH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import json
    import jax
    import numpy as np

    from repro.core.archspec import TPU_V5E_SPEC, EDGE_SPEC
    from repro.core.fleet import search_group_results
    from repro.core.problem import Layer, Workload
    from repro.core.search import SearchConfig, dosa_search

    assert len(jax.devices()) == 8
    wl = Workload(layers=(Layer.conv(64, 64, 3, 56, name="c1"),
                          Layer.matmul(512, 1024, 768, name="m1")),
                  name="two")
    base = SearchConfig(steps=40, round_every=20, n_start_points=4,
                        seed=3)
    summary = {}

    def same(a, b):
        assert a.best_edp == b.best_edp, (a.best_edp, b.best_edp)
        assert a.n_evals == b.n_evals
        assert np.array_equal(np.asarray(a.history),
                              np.asarray(b.history))

    # -- single-target fused parity on every shipped spec ------------
    for name, spec in (("gemmini", None), ("tpu_v5e", TPU_V5E_SPEC),
                       ("edge", EDGE_SPEC)):
        cfg = dataclasses.replace(base, spec=spec, shards=1)
        ref = dosa_search(wl, cfg, population=4, fused=True)
        for sh in (2, 4, None):       # explicit counts + auto-resolve
            cfg_s = dataclasses.replace(cfg, shards=sh)
            same(ref, dosa_search(wl, cfg_s, population=4, fused=True))
        summary[name] = ref.best_edp

    # -- on-device seeding, sharded == unsharded ---------------------
    for sp in ("random-device", "cosa-device"):
        cfg = dataclasses.replace(base, start_points=sp, shards=1)
        ref = dosa_search(wl, cfg, population=4, fused=True)
        cfg_s = dataclasses.replace(cfg, shards=4)
        same(ref, dosa_search(wl, cfg_s, population=4, fused=True))
        summary[sp] = ref.best_edp

    # -- a fleet group (TPU v5e + edge share one engine) sharded over
    # the member axis ------------------------------------------------
    specs = [TPU_V5E_SPEC, EDGE_SPEC]
    cfg = dataclasses.replace(base, shards=1)
    refs = search_group_results(wl, specs, cfg, fused=True)
    for sh in (2, 4):
        cfg_s = dataclasses.replace(base, shards=sh)
        for a, b in zip(refs,
                        search_group_results(wl, specs, cfg_s,
                                             fused=True)):
            same(a, b)
    summary["fleet"] = [r.best_edp for r in refs]
    print(json.dumps(summary))
""")


@pytest.mark.slow
def test_sharded_fused_search_bit_identical(tmp_path):
    script = tmp_path / "sharded_search.py"
    script.write_text(_SEARCH_SCRIPT)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath("src")
    r = subprocess.run([sys.executable, str(script)],
                       capture_output=True, text=True, env=env,
                       timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    # the parity asserts live in the subprocess; sanity-check it really
    # searched everything
    for key in ("gemmini", "tpu_v5e", "edge", "random-device",
                "cosa-device", "fleet"):
        assert key in out, out
