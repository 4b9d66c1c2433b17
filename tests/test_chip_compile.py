"""Compile the chip's main-path programs for a TPU v5e that is described,
not attached: the fused segment program of both `chip_smoke.py`
requests and of the Kimi-K2 decode step, the DOSA-tuned Pallas matmul
and the GQA flash attention.
The TPU compiler refuses here what the chip would refuse (VMEM
overflow, unaligned tiles, programs that do not fit), at no chip time.

The topology is described inside a fixture, never at import, so test
collection is the same in every pytest-xdist worker."""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import SHAPES, ShapeConfig
from repro.core.archspec import (GEMMINI_SPEC, TPU_V5E_SPEC, bucket_workload,
                                 resolve_spec)
from repro.core.autotune import default_blocks
from repro.core.search import SearchConfig, make_fused_runner
from repro.kernels.flash_attention.ops import gqa_flash_attention
from repro.kernels.matmul.ops import tuned_matmul
from repro.workloads.dnn_zoo import resnet50
from repro.workloads.lm_extract import extract

# The budget `chip_smoke.py` serves at; the service advances one
# rounding segment per dispatch.
SMOKE_CFG = SearchConfig(steps=100, round_every=25, n_start_points=8,
                         seed=0)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _smoke_request(name):
    if name == "resnet50-gemmini":
        return resnet50(), GEMMINI_SPEC
    if name == "kimi_k2-decode32k-ep48-tpu_v5e":
        # the whole decode step the Kimi-K2 benchmark cell serves, as
        # the service searches it (bucketed)
        return bucket_workload(extract(
            get_config("kimi_k2_1t"),
            ShapeConfig("decode32k", 32768, 24, "decode"), ep_chips=48,
            depth=5)), TPU_V5E_SPEC
    return extract(get_config("qwen3_0_6b"), SHAPES["decode_32k"]), \
        TPU_V5E_SPEC


@pytest.mark.parametrize("name", ["resnet50-gemmini",
                                  "qwen3_0_6b-decode_32k-tpu_v5e",
                                  "kimi_k2-decode32k-ep48-tpu_v5e"])
def test_fused_segment_program_compiles(one_chip, name):
    wl, spec = _smoke_request(name)
    cfg = dataclasses.replace(SMOKE_CFG, spec=spec)
    run_fused = make_fused_runner(wl, cfg)[0]
    n_levels = resolve_spec(spec).n_levels
    p, n_layers = cfg.n_start_points, len(wl.layers)
    theta = _sds((p, n_layers, 2, n_levels, 7), jnp.float32, one_chip)
    orders = _sds((p, n_layers, n_levels), jnp.int32, one_chip)
    compiled = run_fused.lower(theta, orders, n_full=1, rem=0,
                               seg_len=cfg.round_every,
                               shards=1).compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("mkn", [
    (4096, 3584, 18944),          # qwen2_7b d_model x d_ff
    (8192, 2048, 7168 // 16),     # tpu_autotune "kimi_expert"
])
def test_tuned_matmul_compiles(one_chip, mkn):
    m, k, n = mkn
    default_blocks(m, n, k)       # tune on the host, outside the trace
    x = _sds((m, k), jnp.bfloat16, one_chip)
    y = _sds((k, n), jnp.bfloat16, one_chip)
    compiled = jax.jit(tuned_matmul).lower(x, y).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gqa_flash_attention_compiles(one_chip):
    cfg = get_config("qwen3_0_6b")
    q = _sds((1, cfg.n_heads, 4096, cfg.head_dim), jnp.bfloat16, one_chip)
    kv = _sds((1, cfg.n_kv_heads, 4096, cfg.head_dim), jnp.bfloat16,
              one_chip)
    fn = jax.jit(functools.partial(gqa_flash_attention, causal=True))
    compiled = fn.lower(q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()
