"""Pallas kernel validation: shape/dtype sweeps in interpret mode
against the pure-jnp oracles (assignment requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.matmul.matmul import matmul
from repro.kernels.matmul.ref import matmul_ref

MM_SHAPES = [
    (128, 128, 128), (256, 512, 384), (64, 1024, 256), (512, 64, 128),
]
MM_BLOCKS = [(64, 64, 64), (128, 128, 128), (32, 128, 64)]


@pytest.mark.parametrize("shape", MM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_kernel_sweep(shape, dtype):
    m, k, n = shape
    key = jax.random.PRNGKey(m * 31 + n)
    x = jax.random.normal(jax.random.fold_in(key, 0), (m, k), dtype)
    y = jax.random.normal(jax.random.fold_in(key, 1), (k, n), dtype)
    for (bm, bk, bn) in MM_BLOCKS:
        if m % min(bm, m) or k % min(bk, k) or n % min(bn, n):
            continue
        out = matmul(x, y, bm=bm, bk=bk, bn=bn, interpret=True)
        ref = matmul_ref(x, y)
        tol = 1e-4 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(ref, np.float32),
            rtol=tol, atol=tol)


@pytest.mark.parametrize("sq,sk", [(128, 128), (256, 128), (128, 256)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(sq, sk, causal, dtype):
    if causal and sq != sk:
        pytest.skip("causal requires square here")
    bh, d = 3, 64
    key = jax.random.PRNGKey(sq + sk)
    q = jax.random.normal(jax.random.fold_in(key, 0), (bh, sq, d),
                          dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (bh, sk, d),
                          dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (bh, sk, d),
                          dtype)
    out = flash_attention(q, k, v, causal=causal, bq=64, bkv=64,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol,
                               atol=tol)


def test_tuned_matmul_wrapper():
    from repro.kernels.matmul.ops import tuned_matmul
    x = jax.random.normal(jax.random.PRNGKey(0), (256, 768))
    y = jax.random.normal(jax.random.PRNGKey(1), (768, 512))
    out = tuned_matmul(x, y, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(matmul_ref(x, y)), rtol=1e-4,
                               atol=1e-4)


def test_autotuner_respects_vmem_and_alignment():
    from repro.core.autotune import tune_matmul_blocks
    from repro.core.tpu_model import vmem_footprint
    from repro.core.arch import TPU_V5E
    res = tune_matmul_blocks(8192, 8192, 8192, steps=80)
    bm, bn, bk = res.blocks
    assert 8192 % bm == 0 and 8192 % bn == 0 and 8192 % bk == 0
    assert vmem_footprint(bm, bn, bk) <= TPU_V5E.vmem_bytes
    # MXU-aligned lanes
    assert bn % 128 == 0 and bk % 128 == 0
