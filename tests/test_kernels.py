"""Pallas kernel validation (interpret=True): shape/dtype sweeps vs the
pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sketch import make_plan, selection_matrices, compress, decompress
from repro.core import ssop as ssop_core
from repro.kernels.count_sketch import ops as cs_ops
from repro.kernels.count_sketch.ref import compress_ref, decompress_ref
from repro.kernels.flash_attention.kernel import flash_attention_bhsd
from repro.kernels.flash_attention.ref import attention_bhsd_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.lora import ops as lora_ops
from repro.kernels.lora.ref import lora_matmul_ref
from repro.kernels.ssop import ops as ssop_ops
from repro.kernels.ssop.ref import ssop_apply_ref

TOL = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
       jnp.bfloat16: dict(atol=5e-2, rtol=5e-2)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bh,bkv,sq,sk,d,causal,window,bq,bk", [
    (8, 2, 128, 128, 64, True, 0, 64, 64),
    (4, 4, 256, 256, 32, True, 64, 128, 64),
    (4, 2, 128, 256, 64, False, 0, 128, 128),
    (2, 1, 64, 512, 128, True, 128, 64, 128),
])
def test_flash_attention_sweep(dtype, bh, bkv, sq, sk, d, causal, window,
                               bq, bk):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (bh, sq, d), dtype)
    k = jax.random.normal(keys[1], (bkv, sk, d), dtype)
    v = jax.random.normal(keys[2], (bkv, sk, d), dtype)
    out = flash_attention_bhsd(q, k, v, causal=causal, window=window,
                               bq=bq, bk=bk)
    ref = attention_bhsd_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


def test_flash_attention_bshd_wrapper():
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 128, 8, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 128, 2, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 128, 2, 32))
    out = fa_ops.flash_attention(q, k, v, causal=True, bq=64, bk=64)
    from repro.models.common import gqa_attention
    ref = gqa_attention(q, k, v, causal=True, chunk=4096)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("t,d,y,z", [(64, 256, 3, 32), (128, 512, 5, 64),
                                     (32, 128, 4, 16),
                                     (512, 768, 3, 121)])   # BERT-base
def test_count_sketch_kernels(t, d, y, z):
    h = jax.random.normal(jax.random.PRNGKey(0), (t, d))
    plan = make_plan(d, y, z, seed=3)
    s = selection_matrices(plan)
    u_k = cs_ops.sketch_compress(h, plan)
    np.testing.assert_allclose(np.asarray(u_k), np.asarray(compress_ref(h, s)),
                               atol=1e-5)
    # kernel compress == core (scatter) compress
    np.testing.assert_allclose(np.asarray(u_k),
                               np.asarray(compress(h, plan, via_matmul=False)),
                               atol=1e-4)
    d_k = cs_ops.sketch_decompress(u_k, plan)
    np.testing.assert_allclose(np.asarray(d_k),
                               np.asarray(decompress_ref(u_k, s)), atol=1e-5)
    np.testing.assert_allclose(np.asarray(d_k),
                               np.asarray(decompress(u_k, plan)), atol=1e-4)


@pytest.mark.parametrize("t,d,r", [(64, 256, 8), (128, 512, 16), (32, 128, 4)])
def test_ssop_kernel(t, d, r):
    j = jax.random.normal(jax.random.PRNGKey(0), (40, d))
    so = ssop_core.make_ssop(j, r, "salt", 5)
    h = jax.random.normal(jax.random.PRNGKey(1), (t, d))
    w = so.v.T - jnp.eye(r)
    out_k = ssop_ops.ssop_apply(h, so.u, so.v)
    np.testing.assert_allclose(np.asarray(out_k),
                               np.asarray(ssop_apply_ref(h, so.u, w)),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_k),
                               np.asarray(ssop_core.apply_ssop(h, so)),
                               atol=1e-5)
    # kernel inverse restores exactly
    back = ssop_ops.ssop_apply_inverse(out_k, so.u, so.v)
    np.testing.assert_allclose(np.asarray(back), np.asarray(h), atol=1e-4)


@pytest.mark.parametrize("t,k,o,r", [(64, 128, 256, 8), (128, 256, 128, 16),
                                     (256, 768, 768, 8)])   # BERT-base
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_lora_kernel(t, k, o, r, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (t, k), dtype)
    w = (jax.random.normal(ks[1], (k, o)) * 0.05).astype(dtype)
    a = (jax.random.normal(ks[2], (k, r)) * 0.05).astype(dtype)
    b = (jax.random.normal(ks[3], (r, o)) * 0.05).astype(dtype)
    out = lora_ops.lora_matmul(x, w, a, b, 2.0)
    ref = lora_matmul_ref(x, w, a, b, 2.0)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])
