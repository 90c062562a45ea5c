"""Compute-op tests: the Pallas flash attention kernel vs the reference.

Runs in Pallas interpreter mode on CPU (the kernel auto-selects interpret
off-TPU); the same kernel compiles for real TPU (validated in CI bench
sessions).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tritonclient_tpu.ops import dot_product_attention, flash_attention


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(2, 256, 4, 64), (1, 128, 2, 32)])
def test_flash_matches_reference(causal, shape):
    b, l, h, d = shape
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    got = flash_attention(q, k, v, causal=causal)
    ref = dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_multi_tile_accumulation():
    # More K tiles than Q tiles: the online-softmax carry across the
    # innermost grid dimension is what this exercises.
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 2, 32), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 512, 2, 32), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(3), (1, 512, 2, 32), jnp.float32)
    got = flash_attention(q, k, v, block_q=64, block_k=128)
    ref = dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_bf16_dtype_preserved():
    q = jax.random.normal(jax.random.PRNGKey(4), (1, 256, 2, 64), jnp.bfloat16)
    got = flash_attention(q, q, q, causal=True)
    assert got.dtype == jnp.bfloat16
    ref = dot_product_attention(q, q, q, causal=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32),
        rtol=2e-2, atol=2e-2,
    )


def test_flash_untileable_shapes_fall_back(monkeypatch):
    # Odd lengths cannot tile onto TPU-aligned blocks; the wrapper must take
    # the reference path (asserted, not assumed) and still be correct.
    import importlib

    # The function re-exported from ops/__init__ shadows the submodule
    # attribute; importlib resolves the real module.
    fa_mod = importlib.import_module("tritonclient_tpu.ops.flash_attention")

    def boom(*args, **kwargs):
        raise AssertionError("kernel path taken for untileable shape")

    monkeypatch.setattr(fa_mod, "_flash", boom)
    q = jax.random.normal(jax.random.PRNGKey(5), (1, 100, 2, 16), jnp.float32)
    got = fa_mod.flash_attention(q, q, q, causal=True)
    ref = dot_product_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="compiled (Mosaic) kernel path needs a real TPU; CI runs the "
    "interpreter path. Run chip_smoke.py on hardware.",
)
def test_flash_compiles_on_tpu_bert_base_shape():
    # bert_base: H=12, d=64 — d below the 128-lane tile, relying on Mosaic
    # lane padding; this is exactly the lowering the guard cannot prove.
    q = jax.random.normal(jax.random.PRNGKey(7), (2, 128, 12, 64), jnp.float32)
    got = flash_attention(q, q, q, interpret=False)
    ref = dot_product_attention(q, q, q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_flash_under_jit_and_grad():
    q = jax.random.normal(jax.random.PRNGKey(6), (1, 128, 2, 32), jnp.float32)

    @jax.jit
    def f(x):
        return flash_attention(x, x, x, causal=True).sum()

    assert np.isfinite(float(f(q)))

    # The fused Pallas backward must match the reference gradient.
    grad_flash = jax.grad(
        lambda x: flash_attention(x, x, x, causal=True).sum()
    )(q)
    grad_ref = jax.grad(
        lambda x: dot_product_attention(x, x, x, causal=True).sum()
    )(q)
    np.testing.assert_allclose(np.asarray(grad_flash), np.asarray(grad_ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_fused_backward_per_input_grads(causal):
    # Separate q/k/v cotangents through the fused dq and dk/dv kernels,
    # weighted so per-row deltas differ (a uniform .sum() would mask
    # delta-handling bugs).
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(8), 3)
    shape = (2, 256, 4, 64)
    q = jax.random.normal(kq, shape, jnp.float32)
    k = jax.random.normal(kk, shape, jnp.float32)
    v = jax.random.normal(kv, shape, jnp.float32)
    w = jnp.arange(shape[-1], dtype=jnp.float32)

    def loss(fn):
        return lambda a, b, c: (fn(a, b, c) * w).sum()

    got = jax.grad(
        loss(lambda a, b, c: flash_attention(a, b, c, causal=causal)),
        argnums=(0, 1, 2),
    )(q, k, v)
    ref = jax.grad(
        loss(lambda a, b, c: dot_product_attention(a, b, c, causal=causal)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for g, r, name in zip(got, ref, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-3, atol=5e-4,
                                   err_msg=f"d{name} causal={causal}")


def test_flash_fused_backward_rectangular():
    # Lq != Lk exercises the independent num_q/num_k grids of the two
    # backward kernels.
    q = jax.random.normal(jax.random.PRNGKey(9), (1, 256, 2, 32), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(10), (1, 128, 2, 32), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(11), (1, 128, 2, 32), jnp.float32)
    got = jax.grad(
        lambda a, b, c: (flash_attention(a, b, c) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    ref = jax.grad(
        lambda a, b, c: (dot_product_attention(a, b, c) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for g, r, name in zip(got, ref, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=1e-3, atol=5e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("causal", [False, True])
def test_flash_return_lse_matches_logsumexp(causal):
    import math

    q = jax.random.normal(jax.random.PRNGKey(12), (2, 256, 2, 32), jnp.float32)
    o, lse = flash_attention(q, q, q, causal=causal, return_lse=True)
    assert lse.shape == (2, 256, 2) and lse.dtype == jnp.float32
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q * scale, q)
    if causal:
        keep = jnp.arange(256)[:, None] >= jnp.arange(256)[None, :]
        s = jnp.where(keep[None, None], s, -1e30)
    ref = jnp.transpose(jax.scipy.special.logsumexp(s, axis=-1), (0, 2, 1))
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_flash_lse_cotangent_exact():
    # Ring attention differentiates through the returned LSE; the backward
    # kernels fold that cotangent into the delta term. Compare against the
    # materializing reference of the same (o, lse) function.
    import importlib

    fa_mod = importlib.import_module("tritonclient_tpu.ops.flash_attention")
    q = jax.random.normal(jax.random.PRNGKey(13), (1, 256, 2, 32), jnp.float32)
    wl = jnp.linspace(0.1, 1.0, 256)[None, :, None]

    def loss(fn):
        def f(x):
            o, lse = fn(x)
            return (o * 0.3).sum() + (lse * wl).sum()
        return f

    got = jax.grad(loss(
        lambda x: flash_attention(x, x, x, causal=True, return_lse=True)
    ))(q)
    ref = jax.grad(loss(
        lambda x: fa_mod._reference_with_lse(x, x, x, True,
                                             1.0 / np.sqrt(32.0))
    ))(q)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-3, atol=5e-4)
