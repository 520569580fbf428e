"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.conv2d.ops import conv2d_stencil
from repro.kernels.conv2d.ref import conv2d_ref
from repro.kernels.flash.ops import flash_attention_tpu, flash_decode_tpu
from repro.kernels.flash.ref import attention_ref
from repro.kernels.sad.ops import sad_disparity
from repro.kernels.sad.ref import sad_ref

rng = np.random.RandomState(3)


def test_every_kernel_wrapper_interprets_on_cpu_and_compiles_elsewhere(
        monkeypatch):
    """The interpret decision is made once, from the platform, when a
    kernel is called: every kernel wrapper hands ``interpret=True`` to
    Pallas on the CPU backend and ``False`` on an accelerator."""
    from jax.experimental import pallas as pl

    from repro.core import Array2d, Input, Max, Reduce, Stencil, UInt
    from repro.core.lowering import lower_pipeline
    from repro.kernels import stream

    seen = []
    real = pl.pallas_call

    def spy(*args, **kw):
        seen.append(kw["interpret"])
        return real(*args, **kw)

    def call_every_wrapper():
        seen.clear()
        jax.clear_caches()              # re-trace the jitted wrappers
        x = Input(Array2d(UInt(8), 16, 8), "x")
        lp = lower_pipeline(Reduce(Max)(Stencil(0, 1, 0, 0)(x)),
                            backend="pallas")
        with jax.enable_x64(True):
            jax.eval_shape(lp.megakernels[0].apply,
                           jax.ShapeDtypeStruct((8, 16), jnp.int64))
        jax.eval_shape(lambda p, k: conv2d_stencil(p, k),
                       jax.ShapeDtypeStruct((15, 23), jnp.int32),
                       jax.ShapeDtypeStruct((8, 8), jnp.int32))
        jax.eval_shape(lambda a, b: sad_disparity(a, b, nd=4),
                       jax.ShapeDtypeStruct((15, 26), jnp.int32),
                       jax.ShapeDtypeStruct((15, 26), jnp.int32))
        q = jax.ShapeDtypeStruct((1, 8, 2, 128), jnp.float32)
        jax.eval_shape(flash_attention_tpu, q, q, q)
        return list(seen)

    monkeypatch.setattr(pl, "pallas_call", spy)
    assert stream.interpret_default() is True
    assert call_every_wrapper() == [True] * 4
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert stream.interpret_default() is False
    assert call_every_wrapper() == [False] * 4


@pytest.mark.parametrize("h,w,kh,kw,shift", [
    # one case per coverage class: lane-aligned 8x8, small 3x3, odd rows,
    # mid-size 5x5 (redundant shapes trimmed for tier-1 wall time)
    (16, 128, 8, 8, 11), (8, 32, 3, 3, 4), (9, 48, 8, 8, 11),
    (24, 64, 5, 5, 8),
])
def test_conv2d_kernel_vs_ref(h, w, kh, kw, shift):
    p = rng.randint(0, 256, (h + kh - 1, w + kw - 1)).astype(np.int32)
    k = rng.randint(0, 64, (kh, kw)).astype(np.int32)
    out = conv2d_stencil(p, k, shift=shift)
    ref = conv2d_ref(jnp.asarray(p), jnp.asarray(k), shift=shift)
    assert np.array_equal(out, ref)


def _sad_planes(kind, shape):
    """Random planes, or planes on which several disparities tie: constant
    (every disparity costs the same) or equal and periodic along the lanes
    with period 4 (cost 0 every 4 disparities)."""
    if kind == "random":
        return (rng.randint(0, 256, shape).astype(np.int32),
                rng.randint(0, 256, shape).astype(np.int32))
    if kind == "constant":
        return np.full(shape, 7, np.int32), np.full(shape, 200, np.int32)
    period = rng.randint(0, 256, (shape[0], 4)).astype(np.int32)
    plane = np.tile(period, (1, -(-shape[1] // 4)))[:, :shape[1]]
    return plane, plane.copy()


@pytest.mark.parametrize("h,w,nd,bh,bw,kind", [
    pytest.param(8, 24, 16, 8, 8, "random", id="8-24-16-8-8"),
    pytest.param(12, 40, 4, 4, 4, "random", id="12-40-4-4-4"),
    # several row tiles, and a height that no tile divides
    (45, 16, 8, 8, 8, "random"),
    # wider than one 128-lane register
    (8, 150, 16, 8, 8, "random"),
    # ties: the first least disparity wins
    (8, 24, 16, 8, 8, "constant"), (9, 20, 14, 8, 8, "periodic"),
    # the paper's 8x8 blocks over 64 disparities, at a small height
    (8, 16, 64, 8, 8, "random"),
])
def test_sad_kernel_vs_ref(h, w, nd, bh, bw, kind):
    L, R = _sad_planes(kind, (h + bh - 1, w + bw - 1 + nd - 1))
    out = sad_disparity(L, R, nd=nd, bh=bh, bw=bw)
    ref = sad_ref(jnp.asarray(L), jnp.asarray(R), nd=nd, bh=bh, bw=bw)
    assert np.array_equal(out, ref)
    if kind != "random":      # disparity 0 aligns column j + nd - 1 with j
        first = 0 if kind == "constant" else (nd - 1) % 4
        assert np.all(np.asarray(out) == first)


@pytest.mark.parametrize("B,S,H,Hkv,D,window,dtype,atol", [
    # coverage classes: GQA f32, windowed bf16, MHA D=256 f32, ragged bf16
    (2, 48, 4, 2, 128, None, jnp.float32, 2e-5),
    (2, 48, 4, 4, 128, 13, jnp.bfloat16, 3e-2),
    (1, 64, 8, 2, 256, None, jnp.float32, 2e-5),
    (1, 40, 4, 1, 128, None, jnp.bfloat16, 3e-2),
])
def test_flash_kernel_vs_ref(B, S, H, Hkv, D, window, dtype, atol):
    q = jnp.asarray(rng.randn(B, S, H, D), dtype)
    k = jnp.asarray(rng.randn(B, S, Hkv, D), dtype)
    v = jnp.asarray(rng.randn(B, S, Hkv, D), dtype)
    out = flash_attention_tpu(q, k, v, causal=True, window=window,
                              bq=16, bk=16)
    ref = attention_ref(q, k, v, causal=True, window=window)
    assert np.allclose(np.asarray(out, np.float32), ref, atol=atol)


def test_flash_decode_vs_ref():
    B, S, H, Hkv, D = 2, 64, 8, 2, 128
    q = jnp.asarray(rng.randn(B, 1, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.float32)
    out = flash_decode_tpu(q, k, v, bk=32)
    ref = attention_ref(q, k, v, causal=False)
    assert np.allclose(out, ref, atol=2e-5)


def test_model_flash_vjp_vs_naive():
    """The model-side flash custom_vjp (pure JAX) matches naive gradients."""
    from repro.models.layers import flash_attention, naive_attention
    B, S, H, Hkv, D = 2, 33, 4, 2, 16
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, Hkv, D), jnp.float32)
    f = lambda q, k, v: flash_attention(q, k, v, True, None, 16, False).sum()
    n = lambda q, k, v: naive_attention(q, k, v, causal=True).sum()
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(n, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gn):
        assert np.allclose(a, b, atol=3e-4)
