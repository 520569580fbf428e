"""The main path's kernels compiled for a TPU v5e that is described, not
attached: what the chip's compiler would refuse fails here, at no chip
time.  Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a module-scoped fixture (only one
process at a time may load the TPU library, and every test worker imports
this file), and the persistent compilation cache is off around the
compiles (an entry written for a described chip cannot be read back).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.apps import PIPELINES
from repro.core import compile_pipeline
from repro.core.hwimg import type_shape
from repro.core.lowering.megakernel import _carrier_dtype, _elems


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:              # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile(one_chip, monkeypatch):
    """Compile for the described chip: cache off, and the kernels' one
    interpret decision (``kernels/stream.interpret_default``) steered to
    the accelerator answer."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    yield one_chip
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _shapes(ty, sharding, batch=()):
    legs = [jax.ShapeDtypeStruct(batch + type_shape(t), _carrier_dtype(t),
                                 sharding=sharding) for t in _elems(ty)]
    return tuple(legs) if len(legs) > 1 else legs[0]


def test_conv2d_compiles_at_1080p(chip_compile):
    from repro.kernels.conv2d.kernel import TILE_ROWS, conv2d_strips
    s = lambda shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                           sharding=chip_compile)
    c = jax.jit(lambda p, k: conv2d_strips(
        p, k, kh=8, kw=8, w_out=1920, shift=11, interpret=False)).lower(
            s((1080 + TILE_ROWS, 1920 + 7)), s((8, 8))).compile()
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().output_size_in_bytes == 1080 * 1920 * 4


@pytest.mark.parametrize("h,w", [(400, 720), (375, 1242)])
def test_sad_compiles_at_720x400_64_disparities(h, w, chip_compile):
    """STEREO's frame, and the KITTI frame (a height that is not a
    multiple of 8), in planes shaped as ``sad_disparity`` pads them."""
    from repro.kernels.sad.kernel import plane_rows, sad_strips
    w_in = w + 7 + 63
    shape = (plane_rows(h, w_in), w_in)
    s = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=chip_compile)
    c = jax.jit(lambda l, r: sad_strips(
        l, r, nd=64, bh=8, bw=8, w_out=w, interpret=False)).lower(
            s, s).compile()
    assert "tpu_custom_call" in c.as_text()
    assert c.memory_analysis().output_size_in_bytes >= h * w * 4


@pytest.mark.parametrize("app", ["flow", "pyramid", "descriptor"])
def test_megakernel_compiles_at_1080p(app, chip_compile):
    """Every fused segment of the app's pallas plan compiles as one Mosaic
    kernel, and no f64 reaches the program under the engine's x64."""
    lp = compile_pipeline(PIPELINES[app]()).lower("pallas")
    tasks = [t for t in lp._plan if hasattr(t, "mk")]
    assert tasks and not any("fallback" in n for n in lp.notes), lp.notes
    for t in tasks:
        args = [_shapes(lp.ir.nodes[u].ty, chip_compile) for u in t.in_uids]
        with jax.enable_x64(True):
            text = jax.jit(t.mk.apply).lower(*args).compile().as_text()
        assert text.count("tpu_custom_call") == 1
        assert "f64[" not in text


def test_generic_xla_path_compiles_with_int64_and_no_f64(chip_compile):
    """The jax backend keeps the int64 carrier on the chip: FLOW's generic
    plan (int64 box sums, f32 solve) compiles for it, and no Python or
    numpy float turned into f64 under the engine's x64."""
    lp = compile_pipeline(PIPELINES["flow"]()).lower("jax")
    inputs = {n.params["name"]: _shapes(n.ty, chip_compile)
              for n in lp._inputs}
    with jax.enable_x64(True):
        text = jax.jit(lambda x: lp._run(x, "frame")).lower(
            inputs).compile().as_text()
    assert "s64[" in text and "f64[" not in text
