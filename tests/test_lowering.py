"""Cross-backend equivalence suite: the lowering compiler (core/lowering/)
must be *bit-identical* to the numpy reference executor on every backend —
"jax" (jnp lowering + jnp-level fusions under the segmented jit engine) and
"pallas" (the above + fused dispatch to the resident Pallas kernels) — for
the paper's four apps, the PYRAMID app, randomized DAGs over the point-op
vocabulary, and every fusion-guard boundary."""
import numpy as np
import pytest

from repro.core import (AddAsync, AddMSBs, Array2d, Const, Map, Mul, Crop,
                        Downsample, Input, Pad, Reduce, RemoveMSBs, Rshift,
                        Stencil, UInt, Upsample)
from repro.core.dtypes import Int
from repro.core.executor import evaluate
from repro.core.hwimg import (Abs, AbsDiff, Add, External, Max, Min, Sub,
                              scalar_of)
from repro.core.lowering import lower_pipeline

APPS = ["convolution", "stereo", "flow", "descriptor", "pyramid"]
BACKENDS = ["jax", "pallas"]

rng_global = np.random.RandomState(11)


def _eq(a, b):
    if isinstance(a, tuple):
        return len(a) == len(b) and all(_eq(x, y) for x, y in zip(a, b))
    return np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("app", APPS)
def test_apps_cross_backend_bit_exact(app, backend, lowering_cases):
    design, inputs_fn = lowering_cases[app]
    inp = inputs_fn(np.random.RandomState(11))
    assert _eq(design.run(inp), design.run(inp, backend=backend))


def test_conv2d_fusion_dispatches_to_pallas_kernel(lowering_cases):
    design, _ = lowering_cases["convolution"]
    lp = design.lower("pallas")
    assert any("kernels/conv2d" in n for n in lp.notes), lp.notes
    assert len(lp.fusions) == 1
    assert any("kernels/conv2d" in n for n in design.notes)  # report


def test_sad_fusion_dispatches_to_pallas_kernel(lowering_cases):
    design, _ = lowering_cases["stereo"]
    lp = design.lower("pallas")
    assert any("kernels/sad" in n for n in lp.notes), lp.notes
    assert len(lp.fusions) == 1


@pytest.mark.parametrize("app,expected", [("flow", 5), ("descriptor", 3)])
def test_second_moment_window_fusions_fire(app, expected, lowering_cases):
    """The FLOW second-moment block (Ix·Iy products -> box-sum) fuses into
    jnp window-reduces on the jax backend.  On pallas, megakernel emission
    subsumes the window_sum rule: the chains stream inside the fused
    kernel, where the same box sums lower to separable row/lane sums."""
    design, _ = lowering_cases[app]
    lp = design.lower("jax")
    assert len(lp.fusions) == expected, lp.notes
    assert all(d.kernel == "window_sum" for d in lp.fusions.values())

    lp = design.lower("pallas")
    assert not any(d.kernel == "window_sum" for d in lp.fusions.values())
    assert any(f"{expected} box-sum chain(s) as separable sums" in n
               for n in lp.notes), lp.notes


def test_pyramid_chains_collapse(lowering_cases):
    """Down/Down and Up/Up chains collapse to combined-stride nodes."""
    design, _ = lowering_cases["pyramid"]
    lp = design.lower("jax")
    assert lp.graph_rewrites == 2, lp.notes
    assert any("Downsample(4x4)" in n for n in lp.notes), lp.notes
    assert any("Upsample(4x4)" in n for n in lp.notes), lp.notes


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("app", ["convolution", "stereo", "flow"])
def test_run_batch_matches_per_frame(app, backend, lowering_cases):
    """vmap-over-frames (the throughput entry point) == per-frame loop."""
    design, inputs_fn = lowering_cases[app]
    batch = inputs_fn(np.random.RandomState(3), frames=3)
    assert _eq(design.run_batch(batch), design.run_batch(batch, backend=backend))


# ---- fusion guard boundaries ----

def _conv_chain(acc_widen, w=24, h=16):
    """Stencil->Mul->AddMSBs(acc_widen)->Reduce->Rshift->RemoveMSBs chain;
    u16 products widened to a (16+acc_widen)-bit accumulator, u8 output."""
    rng = np.random.RandomState(5)
    inp = Input(Array2d(UInt(8), w, h), "x")
    k = rng.randint(128, 256, (8, 8)).astype(np.int64)
    st = Stencil(-7, 0, -7, 0)(inp)
    prod = Map(Mul)(st, Const(Array2d(UInt(8), 8, 8), k))
    s = Reduce(AddAsync)(Map(AddMSBs(acc_widen))(prod))
    out = Map(RemoveMSBs(8 + acc_widen))(Map(Rshift(3))(s))
    x = rng.randint(0, 256, (h, w)).astype(np.int64)
    return out, x


def test_conv2d_wrap_guard_boundary():
    """max_sum = (2^8-1)^2 * 64 = 4161600: a u22 accumulator (2^22 >
    max_sum) fuses, a u21 accumulator (2^21 <= max_sum) must fall back —
    and both stay bit-exact."""
    for widen, want_fused in ((6, True), (5, False)):
        out, x = _conv_chain(widen)
        lp = lower_pipeline(out, backend="pallas")
        assert (len(lp.fusions) == 1) == want_fused, lp.notes
        assert _eq(evaluate(out, {"x": x}), lp({"x": x}))


def test_sad_wrap_guard_boundary():
    """SAD max_sum = (2^8-1)*bh*bw: a u14 accumulator takes the fusion at
    8x8 blocks (16320 < 16384), a u13 one must fall back, bit-exact."""
    from repro.core import ArgMin, ReducePatch, Replicate, TupleT
    rng = np.random.RandomState(7)
    for widen, want_fused in ((6, True), (5, False)):
        img = Array2d(UInt(8), 32, 16)
        inp = Input(TupleT((img, img)), "p")
        left, right = inp[0], inp[1]
        cand = Stencil(-7, 0, 0, 0)(right)
        diff = Map(AbsDiff)(Replicate(8, 1)(left), cand)
        wide = Map(AddMSBs(widen))(diff)          # u(8+widen) accumulator
        patches = Stencil(-7, 0, -7, 0)(wide)
        out = ArgMin(ReducePatch(AddAsync)(patches))
        lp = lower_pipeline(out, backend="pallas")
        assert (len(lp.fusions) == 1) == want_fused, lp.notes
        l = rng.randint(0, 256, (16, 32)).astype(np.int64)
        r = np.roll(l, 2, axis=-1)
        assert _eq(evaluate(out, {"p": (l, r)}), lp({"p": (l, r)}))


@pytest.mark.parametrize("bits,bh", [(9, 8), (8, 10)])
def test_sad_fusion_needs_8_bit_images_and_short_blocks(bits, bh):
    """Images wider than 8 bits (|L - R| not exact in bfloat16) or blocks
    taller than one halo block below a row fall back, bit-exact."""
    from repro.core import ArgMin, ReducePatch, Replicate, TupleT
    rng = np.random.RandomState(11)
    img = Array2d(UInt(bits), 24, 20)
    inp = Input(TupleT((img, img)), "p")
    left, right = inp[0], inp[1]
    cand = Stencil(-3, 0, 0, 0)(right)
    diff = Map(AbsDiff)(Replicate(4, 1)(left), cand)
    patches = Stencil(-3, 0, -(bh - 1), 0)(Map(AddMSBs(12))(diff))
    out = ArgMin(ReducePatch(AddAsync)(patches))
    lp = lower_pipeline(out, backend="pallas")
    assert not lp.fusions, lp.notes
    l = rng.randint(0, 2 ** bits, (20, 24)).astype(np.int64)
    r = np.roll(l, 2, axis=-1)
    assert _eq(evaluate(out, {"p": (l, r)}), lp({"p": (l, r)}))


def test_multi_consumer_stencil_is_not_fused():
    """A stencil whose patches feed a second consumer must not be claimed
    by the conv2d fusion (interior single-consumer discipline)."""
    rng = np.random.RandomState(5)
    inp = Input(Array2d(UInt(8), 24, 16), "x")
    k = rng.randint(0, 16, (4, 4)).astype(np.int64)
    st = Stencil(-3, 0, -3, 0)(inp)
    prod = Map(Mul)(st, Const(Array2d(UInt(8), 4, 4), k))
    s = Reduce(AddAsync)(Map(AddMSBs(16))(prod))
    u8 = Map(RemoveMSBs(24))(Map(Rshift(4))(s))
    other = Reduce(Max)(st)                       # second consumer of st
    out = Map(Add)(u8, other)
    lp = lower_pipeline(out, backend="pallas")
    assert not lp.fusions, lp.notes
    x = rng.randint(0, 256, (16, 24)).astype(np.int64)
    assert _eq(evaluate(out, {"x": x}), lp({"x": x}))


def test_unsafe_conv_chain_is_not_fused_but_stays_exact():
    """A conv chain whose u16 accumulator wraps fails the exactness guard:
    the matcher must fall back to the generic lowering and still match the
    executor bit-for-bit."""
    rng = np.random.RandomState(5)
    inp = Input(Array2d(UInt(8), 24, 16), "x")
    k = rng.randint(0, 256, (8, 8)).astype(np.int64)
    st = Stencil(-7, 0, -7, 0)(inp)
    prod = Map(Mul)(st, Const(Array2d(UInt(8), 8, 8), k))  # u16 products
    s = Reduce(AddAsync)(prod)                             # u16 acc: wraps!
    out = Map(RemoveMSBs(8))(Map(Rshift(3))(s))
    lp = lower_pipeline(out, backend="pallas")
    assert not lp.fusions
    x = rng.randint(0, 256, (16, 24)).astype(np.int64)
    assert _eq(evaluate(out, {"x": x}), lp({"x": x}))


# ---- the three new rewrite rules ----

@pytest.mark.parametrize("backend", BACKENDS)
def test_separable_filter_split(backend):
    """A rank-1 integer kernel splits into two 1-D conv passes on the jax
    backend.  On pallas, megakernel emission subsumes the separable split:
    the whole chain streams inside one fused kernel instead."""
    rng = np.random.RandomState(3)
    inp = Input(Array2d(UInt(8), 24, 16), "x")
    k = np.outer([1, 2, 3, 2], [1, 1, 2, 1]).astype(np.int64)
    st = Stencil(-3, 0, -3, 0)(inp)
    prod = Map(Mul)(st, Const(Array2d(UInt(8), 4, 4), k))
    out = Reduce(AddAsync)(Map(AddMSBs(16))(prod))
    lp = lower_pipeline(out, backend=backend)
    if backend == "jax":
        assert [d.kernel for d in lp.fusions.values()] == ["separable_conv"]
    else:
        assert not lp.fusions
        assert len(lp.megakernels) == 1, lp.notes
    x = rng.randint(0, 256, (16, 24)).astype(np.int64)
    assert _eq(evaluate(out, {"x": x}), lp({"x": x}))


def test_separable_signed_sobel_kernel():
    """Sobel is rank-1 over the integers with signed factors."""
    rng = np.random.RandomState(3)
    sob = np.array([[-1, 0, 1], [-2, 0, 2], [-1, 0, 1]], dtype=np.int64)
    inp = Input(Array2d(UInt(8), 24, 16), "x")
    st = Stencil(-1, 1, -1, 1)(inp)
    out = Reduce(AddAsync)(Map(Mul)(st, Const(Array2d(Int(8), 3, 3), sob)))
    lp = lower_pipeline(out, backend="jax")
    assert [d.kernel for d in lp.fusions.values()] == ["separable_conv"]
    x = rng.randint(0, 256, (16, 24)).astype(np.int64)
    assert _eq(evaluate(out, {"x": x}), lp({"x": x}))


def test_full_rank_kernel_is_not_split():
    rng = np.random.RandomState(3)
    inp = Input(Array2d(UInt(8), 24, 16), "x")
    k = rng.randint(1, 16, (4, 4)).astype(np.int64)
    assert np.linalg.matrix_rank(k) > 1
    st = Stencil(-3, 0, -3, 0)(inp)
    out = Reduce(AddAsync)(Map(AddMSBs(16))(
        Map(Mul)(st, Const(Array2d(UInt(8), 4, 4), k))))
    lp = lower_pipeline(out, backend="jax")
    assert not lp.fusions
    x = rng.randint(0, 256, (16, 24)).astype(np.int64)
    assert _eq(evaluate(out, {"x": x}), lp({"x": x}))


def test_separable_app_kernel_fires_in_convolution_pipeline():
    """Convolution(kernel=separable_kernel()) takes the separable split on
    the jax backend and the conv2d Pallas dispatch on pallas."""
    from repro.apps import Convolution
    from repro.apps.convolution import separable_kernel
    from repro.core import compile_pipeline
    design = compile_pipeline(Convolution(w=96, h=40,
                                          kernel=separable_kernel()))
    assert [d.kernel for d in design.lower("jax").fusions.values()] == \
        ["separable_conv"]
    assert [d.kernel for d in design.lower("pallas").fusions.values()] == \
        ["conv2d"]
    rng = np.random.RandomState(1)
    inp = {"convolution.in": rng.randint(0, 256, (40, 96)).astype(np.int64)}
    ref = design.run(inp)
    assert _eq(ref, design.run(inp, backend="jax"))
    assert _eq(ref, design.run(inp, backend="pallas"))


@pytest.mark.parametrize("backend", BACKENDS)
def test_rewire_of_dispatch_leaf_terminates(backend):
    """A Down(Up) identity pair feeding a fused region's leaf: the rewire
    must retarget the dispatch's leaf (regression: the rewired node stayed
    live through the dispatch and the fixpoint loop never terminated)."""
    rng = np.random.RandomState(8)
    inp = Input(Array2d(UInt(8), 24, 16), "x")
    idn = Downsample(2, 2)(Upsample(2, 2)(inp))
    k = np.outer([1, 2, 1], [1, 3, 1]).astype(np.int64)
    st = Stencil(-2, 0, -2, 0)(idn)
    out = Reduce(AddAsync)(Map(AddMSBs(16))(
        Map(Mul)(st, Const(Array2d(UInt(8), 3, 3), k))))
    lp = lower_pipeline(out, backend=backend)     # regression: used to hang
    assert lp.graph_rewrites == 1, lp.notes
    if backend == "jax":
        assert [d.kernel for d in lp.fusions.values()] == ["separable_conv"]
    else:                       # megakernel emission subsumes the split
        assert not lp.fusions and len(lp.megakernels) == 1, lp.notes
    x = rng.randint(0, 256, (16, 24)).astype(np.int64)
    assert _eq(evaluate(out, {"x": x}), lp({"x": x}))


def test_down_up_identity_collapses_and_up_down_does_not():
    rng = np.random.RandomState(9)
    inp = Input(Array2d(UInt(8), 16, 12), "x")
    x = rng.randint(0, 256, (12, 16)).astype(np.int64)

    idn = Downsample(2, 2)(Upsample(2, 2)(inp))   # identity
    out = Map(AbsDiff)(inp, idn)
    lp = lower_pipeline(out, backend="jax")
    assert lp.graph_rewrites == 1, lp.notes
    assert _eq(evaluate(out, {"x": x}), lp({"x": x}))

    nid = Upsample(2, 2)(Downsample(2, 2)(inp))   # NOT an identity
    lp2 = lower_pipeline(nid, backend="jax")
    assert lp2.graph_rewrites == 0
    assert _eq(evaluate(nid, {"x": x}), lp2({"x": x}))


# ---- External ops through pure_callback (jit + run_batch) ----

@pytest.mark.parametrize("bits", [10, 40])
def test_external_traces_under_jit_and_run_batch(bits):
    """External numpy models lower through jax.pure_callback with declared
    result shapes/dtypes (x64-proof transport), so they work under the jit
    engine and under vmapped run_batch — narrow and wide carriers."""
    rng = np.random.RandomState(2)
    inp = Input(Array2d(UInt(8), 24, 16), "x")

    def ext_fn(a):
        return np.asarray(a) * 1234567 + 3

    e = External("aff", Array2d(UInt(bits), 24, 16), ext_fn, inp)
    out = Map(AddMSBs(2))(e)
    lp = lower_pipeline(out, backend="jax")
    x = rng.randint(0, 256, (16, 24)).astype(np.int64)
    assert _eq(evaluate(out, {"x": x}), lp({"x": x}))
    xb = rng.randint(0, 256, (3, 16, 24)).astype(np.int64)
    ref_b = np.stack([evaluate(out, {"x": xb[i]}) for i in range(3)])
    assert _eq(ref_b, lp.run_batch({"x": xb}))


def test_run_batch_const_across_segment_boundary():
    """A const-derived value exported from a vmapped program segment gets
    broadcast onto the frame axis (vmap out_axes=0); the next segment must
    treat it as batched. Regression: ToFloat -> FloatMul -> FloatSub(., C)
    splits at the f32 mul->sub boundary with the Const crossing it."""
    from repro.core import Float, FloatMul, FloatSub, ToFloat
    rng = np.random.RandomState(6)
    inp = Input(Array2d(UInt(8), 8, 6), "x")
    sq = Map(FloatMul)(Map(ToFloat)(inp), Map(ToFloat)(inp))
    out = Map(FloatSub)(sq, Const(Float(8, 24), np.float32(3.5)))
    lp = lower_pipeline(out, backend="jax")
    assert len(lp._plan) > 1          # the FMA rule actually split here
    xb = rng.randint(0, 256, (3, 6, 8)).astype(np.int64)
    ref = np.stack([evaluate(out, {"x": xb[i]}) for i in range(3)])
    got = lp.run_batch({"x": xb})
    assert got.shape == ref.shape
    assert _eq(ref, got)


def test_fma_partition_rule_matches_runtime_probe():
    """ROADMAP "known gaps": the engine used to hardcode the XLA:CPU
    assumption that an f32 mul feeding an add/sub contracts to FMA inside
    one program; the partition rule now follows a runtime probe. The probe
    must agree with an independently jit'd residual computation, and the
    partitioner must split a mul->sub pipeline exactly when the probe says
    the backend contracts."""
    import jax
    import jax.numpy as jnp

    from repro.core import Float, FloatMul, FloatSub, ToFloat
    from repro.core.lowering.engine import backend_contracts_fma
    if jax.default_backend() == "tpu":
        pytest.xfail("TPU contraction/rounding not yet validated "
                     "(ROADMAP known gap)")
    probe = backend_contracts_fma()
    # independent numeric witness of the same question: x*x - round(x*x)
    # is 0 under two-step IEEE semantics, 2^-24 under a contracted FMA
    x = np.float32(1 + 2 ** -12)
    p = np.float32(x * x)
    fused = np.asarray(jax.jit(lambda a, b: a * a - b)(jnp.float32(x),
                                                       jnp.float32(p)))
    assert probe == bool(fused != np.float32(0.0))
    # the partition rule must match: a minimal f32 mul->sub pipeline
    # splits into >1 program segments iff the backend contracts
    inp = Input(Array2d(UInt(8), 8, 6), "x")
    sq = Map(FloatMul)(Map(ToFloat)(inp), Map(ToFloat)(inp))
    out = Map(FloatSub)(sq, Const(Float(8, 24), np.float32(3.5)))
    lp = lower_pipeline(out, backend="jax")
    assert (len(lp._plan) > 1) == probe


# ---- engine surface: debug path, cache stats, report ----

def test_debug_path_and_node_values():
    rng = np.random.RandomState(4)
    inp = Input(Array2d(UInt(8), 12, 8), "x")
    out = Map(Abs)(Map(Sub)(inp, Map(Rshift(1))(inp)))
    lp = lower_pipeline(out, backend="jax", debug=True)
    x = rng.randint(0, 256, (8, 12)).astype(np.int64)
    assert _eq(evaluate(out, {"x": x}), lp({"x": x}))
    vals = lp.node_values({"x": x})
    assert set(vals) == {n.uid for n in lp.ir.order}
    assert _eq(vals[out.uid], evaluate(out, {"x": x}))


def test_jit_cache_stats_and_design_report(lowering_cases):
    design, inputs_fn = lowering_cases["convolution"]
    lp = design.lower("pallas")
    inp = inputs_fn(np.random.RandomState(11))
    design.run(inp, backend="pallas")
    design.run(inp, backend="pallas")
    stats = "\n".join(lp.cache_stats())
    assert "jit[frame]" in stats
    report = design.report()
    assert "kernels/conv2d" in report          # fused-dispatch note
    assert "jit[frame]" in report              # per-signature cache stats


# ---- property-style randomized DAGs over the point-op vocabulary ----

_BINARY = [Add, Sub, Mul, Max, Min, AbsDiff]


def _random_pointop_dag(rng, n_inputs=2, h=6, w=9):
    vals = [Input(Array2d(UInt(8), w, h), f"in{i}") for i in range(n_inputs)]
    for _ in range(rng.randint(4, 10)):
        if rng.rand() < 0.6:
            a, b = (vals[rng.randint(len(vals))] for _ in range(2))
            fn = _BINARY[rng.randint(len(_BINARY))]
            if fn is Mul and (scalar_of(a.ty).bits()
                              + scalar_of(b.ty).bits()) > 40:
                continue                  # keep carriers inside int64
            vals.append(Map(fn)(a, b))
        else:
            a = vals[rng.randint(len(vals))]
            bits = scalar_of(a.ty).bits()
            kind = rng.randint(4)
            if kind == 0:
                fn = Abs
            elif kind == 1:
                fn = Rshift(int(rng.randint(0, 5)))
            elif kind == 2 and bits < 40:
                fn = AddMSBs(int(rng.randint(1, 5)))
            elif bits > 2:
                fn = RemoveMSBs(int(rng.randint(1, bits - 1)))
            else:
                continue
            vals.append(Map(fn)(a))
    return vals[-1], n_inputs, h, w


@pytest.mark.parametrize("seed", range(6))
def test_random_pointop_dags_cross_backend(seed):
    rng = np.random.RandomState(100 + seed)
    out, n_inputs, h, w = _random_pointop_dag(rng)
    inputs = {f"in{i}": rng.randint(0, 256, (h, w)).astype(np.int64)
              for i in range(n_inputs)}
    ref = evaluate(out, inputs)
    for backend in BACKENDS:
        assert _eq(ref, lower_pipeline(out, backend=backend)(inputs)), \
            (seed, backend)


@pytest.mark.parametrize("backend", BACKENDS)
def test_structural_ops_cross_backend(backend):
    """Pad / centered Stencil / Crop / Downsample / Upsample — the
    geometry ops, in a shape the kernel fusion matchers must not claim."""
    rng = np.random.RandomState(9)
    inp = Input(Array2d(UInt(8), 16, 12), "x")
    k = rng.randint(0, 16, (3, 3)).astype(np.int64)
    g = Pad(2, 1, 1, 2)(inp)
    st = Stencil(-1, 1, -1, 1)(g)          # centered window
    prod = Map(Mul)(st, Const(Array2d(UInt(8), 3, 3), k))
    s = Reduce(AddAsync)(Map(AddMSBs(8))(prod))
    c = Crop(1, 1, 1, 1)(s)
    out = Upsample(2, 2)(Downsample(2, 2)(c))
    lp = lower_pipeline(out, backend=backend)
    x = rng.randint(0, 256, (12, 16)).astype(np.int64)
    assert _eq(evaluate(out, {"x": x}), lp({"x": x}))


# ---- declared-width output carrier ----

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("ty,device", [
    (UInt(1), "uint32"), (UInt(8), "uint32"), (UInt(9), "uint32"),
    (UInt(16), "uint32"), (UInt(17), "uint32"), (UInt(32), "uint32"),
    (UInt(33), "int64"), (Int(12), "int32")])
def test_output_carrier_type_edges(ty, device, backend):
    """A point-op root of at most 32 bits leaves the device on the 32-bit
    integer of its signedness (int64 above 32 bits) and comes back to
    every caller as the executor's int64, sign included."""
    from repro.serve import split_frames
    rng = np.random.RandomState(ty.bits())
    x, y = Input(Array2d(ty, 9, 6), "x"), Input(Array2d(ty, 9, 6), "y")
    out = Map(Min)(x, y)
    lo, hi = ((-(1 << (ty.bits() - 1)), 1 << (ty.bits() - 1))
              if isinstance(ty, Int) else (0, 1 << ty.bits()))
    xb = rng.randint(lo, hi, (3, 6, 9), dtype=np.int64)
    yb = rng.randint(lo, hi, (3, 6, 9), dtype=np.int64)
    xb[:, 0, 0] = yb[:, 0, 0] = hi - 1          # both ends of the range
    xb[:, 0, 1] = lo
    lp = lower_pipeline(out, backend=backend)
    dev = lp.run_batch_device({"x": xb, "y": yb})
    assert str(dev.dtype) == device
    refs = [evaluate(out, {"x": xb[i], "y": yb[i]}) for i in range(3)]
    got = lp.run_batch({"x": xb, "y": yb})
    assert got.dtype == np.int64 and _eq(np.stack(refs), got)
    for i, f in enumerate(split_frames(dev, 3)):
        assert f.dtype == np.int64 and _eq(refs[i], f)
        one = lp({"x": xb[i], "y": yb[i]})
        assert one.dtype == np.int64 and _eq(refs[i], one)
    if isinstance(ty, Int):
        assert got.min() < 0 and got.min() == np.stack(refs).min()


def test_lowering_report_names_output_carrier(lowering_cases):
    """The report names the root's device dtype where it narrows (STEREO's
    disparity) and says nothing for a float root (FLOW)."""
    stereo, _ = lowering_cases["stereo"]
    lp = stereo.lower("pallas")
    assert "output carrier: uint32 for Uint(3) (was int64)" in lp.notes
    assert "output carrier: uint32" in stereo.lowering_report()
    flow, _ = lowering_cases["flow"]
    assert not any("output carrier" in n for n in flow.lower("pallas").notes)
