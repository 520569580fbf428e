"""Megakernel contract suite (core/lowering/megakernel.py).

The megakernel emitter promises a two-tier verification contract against
the numpy reference executor:

- integer pipelines (and every integer output of a mixed pipeline) are
  **bit-exact**;
- float segments are within ``FLOAT_ULP_BOUND`` ULPs per element (on CPU
  the emitter is currently bit-exact too — ``_exact_f32_mul`` blocks
  LLVM's FMA re-contraction — but the *contract* is the ULP bound, which
  is what real-hardware FMA/reassociation may consume).

Also covered here: the mul->add no-split regression (a fused f32 segment
must stay one megakernel instead of splitting at the FMA-contract
boundary the generic path uses), the traced-offset streaming path
(explicit ``block_rows`` grid) against the whole-frame fast path, Const
hoisting, the Downsample divisibility gate, and the serving call path.
"""
import numpy as np
import pytest

import jax

from repro.core import (AddMSBs, Array2d, Const, Crop, Downsample, Input,
                        Map, Max, Mul, Pad, Reduce, Stencil, UInt)
from repro.core.executor import evaluate
from repro.core.lowering import lower_pipeline
from repro.core.lowering.megakernel import FLOAT_ULP_BOUND, emit_megakernel

APPS = ["convolution", "stereo", "flow", "descriptor", "pyramid"]
MK_APPS = ["flow", "descriptor", "pyramid"]   # apps with >=1 fused segment
FLOAT_APPS = ["flow", "descriptor"]


def _flat(o):
    if isinstance(o, tuple):
        return [x for e in o for x in _flat(e)]
    return [np.asarray(o)]


def _f32_lex(x):
    """Map f32 bit patterns to a monotone int64 space so adjacent
    representable floats differ by exactly 1 (the ULP metric)."""
    u = np.asarray(x, np.float32).view(np.uint32).astype(np.int64)
    return np.where(u < 2 ** 31, u + 2 ** 31, 2 ** 32 - u)


def _ulp_diff(a, b):
    return int(np.max(np.abs(_f32_lex(a) - _f32_lex(b)), initial=0))


def _mk_tasks(lp):
    return [t for t in lp._plan if hasattr(t, "mk")]


@pytest.mark.parametrize("app", APPS)
def test_two_tier_contract_vs_reference(app, lowering_cases):
    """Integer outputs bit-exact, float outputs within FLOAT_ULP_BOUND of
    the numpy reference — the contract every fused segment must honor."""
    design, inputs_fn = lowering_cases[app]
    inp = inputs_fn(np.random.RandomState(23))
    ref, out = design.run(inp), design.run(inp, backend="pallas")
    for r, o in zip(_flat(ref), _flat(out)):
        assert r.shape == o.shape and r.dtype == o.dtype
        if r.dtype.kind == "f":
            assert not np.isnan(o).any()
            assert _ulp_diff(r, o) <= FLOAT_ULP_BOUND
        else:
            assert np.array_equal(r, o)
    stats = design.lower("pallas").megakernel_stats()
    if app in MK_APPS:
        assert stats["segments"] >= 1 and stats["fused_nodes"] >= 2
        assert stats["linebuf_bytes"] > 0


@pytest.mark.parametrize("app", FLOAT_APPS)
def test_fused_f32_segment_does_not_split_at_mul_add(app, lowering_cases):
    """Regression: the generic path splits float segments at every mul->add
    boundary (the FMA-contraction contract); a megakernel folds that
    decision per segment, so the FloatMul and its FloatSub/FloatAdd
    consumer live in ONE fused kernel and the pallas plan has fewer
    segments than the jax plan."""
    design, _ = lowering_cases[app]
    lp, lpj = design.lower("pallas"), design.lower("jax")
    stats = lp.megakernel_stats()
    assert stats["float_nodes"] > 0
    assert stats["total_segments"] < len(lpj._plan)
    fused_pair = False
    for t in _mk_tasks(lp):
        uids = {n.uid for n in t.nodes}
        for n in t.nodes:
            if n.op != "Map" or n.params["fn"].name != "FloatMul":
                continue
            for c in n.consumers:
                cn = lp.ir.nodes[c]
                if (c in uids and cn.op == "Map"
                        and cn.params["fn"].name in ("FloatAdd", "FloatSub")):
                    fused_pair = True
    assert fused_pair, "no FloatMul->FloatAdd/Sub pair inside a megakernel"


@pytest.mark.parametrize("app", MK_APPS)
def test_streaming_grid_matches_whole_frame_emission(app, lowering_cases):
    """Re-emit every fused segment at block_rows=4 (a multi-step grid, so
    row offsets are traced scalars through the gather path) and check it
    bit-matches the whole-frame single-block emission."""
    design, inputs_fn = lowering_cases[app]
    lp = design.lower("pallas")
    vals = lp.node_values(inputs_fn(np.random.RandomState(7)))
    assert _mk_tasks(lp), "expected at least one megakernel segment"
    for t in _mk_tasks(lp):
        mk4 = emit_megakernel(lp.ir, t.nodes, t.in_uids, t.out_uids,
                              name=t.mk.name + "_s4", block_rows=4)
        invals = [vals[u] for u in t.in_uids]
        with jax.enable_x64(True):
            a = jax.jit(t.mk.apply)(*invals)
            b = jax.jit(mk4.apply)(*invals)
        for x, y in zip(a, b):
            assert np.array_equal(np.asarray(x), np.asarray(y))


def _const_stencil_pipeline(w):
    rng = np.random.RandomState(3)
    x = Input(Array2d(UInt(8), w, 12), "x")
    k = rng.randint(0, 16, (3, 3)).astype(np.int64)
    st = Stencil(-1, 1, -1, 1)(Pad(1, 1, 1, 1)(x))
    prod = Map(Mul)(st, Const(Array2d(UInt(8), 3, 3), k))
    m = Reduce(Max)(Map(AddMSBs(8))(prod))   # Max: not conv2d, not winsum
    return Crop(1, 1, 1, 1)(m), rng


def test_const_hoisting_and_geometry_ops_stream():
    """A Pad/Stencil/Crop chain with a Const kernel operand must emit as
    one megakernel (the Const hoisted to a VMEM-resident leaf) and stay
    bit-exact against the reference executor."""
    out, rng = _const_stencil_pipeline(16)
    lp = lower_pipeline(out, backend="pallas")
    assert len(lp.megakernels) == 1
    assert not any("megakernel fallback" in n for n in lp.notes)
    x = rng.randint(0, 256, (12, 16)).astype(np.int64)
    assert np.array_equal(evaluate(out, {"x": x}), lp({"x": x}))


@pytest.mark.parametrize("w", [16, 17])
def test_downsample_divisibility_gate(w):
    """Downsample streams only when the strides divide the frame dims
    (type layer floors, executor stride-slices — they agree exactly on
    divisible frames).  A 17-wide frame must fall back to the generic
    path for the Downsample node and still match the reference."""
    out, rng = _const_stencil_pipeline(w)
    out = Downsample(2, 2)(out)
    lp = lower_pipeline(out, backend="pallas")
    in_mk = any(n.op == "Downsample"
                for t in _mk_tasks(lp) for n in t.nodes)
    assert in_mk == (w % 2 == 0)
    x = rng.randint(0, 256, (12, w)).astype(np.int64)
    assert np.array_equal(evaluate(out, {"x": x}), lp({"x": x}))


def test_serve_path_accepts_megakernel_programs(lowering_cases):
    """run_batch_device must take a megakernel plan unchanged: batched
    execution through the same fused kernels, results still on device."""
    design, inputs_fn = lowering_cases["flow"]
    lp = design.lower("pallas")
    assert _mk_tasks(lp)
    batch = inputs_fn(np.random.RandomState(3), frames=3)
    out = lp.run_batch_device(batch)
    leaves = jax.tree_util.tree_leaves(out)
    assert leaves and all(isinstance(x, jax.Array) for x in leaves)
    ref = _flat(design.run_batch(batch))
    assert len(leaves) == len(ref)
    for d, r in zip(leaves, ref):
        assert np.array_equal(np.asarray(d), r)


def test_lowering_report_lists_megakernel_segments(lowering_cases):
    """HWDesign.lowering_report() names each fused segment with its node
    count and VMEM line-buffer bytes once the pallas backend exists."""
    design, _ = lowering_cases["flow"]
    lp = design.lower("pallas")
    report = design.lowering_report()
    for mk in lp.megakernels:
        assert mk.report_line() in report
        assert mk.name in report
    assert "line-buffer" in report or "linebuf" in report
