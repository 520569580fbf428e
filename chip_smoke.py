#!/usr/bin/env python3
"""Chip smoke check: the main path of the system on a TPU, end to end.

For each of the five HWImg apps at the paper's frame sizes (CONVOLUTION,
FLOW and DESCRIPTOR at 1920x1080, STEREO at 720x400 with 64 disparities,
PYRAMID at 1920x1080 with 2 levels) this compiles the pipeline with
``compile_pipeline(..., options=CompileOptions(backend="pallas"))``,
serves frames made from a seed through one ``FrameServer`` on one chip, and
checks every served frame against a plain numpy reference: integer apps
bit-exact, float apps within ``FLOAT_ULP_BOUND`` ULPs.

    python chip_smoke.py              # one chip, all five apps
    python chip_smoke.py --chips 4    # only the sharded serve phase: the
                                      # frames served over 4 chips against
                                      # the same frames served on one chip

It exits non-zero, printing no result line, when JAX finds no TPU, when a
kernel would run in interpret mode, when FLOW, DESCRIPTOR or PYRAMID falls
back from its megakernel to the generic path, or when any phase or frame
fails.  On success the last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

APPS = ("convolution", "stereo", "flow", "descriptor", "pyramid")
MK_APPS = ("flow", "descriptor", "pyramid")   # must emit a megakernel
FRAMES = 8


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def make_frames(lp, n: int, seed: int):
    """``n`` input dicts of random u8 pixels (int64 carrier); a pair input
    (FLOW, STEREO) gets its second image as the first shifted 3 pixels."""
    import numpy as np
    from repro.core.hwimg import type_shape
    from repro.core.lowering.megakernel import _elems
    rng = np.random.RandomState(seed)
    frames = [dict() for _ in range(n)]
    for node in lp._inputs:
        legs = _elems(node.ty)
        h, w = type_shape(legs[0])[:2]
        stack = rng.randint(0, 256, (n, h, w)).astype(np.int64)
        for i in range(n):
            x = stack[i]
            frames[i][node.params["name"]] = (
                (x, np.roll(x, 3, axis=-1)) if len(legs) == 2 else x)
    return frames


def golden(app: str, inputs):
    """The app's independent numpy model, for apps whose executor run
    would not fit the host (STEREO's 64-disparity patches) or its time
    (FLOW's five 1080p 8x8 patch tensors)."""
    from repro.apps import golden_flow, golden_stereo
    (x, y), = inputs.values()
    return golden_stereo(x, y) if app == "stereo" else golden_flow(x, y)


def leaves(v):
    import numpy as np
    return [np.asarray(e) for e in (v if isinstance(v, tuple) else (v,))]


def ulp_distance(a, b) -> int:
    import numpy as np

    def lex(x):
        u = np.asarray(x, np.float32).view(np.uint32).astype(np.int64)
        return np.where(u < 2 ** 31, u + 2 ** 31, 2 ** 32 - u)
    return int(np.max(np.abs(lex(a) - lex(b)), initial=0))


def check_frame(app: str, got, ref, bound: int) -> int:
    """Largest ULP distance of the float leaves; integer leaves must be
    bit-exact.  Fails the run on any mismatch."""
    import numpy as np
    worst = 0
    g, r = leaves(got), leaves(ref)
    if len(g) != len(r):
        fail(f"{app}: {len(g)} output leaves, reference has {len(r)}")
    for a, b in zip(g, r):
        if a.shape != b.shape:
            fail(f"{app}: output shape {a.shape} != reference {b.shape}")
        if b.dtype.kind == "f":
            if not np.array_equal(np.isnan(a), np.isnan(b)):
                fail(f"{app}: NaN pattern differs from the reference")
            worst = max(worst, ulp_distance(a, b))
        elif not np.array_equal(a.astype(np.int64), b.astype(np.int64)):
            bad = int(np.sum(a.astype(np.int64) != b.astype(np.int64)))
            fail(f"{app}: {bad} integer element(s) differ from reference")
    if worst > bound:
        fail(f"{app}: {worst} ULPs from the reference (bound {bound})")
    return worst


def native_kernels(lp, frame) -> int:
    """Compiled Pallas kernels in the served batch program: a kernel left
    in interpret mode lowers to plain XLA ops and would not be counted."""
    import jax
    import numpy as np
    batch = {k: (tuple(np.stack([e] * FRAMES) for e in v)
                 if isinstance(v, tuple) else np.stack([v] * FRAMES))
             for k, v in frame.items()}
    with jax.enable_x64(True):
        text = jax.jit(lambda b: lp._run(b, "batch")).lower(batch).as_text()
    return text.count("tpu_custom_call")


def serve(design, app: str, frames, devices):
    """Serve ``frames`` as one batch through a FrameServer on
    ``devices``; returns (outputs, seconds of the warmup batch, which
    includes the XLA and Mosaic compiles)."""
    from repro.serve import FrameServer, ServeConfig
    cfg = ServeConfig(max_batch=len(frames), max_delay_ms=1000.0,
                      topup_hold_ms=1000.0, pad_pow2=False,
                      devices=list(devices))
    srv = FrameServer(cfg)
    srv.register(design, name=app, backend="pallas",
                 warm_inputs=[frames[0]])
    with srv:
        futs = srv.submit_many(frames)
        outs = [f.result(timeout=900) for f in futs]   # raises on failure
        if srv.stats.devices != len(devices):
            fail(f"{app}: served on {srv.stats.devices} device(s), "
                 f"wanted {len(devices)}")
    return outs, srv.stats.warmup_s


def compile_app(app: str):
    from repro.apps import PIPELINES
    from repro.core import CompileOptions, compile_pipeline
    t0 = time.perf_counter()
    design = compile_pipeline(PIPELINES[app](),
                              options=CompileOptions(backend="pallas"))
    lp = design.lower("pallas")
    return design, lp, time.perf_counter() - t0


def one_chip(dev) -> None:
    from repro.core.lowering.megakernel import FLOAT_ULP_BOUND
    for app in APPS:
        design, lp, t_design = compile_app(app)
        fallbacks = [n for n in lp.notes if "fallback" in n]
        for note in design.lowering_report().splitlines():
            print(f"[{app}] {note}", flush=True)
        if app in MK_APPS and (not lp.megakernels or fallbacks):
            fail(f"{app}: megakernel fell back to the generic path "
                 f"({fallbacks or 'no segment emitted'})")
        frames = make_frames(lp, FRAMES, seed=APPS.index(app))
        kernels = native_kernels(lp, frames[0])
        if kernels < 1:
            fail(f"{app}: no compiled Pallas kernel in the served program")
        outs, t_warm = serve(design, app, frames, [dev])
        ref_name = ("golden_" + app) if app in ("stereo", "flow") \
            else "numpy executor"
        worst = 0
        t0 = time.perf_counter()
        for frame, out in zip(frames, outs):
            ref = (golden(app, frame) if app in ("stereo", "flow")
                   else design.run(frame, backend="numpy"))
            worst = max(worst, check_frame(app, out, ref, FLOAT_ULP_BOUND))
        print(json.dumps({
            "app": app, "design_compile_s": t_design,
            "warmup_batch_s": t_warm,
            "megakernel_segments": len(lp.megakernels),
            "compiled_pallas_kernels": kernels,
            "fallback_notes": fallbacks, "frames_checked": len(outs),
            "reference": ref_name, "max_ulp": worst,
            "reference_s": time.perf_counter() - t0}),
            flush=True)


def four_chips(devs) -> None:
    """The frame axis sharded over four chips against the same frames
    served on one chip, in this process: outputs must be identical."""
    for app in APPS:
        design, lp, t_design = compile_app(app)
        frames = make_frames(lp, FRAMES, seed=APPS.index(app))
        one, t1 = serve(design, app, frames, devs[:1])
        four, t4 = serve(design, app, frames, devs[:4])
        for a, b in zip(one, four):
            for x, y in zip(leaves(a), leaves(b)):
                if x.shape != y.shape or not (x.tobytes() == y.tobytes()):
                    fail(f"{app}: 4-chip output differs from 1-chip output")
        print(json.dumps({
            "app": app, "design_compile_s": t_design,
            "warmup_batch_s_1chip": t1,
            "warmup_batch_s_4chip": t4,
            "frames_compared": len(frames), "identical": True}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded serve phase on 4 chips")
    args = ap.parse_args()

    import jax
    from repro.cache import enable_compile_cache
    from repro.kernels.stream import interpret_default

    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: JAX's default device is {devs[0].platform}")
    if len(devs) < args.chips:
        fail(f"{args.chips} chips wanted, {len(devs)} found")
    if interpret_default():
        fail("Pallas kernels would run in interpret mode")
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    if args.chips == 4:
        four_chips(devs)
    else:
        one_chip(devs[0])
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
