#!/usr/bin/env python3
"""Knee sweep of an open-loop camera mix on the chip: one server, one
process, each step a schedule of ``K`` cameras at ``fps`` from the
``cameras`` generator (traffic.py) for ``--seconds``.

    python3 benchmarks/chip/knee.py --workload flow_1080p.cameras \\
        --seconds 10 --steps 1x30 1x36 2x20 2x30

Prints one JSON line per step: offered frames/s, latency p50/p95 over the
frames due in the step (timed from when each was due), the p50 of the
last third against the first third (a backlog that grows shows as a
rising tail), shed frames, and mean frames per batch.  The knee is the
highest offered rate whose p95 stays within one frame period (33.3 ms at
30 fps) with no growing backlog.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

import run
import traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--steps", nargs="+", required=True,
                    help="KxFPS: K cameras at FPS frames per second")
    args = ap.parse_args(argv)
    cell = run.Cell(args.workload)
    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    run.enable_compile_cache()
    devices = run.check_devices(cell.chips)
    from repro.apps import PIPELINES
    from repro.core import CompileOptions, compile_pipeline
    from repro.serve import FrameServer, ServeConfig
    cfg, pipe = cell.cfg, cell.cfg["pipeline"]
    design = compile_pipeline(PIPELINES[cfg["app"]](**pipe),
                              options=CompileOptions(backend="pallas"))
    pool = traffic.make_pool(cell.mix, pipe["h"], pipe["w"],
                             int(cell.mix["pool_frames"]), args.seed,
                             cell.images)
    frames = traffic.server_inputs(cfg["input"], pool)
    srv = FrameServer(ServeConfig(devices=list(devices)))
    srv.register(design, backend="pallas", warm_inputs=[frames[0]])
    srv.start()
    for step in args.steps:
        k, fps = step.split("x")
        mix = dict(cell.mix, cameras=int(k), fps=float(fps))
        offs = traffic.camera_schedule(mix, args.seed, args.seconds)
        idx = traffic.order(len(frames), len(offs), args.seed)
        rec = traffic.Recorder(len(offs), 0, args.seed)
        b0, f0 = srv.stats.batches, srv.stats.batch_frames
        traffic.run_open(srv.submit, frames, idx,
                         time.perf_counter() + 0.05 + offs, rec)
        traffic.wait_all(rec, 60.0)
        lat = np.where(rec.shed | rec.error | np.isnan(rec.done), np.inf,
                       (rec.done - rec.due) * 1e3)
        third = len(lat) // 3
        nb = max(srv.stats.batches - b0, 1)
        print(json.dumps({
            "cameras": int(k), "fps": float(fps),
            "offered_fps": len(offs) / args.seconds,
            "frames": len(offs), "shed": int(rec.shed.sum()),
            "p50_ms": run.nearest_rank(lat, 0.5),
            "p95_ms": run.nearest_rank(lat, 0.95),
            "p50_first_third_ms": run.nearest_rank(lat[:third], 0.5),
            "p50_last_third_ms": run.nearest_rank(lat[-third:], 0.5),
            "batch_frames_mean": (srv.stats.batch_frames - f0) / nb}),
            flush=True)
    srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
