#!/usr/bin/env python3
"""Chip benchmark of the HWTool frame server: one cell, one run.

    python3 benchmarks/chip/run.py --workload stereo_720x400.saturated \\
        --seed 7 --seconds 40 --trace 0

The cell is looked up by name in ``BENCHMARK.json`` at the root of the
checkout; its configuration file, traffic mix (``traffic/<name>.json``)
and per-layer metric readers (``metrics/<name>.py``) are found by the
names given there, so a new cell or metric is new files and entries only.

A run compiles the configuration's pipeline with the Pallas backend,
registers it with a ``FrameServer`` built with the program's default
``ServeConfig`` on the cell's chips (warming every batch size the server
warms), then drives ``FrameServer.submit`` with the mix for a short
pre-roll and ``--seconds`` of measured window.  Afterwards it compares a
seeded sample of the answers served in the window with the plain
reference beside the configuration, and prints one JSON line:
end-to-end metrics with ``--trace 0``; with ``--trace 1`` the per-layer
metrics, read from a profiler trace of the window.

It exits non-zero and prints no result when JAX finds no TPU, fewer
chips than the cell asks for, or would run the Pallas kernels in
interpret mode.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)

import compare  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402

PREROLL_S = 1.0         # traffic before the window opens, not measured
MAX_FPS_PER_CHIP = 5000  # records kept for a closed loop, per second
DRAIN_S = 60.0          # how long answers due in the window are awaited
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = os.path.join(ROOT, ".cache", "jax")
TRACE_DIR = os.path.join(ROOT, ".cache", "bench_trace")
WINDOW_SPAN = "bench.window"   # host span over the window in the trace


class Refused(RuntimeError):
    """The run cannot be made here: no result is printed."""


def load_module(path: str):
    name = "bench_" + os.path.basename(path).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with everything found by its names."""

    def __init__(self, name: str, root: str = ROOT):
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            bench = json.load(f)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise Refused(f"no workload {name!r} in BENCHMARK.json")
        self.name = name
        self.spec = cells[name]
        self.chips = int(self.spec["chips"])
        entry = {c["name"]: c for c in bench["configs"]}[self.spec["config"]]
        self.load_config(os.path.join(root, entry["file"]))
        self.mix = traffic.load(os.path.join(
            HERE, "traffic", self.spec["traffic"] + ".json"))

        def mine(m):
            return name in m.get("workloads", [name])
        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"] if mine(m)
                          and m["moves"] in reported]

    def load_config(self, path: str) -> None:
        """The configuration file at ``path``, the number of uint8 images
        a frame carries (``images``, which it must state), and the plain
        reference beside it."""
        with open(path) as f:
            self.cfg = json.load(f)
        self.images = int(self.cfg["images"])
        self.ref = load_module(os.path.join(os.path.dirname(path),
                                            self.cfg["reference"]))


def check_devices(chips: int):
    """The cell's chips, or Refused: a TPU, enough of them, and Pallas
    kernels that compile rather than run interpreted."""
    import jax
    from repro.kernels.stream import interpret_default
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX's default device is {devs[0].platform}")
    if len(devs) < chips:
        raise Refused(f"{chips} chips wanted, {len(devs)} found")
    if interpret_default():
        raise Refused("Pallas kernels would run in interpret mode")
    return devs[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless the environment names one; every program is kept."""
    path = os.environ.get(CACHE_ENV) or CACHE_DIR
    os.makedirs(path, exist_ok=True)
    os.environ[CACHE_ENV] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def nearest_rank(values, q: float) -> float:
    """The q-quantile by nearest rank (inf counts as a value)."""
    v = sorted(values)
    return v[max(0, math.ceil(q * len(v)) - 1)]


class WindowEvents:
    """Counts JAX compile and compile-cache events while ``on``, and the
    interpreter's garbage collections with their longest pause."""

    def __init__(self):
        import gc

        import jax
        self.on = False
        self.n = 0
        self.gc = [0, 0.0]
        started = []

        def collected(phase, info):
            if phase == "start":
                started[:] = [time.perf_counter()]
            elif self.on and started and info.get("generation") == 2:
                self.gc[0] += 1
                self.gc[1] = max(self.gc[1], time.perf_counter() - started[0])
        gc.callbacks.append(collected)

        def seen(event, *args, **kw):
            if self.on and (event.startswith("/jax/core/compile/")
                            or event.startswith("/jax/compilation_cache/")):
                self.n += 1
        jax.monitoring.register_event_listener(seen)
        jax.monitoring.register_event_duration_secs_listener(seen)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float) -> dict:
    """Set up, drive the window, check, and return the result's fields."""
    import jax
    import numpy as np
    from repro.apps import PIPELINES
    from repro.core import CompileOptions, compile_pipeline
    from repro.serve import FrameServer, ServeConfig

    cfg = cell.cfg
    pipe = cfg["pipeline"]
    mix = cell.mix
    chips = len(devices)
    counter = WindowEvents()

    design = compile_pipeline(PIPELINES[cfg["app"]](**pipe),
                              options=CompileOptions(backend="pallas"))
    t_gen = time.perf_counter()
    n_pool = traffic.pool_size(mix, chips)
    pool = traffic.make_pool(mix, pipe["h"], pipe["w"], n_pool, seed,
                             cell.images)
    frames = traffic.server_inputs(cfg["input"], pool)
    t_gen = time.perf_counter() - t_gen

    srv = FrameServer(ServeConfig(devices=list(devices)))
    srv.register(design, backend="pallas", warm_inputs=[frames[0]])
    srv.start()
    setup_s = time.perf_counter() - t_start - t_gen

    # plan the traffic: submissions, pool order, and the window's bounds
    if mix["loop"] == "open":
        offsets = traffic.camera_schedule(mix, seed, PREROLL_S + seconds)
        n_max = len(offsets)
    else:
        n_max = int(MAX_FPS_PER_CHIP * chips * (PREROLL_S + seconds))
    idx = traffic.order(n_pool, n_max, seed)
    rec = traffic.Recorder(n_max, int(cfg["check_frames"]), seed)
    window = {}

    trace_dir = None
    if trace:
        from jax.profiler import ProfileOptions
        trace_dir = TRACE_DIR
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    t_base = time.perf_counter() + 0.05
    t_w0 = t_base + PREROLL_S
    t_w1 = t_w0 + seconds

    def in_window(i, t_done):
        if mix["loop"] == "open":
            return t_w0 <= rec.due[i] < t_w1
        return t_w0 <= t_done <= t_w1
    rec.eligible = in_window

    def sleep_until(t):
        while time.perf_counter() < t:
            time.sleep(min(0.01, max(0.0, t - time.perf_counter())))

    def hold_window():
        # the window's span and counters, on a thread of their own so the
        # generator keeps its schedule; no compile may happen inside
        sleep_until(t_w0)
        window["stats0"] = (srv.stats.batches, srv.stats.batch_frames)
        counter.on = True
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            sleep_until(t_w1)
        counter.on = False
        window["stats1"] = (srv.stats.batches, srv.stats.batch_frames)

    import threading
    holder = threading.Thread(target=hold_window, name="bench-window")
    holder.start()
    if mix["loop"] == "open":
        traffic.run_open(srv.submit, frames, idx, t_base + offsets, rec)
    else:
        traffic.run_closed(srv.submit, frames, idx,
                           traffic.outstanding(mix, chips), t_w1, rec)
    holder.join()
    if trace:
        jax.profiler.stop_trace()
    traffic.wait_all(rec, DRAIN_S)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    warmup_s = srv.stats.warmup_s
    srv.close()

    # ---- what happened in the window ----
    n = rec.n
    due, done = rec.due[:n], rec.done[:n]
    failed_mask = rec.error[:n] | rec.shed[:n] | np.isnan(done)
    if mix["loop"] == "open":
        in_w = (due >= t_w0) & (due < t_w1)
    else:
        in_w = (rec.sent[:n] >= t_w0) & (rec.sent[:n] < t_w1)
    attempted = int(np.sum(in_w))
    failed = int(np.sum(in_w & failed_mask))
    completed = int(np.sum(~failed_mask & (done >= t_w0) & (done <= t_w1)))
    lat_ms = np.where(failed_mask, np.inf, (done - due) * 1e3)[in_w]
    late_ms = ((rec.sent[:n] - due) * 1e3)[in_w & ~np.isnan(rec.sent[:n])]
    unanswered = int(np.sum(in_w & (rec.error[:n] | np.isnan(done))
                            & ~rec.shed[:n]))

    # ---- the comparison, after the window and the server are gone ----
    refs = {}
    pairs_checked = []
    for i, out in sorted(rec.sample, key=lambda s: s[0]):
        p = int(rec.pool_idx[i])
        if p not in refs:
            refs[p] = cell.ref.reference(*pool[p], **pipe)
        pairs_checked.append((out, refs[p]))
    values = compare.readings(pairs_checked, unanswered)
    if not pairs_checked:
        values["unanswered"] = max(values["unanswered"], 1)
    correct, checks = compare.verdict(values, cfg["limits"])

    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed}
    ctx = {
        "seconds": seconds, "frames_completed": completed,
        "latencies_ms": lat_ms,
        "stats": {"batches": window["stats1"][0] - window["stats0"][0],
                  "batch_frames": window["stats1"][1] - window["stats0"][1],
                  "warmup_s": warmup_s},
        "setup_s": setup_s,
    }
    info = {"generator_late_ms": {
        "p50": float(np.percentile(late_ms, 50)) if late_ms.size else 0.0,
        "max": float(late_ms.max()) if late_ms.size else 0.0},
        "compiles_in_window": counter.n, "pool_frames": n_pool,
        "pool_s": t_gen, "frames_in_window": completed,
        "frames_compared": len(pairs_checked),
        "full_gc_in_window": counter.gc[0], "full_gc_max_s": counter.gc[1],
        "answers_per_s": np.histogram(
            done[~failed_mask], bins=int(round(seconds)),
            range=(t_w0, t_w1))[0].tolist()}
    if trace:
        import devtrace
        red = devtrace.reduce_dir(trace_dir, WINDOW_SPAN)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = red
        ctx["work"] = {"ops": work.ops_per_frame(cell.ref.dag(**pipe)),
                       "bytes": work.bytes_per_frame(cell.ref.io_bits(**pipe))}
        ctx["kind"] = devices[0].device_kind
        metrics = {}
        for m in cell.per_layer:
            reader = load_module(os.path.join(HERE, "metrics",
                                              m["name"] + ".py"))
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        out["metrics"] = metrics
    else:
        out["metrics"] = {m["name"]: {"value": float(E2E[m["name"]](ctx)),
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
    out["device"] = {"platform": devices[0].platform,
                     "kind": devices[0].device_kind, "count": chips,
                     "memory_peak_bytes": peak}
    if trace:
        out["device"]["busy_s"] = ctx["trace"]["busy_s"]
        out["device"]["window_s"] = ctx["trace"]["window_s"]
        out["breakdown"] = ctx["trace"]["breakdown"]
    out["checks"] = checks
    out["_info"] = info
    return out


E2E = {
    "setup_s": lambda c: c["setup_s"],
    "frames_per_s": lambda c: c["frames_completed"] / c["seconds"],
    "latency_p50_ms": lambda c: nearest_rank(c["latencies_ms"], 0.50),
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = Cell(args.workload)
        sys.path.insert(0, os.path.join(ROOT, "src"))
        cache = enable_compile_cache()
        devices = check_devices(cell.chips)
    except (Refused, ImportError, OSError, KeyError) as e:
        print(f"run.py: refused: {e}", file=sys.stderr, flush=True)
        return 2
    print(f"compile cache: {cache}", file=sys.stderr, flush=True)
    res = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices,
                   T_START)
    info = res.pop("_info")
    print(json.dumps({"run": info}), flush=True)
    for k, c in res["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    lost = [k for k, m in res["metrics"].items()
            if not math.isfinite(m["value"])]
    if lost:
        # a tail that lands on shed or failed frames has no value
        print(f"run.py: no finite value for {lost}: frames were lost",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
