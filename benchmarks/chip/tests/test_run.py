"""A whole run of the harness on the CPU at a tiny frame size, with the
look for a chip skipped: clean, it is correct; with the served path
broken underneath, ``correct`` comes out false.  And the command itself
refuses to run without a TPU."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import run

ROOT = run.ROOT
TINY = {
    "flow_1080p": {"w": 64, "h": 32},
    "stereo_720x400": {"w": 128, "h": 16, "nd": 64},
}


def tiny_cell(name, config=None, **mix):
    """A cell of BENCHMARK.json at a tiny frame size; ``config`` puts
    another configuration under the cell's traffic mix."""
    cell = run.Cell(name)
    config = config or cell.spec["config"]
    if config != cell.spec["config"]:
        path = os.path.join(run.HERE, "configs", config)
        with open(path + ".json") as f:
            cell.cfg = json.load(f)
        cell.ref = run.load_module(path + ".py")
    cell.cfg = dict(cell.cfg, pipeline=TINY[config], check_frames=4)
    cell.mix = dict(cell.mix, **mix)
    return cell


def cpu_run(cell, seconds=1.0, trace=False):
    import jax
    run.enable_compile_cache()
    return run.run_cell(cell, seed=2 ** 31 + 7, seconds=seconds, trace=trace,
                        devices=jax.devices("cpu")[:1],
                        t_start=time.perf_counter())


SATURATED = dict(outstanding_per_chip=8, pool_per_outstanding=2)


@pytest.mark.parametrize("config", sorted(TINY))
def test_clean_run_is_correct(config):
    res = cpu_run(tiny_cell("stereo_720x400.saturated", config, **SATURATED))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"frames_per_s", "setup_s"}
    assert res["metrics"]["frames_per_s"]["value"] > 0
    assert list(res)[-2:] == ["checks", "_info"]
    assert res["_info"]["frames_compared"] == 4


def test_clean_cameras_run_is_correct():
    res = cpu_run(tiny_cell("flow_1080p.cameras", cameras=2, pool_frames=8),
                  seconds=1.0)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 50
    m = res["metrics"]
    assert set(m) == {"latency_p50_ms", "setup_s"}
    assert m["latency_p50_ms"]["value"] > 0


def altered(outs):
    """One element of every answer changed where it is produced."""
    def bump(a):
        a = np.array(a, copy=True)
        a.flat[0] = a.flat[0] + (1.0 if a.dtype.kind == "f" else 1)
        return a
    return [tuple(bump(x) for x in o) if isinstance(o, tuple) else bump(o)
            for o in outs]


def half_left_out(outs):
    """The second half of a batch gets the first half's answers."""
    h = len(outs) // 2
    return outs[:len(outs) - h] + outs[:h]


class Stale:
    """Every batch answers with the batch before it: the state of the
    served path never moves on."""

    def __init__(self):
        self.last = None

    def __call__(self, outs):
        prev, self.last = self.last, outs
        if prev is None or len(prev) < len(outs):
            return outs
        return prev[:len(outs)]


@pytest.mark.parametrize("fault", ["altered", "half_left_out", "stale"])
@pytest.mark.parametrize("config", sorted(TINY))
def test_broken_serve_path_is_not_correct(config, fault, monkeypatch):
    from repro.serve import dispatch
    orig = dispatch.split_frames
    f = {"altered": altered, "half_left_out": half_left_out,
         "stale": Stale()}[fault]
    monkeypatch.setattr(dispatch, "split_frames",
                        lambda out, n: f(orig(out, n)))
    res = cpu_run(tiny_cell("stereo_720x400.saturated", config, **SATURATED))
    assert not res["correct"], res["checks"]


def test_lost_answers_are_not_correct(monkeypatch):
    from repro.serve import dispatch
    orig = dispatch.split_frames
    calls = []

    def lost(out, n):
        # the warmup's four batch sizes pass; served batches are lost
        calls.append(n)
        if len(calls) > 4:
            raise RuntimeError("readback lost")
        return orig(out, n)
    monkeypatch.setattr(dispatch, "split_frames", lost)
    monkeypatch.setattr(run, "DRAIN_S", 2.0)
    res = cpu_run(tiny_cell("stereo_720x400.saturated", **SATURATED))
    assert not res["correct"]
    assert res["failed"] > 0
    assert res["checks"]["unanswered"]["value"] > 0


@pytest.mark.parametrize("config", sorted(TINY))
def test_control_is_not_correct(config):
    """The reference in a lower precision, put in the program's place,
    fails the configuration's limits; the reference itself passes."""
    import compare
    import traffic
    cell = tiny_cell("stereo_720x400.saturated", config)
    pipe = cell.cfg["pipeline"]
    pairs = traffic.make_pool(cell.mix, pipe["h"], pipe["w"], 4, 3)
    refs = [cell.ref.reference(a, b, **pipe) for a, b in pairs]
    ctl = [cell.ref.control(a, b, **pipe) for a, b in pairs]
    ok, _ = compare.verdict(compare.readings(zip(refs, refs), 0),
                            cell.cfg["limits"])
    assert ok
    ok, checks = compare.verdict(compare.readings(zip(ctl, refs), 0),
                                 cell.cfg["limits"])
    assert not ok, checks


def test_command_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "chip", "run.py"),
         "--workload", "stereo_720x400.saturated", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
    assert "metrics" not in p.stdout


def test_command_refuses_without_the_program(tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    files the command exits non-zero and prints no result."""
    import shutil
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks", "chip"),
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "chip", "run.py"),
         "--workload", "flow_1080p.cameras", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("name", ["stereo_720x400.saturated",
                                  "flow_1080p.cameras"])
def test_traced_run_reports_the_cells_per_layer_metrics(name, monkeypatch):
    """The traced path end to end, with the chip's trace taken from the
    recorded one (a CPU trace has no TPU plane)."""
    import devtrace
    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "flow_256x32.xplane.pb")
    monkeypatch.setattr(devtrace, "reduce_dir",
                        lambda d, span: devtrace.reduce_file(data, span))
    mix = SATURATED if name.endswith("saturated") else dict(cameras=2,
                                                           pool_frames=8)
    cell = tiny_cell(name, "flow_1080p", **mix)
    import work
    monkeypatch.setattr(work, "peaks", lambda kind: {"ops_per_s": 197e12,
                                                     "hbm_bytes_per_s": 819e9})
    res = cpu_run(cell, trace=True)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {m["name"] for m in cell.per_layer}
    want = {"stereo_720x400.saturated": {
        "device_idle_pct.saturated", "pipeline_roofline_pct.saturated",
        "xla_ms_per_frame.saturated", "warmup_s"},
        "flow_1080p.cameras": {"device_idle_pct.cameras",
                               "batch_frames_mean.cameras", "warmup_s"}}
    assert set(res["metrics"]) == want[name]
    for m in res["metrics"].values():
        assert m["value"] >= 0
    assert 0 < res["device"]["busy_s"] < res["device"]["window_s"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


FOUR_DEVICES = r'''
import json, os, sys, time
sys.path[:0] = [{bench!r}, {src!r}]
import jax, run
from repro.serve import dispatch
cell = run.Cell("stereo_720x400.saturated")
cell.chips = 4
cell.cfg = dict(cell.cfg, pipeline={{"w": 128, "h": 16, "nd": 64}},
                check_frames=8)
cell.mix = dict(cell.mix, outstanding_per_chip=4)
if {fault!r}:
    orig = dispatch.split_frames
    def first_chip_only(out, n):
        # the frames of chips 1..3 are answered with chip 0's shard
        outs = orig(out, n)
        per = max(1, -(-n // 4))
        return [outs[i % per] for i in range(n)]
    dispatch.split_frames = first_chip_only
run.enable_compile_cache()
res = run.run_cell(cell, 2 ** 31 + 3, 1.0, False, jax.devices()[:4],
                   time.perf_counter())
print(json.dumps({{"correct": res["correct"], "count": res["device"]["count"],
                  "attempted": res["attempted"]}}))
'''


@pytest.mark.parametrize("fault", [False, True])
def test_four_devices_and_the_exchange_left_out(fault):
    """The frame axis sharded over four (virtual CPU) devices: correct as
    served, not correct when the other chips' answers are left out."""
    code = FOUR_DEVICES.format(bench=os.path.dirname(run.__file__),
                               src=os.path.join(ROOT, "src"), fault=fault)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["count"] == 4 and res["attempted"] > 0
    assert res["correct"] is (not fault)
