"""Spans and counters (repro/spans.py) and the frame server's and the
compiler's use of them: always-on totals, records and counters only while
a profiler session runs, one record per stage per batch, and bare names
with ids as stats in the profiler's trace."""
import collections
import glob
import os
import sys
import threading

import jax
import numpy as np
import pytest

from repro import spans
from repro.apps.convolution import bench_case
from repro.core import compile_pipeline
from repro.serve import FrameServer, ServeConfig

BATCH_SPANS = ("serve.h2d", "serve.enqueue", "serve.d2h", "serve.resolve")


@pytest.fixture(autouse=True)
def _fresh_spans():
    spans.clear()
    yield
    spans.clear()


def _serve(n_frames, trace_dir=None, max_batch=4):
    """Compile a small CONVOLUTION, serve ``n_frames`` through a warmed
    ``FrameServer`` (profiling the traffic into ``trace_dir`` if given);
    returns the server (closed) and the outputs."""
    uf, inputs_fn = bench_case(w=24, h=12)
    design = compile_pipeline(uf)
    frames = [inputs_fn(np.random.RandomState(i)) for i in range(n_frames)]
    srv = FrameServer(ServeConfig(max_batch=max_batch, max_delay_ms=5.0))
    srv.register(design, backend="jax", warm_inputs=[frames[0]])
    with srv:
        if trace_dir is not None:
            jax.profiler.start_trace(str(trace_dir))
        try:
            outs = [f.result(timeout=300) for f in srv.submit_many(frames)]
        finally:
            if trace_dir is not None:
                jax.profiler.stop_trace()
    return srv, outs


def test_span_totals_always_records_only_while_profiling(tmp_path):
    with spans.span("t.phase") as sp:
        pass
    with spans.span("t.work", bid=1) as item:
        pass
    spans.interval("t.wait", 0.0, 1.0, rid=0)
    spans.count("t.bytes", 5)
    assert sp.seconds >= 0.0 and item.seconds >= 0.0
    # a phase is totalled always; an item (a span with ids) is not
    assert spans.totals() == {"t.phase": (1, sp.seconds)}
    assert spans.records() == [] and spans.counters() == {}
    assert not spans.enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert spans.enabled()
        with spans.span("t.phase"):
            pass
        with spans.span("t.work", bid=2):
            pass
        spans.interval("t.wait", 0.0, 1.0, rid=0)
        spans.count("t.bytes", 5)
        spans.count("t.bytes", 7)
    finally:
        jax.profiler.stop_trace()
    assert not spans.enabled()
    (work,) = spans.records("t.work")
    assert work.ids == {"bid": 2} and work.t1 >= work.t0
    (phase,) = spans.records("t.phase")
    assert phase.ids == {}
    (wait,) = spans.records("t.wait")
    assert wait.seconds == 1.0 and wait.ids == {"rid": 0}
    assert spans.counters() == {"t.bytes": 12}
    assert set(spans.totals()) == {"t.phase"}  # a wait is not host work
    assert spans.totals()["t.phase"][0] == 2
    spans.clear()
    assert spans.records() == [] and spans.totals() == {}


def test_totals_and_counters_lose_no_update_across_threads(monkeypatch):
    """The loop thread and the readback threads time spans at once: more
    threads than cores, switching often, must lose no call or byte."""
    monkeypatch.setattr(spans, "enabled", lambda: True)
    workers, rounds = (os.cpu_count() or 4) + 4, 500
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(rounds):
                with spans.span("t.work"):
                    spans.count("t.bytes", 3)
        threads = [threading.Thread(target=work) for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(switch)
    assert spans.totals()["t.work"][0] == workers * rounds
    assert spans.counters() == {"t.bytes": 3 * workers * rounds}


def test_untraced_serve_keeps_totals_and_no_records():
    srv, outs = _serve(5)
    assert len(outs) == 5
    assert spans.records() == [] and spans.counters() == {}
    tot = spans.totals()
    for name in ("compile.pipeline", "compile.lower", "serve.warmup"):
        assert tot[name][0] >= 1 and tot[name][1] > 0.0, name
    # per-batch spans keep nothing with no profiler running
    assert not set(BATCH_SPANS) & set(tot)
    assert not any(k.startswith("bench.") for k in tot)


def test_warmup_s_is_the_warmup_span():
    srv, _ = _serve(1)
    calls, seconds = spans.totals()["serve.warmup"]
    assert calls == 1
    assert srv.stats.warmup_s == seconds


def test_traced_serve_records_every_frame_and_batch(tmp_path):
    n_frames = 7
    srv, outs = _serve(n_frames, trace_dir=tmp_path)
    queue = spans.records("serve.queue")
    assert len(queue) == n_frames
    assert len({r.ids["rid"] for r in queue}) == n_frames
    assert all(r.t1 >= r.t0 for r in queue)
    bids = {r.ids["bid"] for r in queue}
    for name in BATCH_SPANS:
        per_bid = collections.Counter(r.ids["bid"] for r in
                                      spans.records(name))
        assert per_bid == {b: 1 for b in bids}, name
    d2h = spans.records("serve.d2h")
    assert sum(r.ids["n"] for r in d2h) == n_frames
    assert len(d2h) == srv.stats.batches
    # padding rows are read back too: every row the batches carried, on
    # the root's 32-bit device carrier (Uint(8): four bytes a pixel), while
    # the answers are back on the int64 carrier
    rows = srv.stats.batch_frames + srv.stats.padded_frames
    assert outs[0].dtype == np.int64
    assert spans.counters()["serve.d2h_bytes"] == rows * outs[0].size * 4
    assert not any(r.name.startswith("bench.") for r in spans.records())


def test_trace_holds_bare_names_with_ids_as_stats(tmp_path):
    from jax.profiler import ProfileData
    _serve(4, trace_dir=tmp_path)
    (path,) = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                        recursive=True)
    seen = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve."):
                    seen[ev.name].append(dict(ev.stats))
    for name in ("serve.h2d", "serve.d2h"):
        assert seen[name], f"no {name} event in the trace"
        assert all("bid" in stats and "n" in stats for stats in seen[name])
    assert "serve.queue" not in seen             # waits stay off the trace
