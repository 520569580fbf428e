"""The per-layer readers of the program's own spans and counters
(``metrics/*.py`` over ``repro.spans``), each on a span store filled by
hand, on an empty store, and on a program that has no spans at all."""
import os
import sys

import pytest

import run
from repro import spans

READERS = ("d2h_ms_p50.saturated", "d2h_bytes_per_frame.saturated",
           "queue_ms_p50.cameras", "h2d_ms_p50.cameras",
           "d2h_ms_p50.cameras", "compile_s")


def reader(name):
    return run.load_module(os.path.join(run.HERE, "metrics", name + ".py"))


@pytest.fixture
def store(monkeypatch):
    """An empty span store that keeps records and counters as if a
    profiler session were running."""
    spans.clear()
    monkeypatch.setattr(spans, "enabled", lambda: True)
    yield spans
    spans.clear()


def batches(name, seconds, n=8):
    for bid, s in enumerate(seconds):
        spans.interval(name, 10.0, 10.0 + s, bid=bid, n=n)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_none_from_an_empty_store(name, store):
    assert reader(name).read({}) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_none_without_the_programs_spans(name, monkeypatch):
    import repro
    monkeypatch.delattr(repro, "spans")
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert reader(name).read({}) is None


@pytest.mark.parametrize("name,span", [
    ("d2h_ms_p50.saturated", "serve.d2h"),
    ("d2h_ms_p50.cameras", "serve.d2h"),
    ("h2d_ms_p50.cameras", "serve.h2d")])
def test_batch_span_median_in_ms(name, span, store):
    batches(span, [0.010, 0.040, 0.020])
    batches("serve.enqueue", [1.0, 1.0, 1.0])    # other stages: not read
    assert reader(name).read({}) == pytest.approx(20.0)


def test_queue_median_over_frames(store):
    for rid, s in enumerate([0.001, 0.002, 0.003, 0.100]):
        spans.interval("serve.queue", 5.0, 5.0 + s, rid=rid, bid=0)
    assert reader("queue_ms_p50.cameras").read({}) == pytest.approx(2.5)


def test_d2h_bytes_per_real_frame(store):
    batches("serve.d2h", [0.01, 0.01], n=8)
    batches("serve.d2h", [0.01], n=5)           # padded to 8 rows
    spans.count("serve.d2h_bytes", 3 * 8 * 1000)
    assert reader("d2h_bytes_per_frame.saturated").read({}) == \
        pytest.approx(24000 / 21)


def test_d2h_bytes_needs_the_counter(store):
    batches("serve.d2h", [0.01], n=8)
    assert reader("d2h_bytes_per_frame.saturated").read({}) is None


def test_compile_s_sums_the_compilers_totals(store):
    with spans.span("compile.pipeline"):
        pass
    with spans.span("compile.lower"):
        pass
    with spans.span("serve.warmup"):             # not the compiler's
        pass
    tot = spans.totals()
    assert reader("compile_s").read({}) == \
        tot["compile.pipeline"][1] + tot["compile.lower"][1]
