"""The traffic mixes: pure functions of the seed, and a closed loop that
keeps its outstanding count."""
import concurrent.futures
import os
import threading
import time

import numpy as np
import pytest

import traffic
import repro.serve  # noqa: F401  (the generator catches its Overloaded)

MIXES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")
BIG_SEED = 2 ** 31 + 12345


def mix(name):
    return traffic.load(os.path.join(MIXES, name + ".json"))


def test_pool_is_a_function_of_the_seed():
    m = mix("saturated")
    a = traffic.make_pool(m, 6, 10, 5, BIG_SEED, 2)
    b = traffic.make_pool(m, 6, 10, 5, BIG_SEED, 2)
    c = traffic.make_pool(m, 6, 10, 5, BIG_SEED + 1, 2)
    for (x1, y1), (x2, y2) in zip(a, b):
        assert x1.dtype == np.uint8 and y1.dtype == np.uint8
        assert np.array_equal(x1, x2) and np.array_equal(y1, y2)
    assert any(not np.array_equal(x1, x3) for (x1, _), (x3, _) in zip(a, c))
    # the second image is the first shifted within shift_px
    lo, hi = m["shift_px"]
    for x, y in a:
        assert any(np.array_equal(np.roll(x, s, axis=1), y)
                   for s in range(lo, hi + 1))


def test_pool_frames_are_distinct():
    m = mix("saturated")
    n = traffic.pool_size(m, 1)
    assert n >= 2 * traffic.outstanding(m, 1)
    pool = traffic.make_pool(m, 8, 16, n, 3, 2)
    assert len({x.tobytes() for x, _ in pool}) == n


def pinned_pairs(mix, h, w, n, seed):
    """The two-image pool as the benchmark first drew it: the (n, h, w)
    block of first images, then the shifts, from the pool's stream."""
    g = np.random.default_rng([int(seed) % 2 ** 64, 0])
    lo, hi = mix["shift_px"]
    first = g.integers(0, 256, (n, h, w), dtype=np.uint8)
    shifts = g.integers(lo, hi + 1, n)
    return [(first[i], np.roll(first[i], int(shifts[i]), axis=1))
            for i in range(n)]


@pytest.mark.parametrize("seed", [3, 2 ** 31 - 1, BIG_SEED])
@pytest.mark.parametrize("name", ["saturated", "cameras"])
def test_two_image_pool_is_unchanged(name, seed):
    """Two-image cells keep the pool they were measured on, byte for byte,
    and ``server_inputs`` hands the server the same pairs."""
    m = mix(name)
    got = traffic.make_pool(m, 8, 12, 9, seed, 2)
    want = pinned_pairs(m, 8, 12, 9, seed)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert len(g) == 2
        for a, b in zip(g, w):
            assert a.dtype == np.uint8 and a.tobytes() == b.tobytes()
    inputs = traffic.server_inputs("app.in", got)
    assert all(d["app.in"] is p for d, p in zip(inputs, got))


@pytest.mark.parametrize("seed", [5, BIG_SEED])
def test_one_image_pool_is_the_first_images(seed):
    m = mix("saturated")
    one = traffic.make_pool(m, 8, 12, 9, seed, 1)
    two = traffic.make_pool(m, 8, 12, 9, seed, 2)
    assert [len(p) for p in one] == [1] * 9
    assert all(np.array_equal(a[0], b[0]) for a, b in zip(one, two))
    inputs = traffic.server_inputs("app.in", one)
    assert all(d["app.in"] is p[0] for d, p in zip(inputs, one))
    with pytest.raises(ValueError):
        traffic.make_pool(m, 8, 12, 9, seed, 3)


def test_order_cycles_through_the_pool():
    idx = traffic.order(7, 30, BIG_SEED)
    assert np.array_equal(idx, traffic.order(7, 30, BIG_SEED))
    for k in range(4):
        assert sorted(idx[7 * k:7 * k + 7]) == list(range(7))


def test_camera_schedule_is_a_function_of_the_seed():
    m = mix("cameras")
    a = traffic.camera_schedule(m, BIG_SEED, 5.0)
    assert np.array_equal(a, traffic.camera_schedule(m, BIG_SEED, 5.0))
    assert not np.array_equal(a, traffic.camera_schedule(m, 1, 5.0))
    k, fps = m["cameras"], m["fps"]
    assert abs(len(a) - k * 5.0 * fps) <= k
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 5.0


def test_camera_schedule_keeps_period_and_jitter():
    m = dict(mix("cameras"), cameras=1)
    due = traffic.camera_schedule(m, 11, 10.0)
    period = 1.0 / m["fps"]
    gaps = np.diff(due)
    jitter = m["jitter_ms"] * 1e-3
    assert np.all(np.abs(gaps - period) <= 2 * jitter + 1e-12)
    phase = due[0] if due[0] >= jitter else due[1] - period
    assert 0 <= phase + jitter and phase < period + jitter


class FakeServer:
    """Answers each frame after a short delay from a worker thread and
    records how many were in flight."""

    def __init__(self):
        self.inflight = 0
        self.peak = 0
        self.lock = threading.Lock()
        self.pool = concurrent.futures.ThreadPoolExecutor(8)

    def submit(self, frame):
        with self.lock:
            self.inflight += 1
            self.peak = max(self.peak, self.inflight)

        def work():
            time.sleep(0.002)
            with self.lock:
                self.inflight -= 1
            return frame
        return self.pool.submit(work)


def test_closed_loop_keeps_its_outstanding_count():
    srv = FakeServer()
    n_out = 6
    frames = list(range(12))
    idx = traffic.order(12, 100000, 5)
    rec = traffic.Recorder(len(idx), 4, 5)
    counts = []
    stop = threading.Event()

    def watch():
        while not stop.is_set():
            counts.append(srv.inflight)
            time.sleep(0.0005)
    w = threading.Thread(target=watch)
    w.start()
    traffic.run_closed(srv.submit, frames, idx, n_out,
                       time.perf_counter() + 0.3, rec)
    stop.set()
    w.join(timeout=5)
    assert not w.is_alive()
    traffic.wait_all(rec, 5)
    srv.pool.shutdown()
    assert srv.peak == n_out
    assert rec.n > 10 * n_out
    # in flight is at the count nearly always: a slot is refilled as soon
    # as it frees
    assert np.mean(np.array(counts) >= n_out - 1) > 0.9
    assert len(rec.sample) == 4
    assert not np.any(np.isnan(rec.done[:rec.n]))


def test_reservoir_is_a_function_of_the_seed_and_order():
    def fill(seed):
        rec = traffic.Recorder(100, 5, seed)
        for i in range(100):
            f = concurrent.futures.Future()
            f.set_result(i)
            rec.callback(i)(f)
        return sorted(i for i, _ in rec.sample)
    assert fill(9) == fill(9)
    assert fill(9) != fill(10) or fill(9) != fill(11)
