"""The load generator: one general generator for every traffic mix.

A mix is a data file under ``traffic/`` (``<name>.json``).  Its ``loop``
key picks the discipline and the other keys are its parameters:

- ``"closed"``: ``outstanding_per_chip`` frames are in flight per chip of
  the cell at all times; a frame is submitted as soon as one completes.
  Frames come from a pool of ``pool_per_outstanding`` times that many
  distinct frames, in a seeded order.
- ``"open"``: ``cameras`` periodic cameras at ``fps`` frames per second,
  each with a seeded phase in ``[0, 1/fps)`` and seeded per-frame jitter,
  uniform in ``+-jitter_ms``.  A frame is due on its schedule whether or
  not earlier frames are done.  Frames come from a pool of ``pool_frames``
  distinct frames, in a seeded order.

Every frame is made from the seed and carries as many uint8 images as
the configuration's ``images`` states: the first image is uniform uint8
noise; a second, where there is one, is the first shifted right by a
seeded whole number of pixels in ``shift_px`` (inclusive).  The same seed
gives the same pool, order and schedule, and a one-image pool holds the
first images of the two-image pool of that seed.
"""
from __future__ import annotations

import json
import math
import threading
import time

import numpy as np

# streams of one seed: each use draws from its own generator
POOL, ORDER, SCHEDULE, SAMPLE = range(4)


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % 2 ** 64, stream])


def load(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be 'closed' or 'open'")
    return mix


def outstanding(mix: dict, chips: int) -> int:
    return int(mix["outstanding_per_chip"]) * chips


def pool_size(mix: dict, chips: int) -> int:
    if mix["loop"] == "closed":
        return int(mix["pool_per_outstanding"]) * outstanding(mix, chips)
    return int(mix["pool_frames"])


def make_pool(mix: dict, h: int, w: int, n: int, seed: int, images: int):
    """``n`` distinct frames of ``h`` x ``w``: tuples of ``images`` uint8
    images, ``(first,)`` or ``(first, second)``."""
    g = rng(seed, POOL)
    first = g.integers(0, 256, (n, h, w), dtype=np.uint8)
    if images == 1:
        return [(first[i],) for i in range(n)]
    if images != 2:
        raise ValueError(f"a frame carries 1 or 2 images, not {images!r}")
    lo, hi = mix["shift_px"]
    shifts = g.integers(lo, hi + 1, n)
    return [(first[i], np.roll(first[i], int(shifts[i]), axis=1))
            for i in range(n)]


def server_inputs(name: str, pool) -> list:
    """The server's input dict of each frame of ``pool``: a one-image
    pipeline takes the bare image, a two-image one the tuple."""
    return [{name: p[0] if len(p) == 1 else p} for p in pool]


def order(n_pool: int, n: int, seed: int) -> np.ndarray:
    """Pool index of each of ``n`` submissions: seeded permutations of
    the pool, one after another."""
    g = rng(seed, ORDER)
    reps = -(-n // n_pool)
    return np.concatenate([g.permutation(n_pool) for _ in range(reps)])[:n]


def camera_schedule(mix: dict, seed: int, duration_s: float) -> np.ndarray:
    """Due offsets in seconds from the schedule's start, sorted, of every
    frame of every camera due in ``[0, duration_s)``."""
    g = rng(seed, SCHEDULE)
    k, fps = int(mix["cameras"]), float(mix["fps"])
    period = 1.0 / fps
    n = int(math.ceil(duration_s * fps)) + 1
    phase = g.uniform(0.0, period, k)
    jitter = g.uniform(-1e-3, 1e-3, (k, n)) * float(mix["jitter_ms"])
    due = phase[:, None] + np.arange(n)[None, :] * period + jitter
    due = np.sort(due.ravel())
    return due[(due >= 0.0) & (due < duration_s)]


class Recorder:
    """Per-frame records of one run, and a seeded reservoir sample of the
    answers that fall in the window, kept for the comparison.

    ``eligible(i, t_done)`` says whether frame ``i`` counts in the window;
    the reservoir keeps ``k`` of the eligible answers, each equally
    likely, whatever order they complete in."""

    def __init__(self, n_max: int, k: int, seed: int):
        self.due = np.full(n_max, np.nan)
        self.sent = np.full(n_max, np.nan)
        self.done = np.full(n_max, np.nan)
        self.error = np.zeros(n_max, dtype=bool)
        self.shed = np.zeros(n_max, dtype=bool)
        self.pool_idx = np.zeros(n_max, dtype=np.int64)
        self.n = 0
        self.k = k
        self.sample = []           # [(submission index, answer)]
        self._seen = 0
        self._rng = rng(seed, SAMPLE)
        self._lock = threading.Lock()
        self.eligible = lambda i, t: True

    def callback(self, i: int, release=None):
        def done(fut):
            t = time.perf_counter()
            try:
                out = fut.result()
            except Exception:            # a failed frame: never answered
                self.error[i] = True
                out = None
            self.done[i] = t
            if out is not None and self.eligible(i, t):
                with self._lock:
                    self._seen += 1
                    if len(self.sample) < self.k:
                        self.sample.append((i, out))
                    else:
                        j = int(self._rng.integers(0, self._seen))
                        if j < self.k:
                            self.sample[j] = (i, out)
            if release is not None:
                release()
        return done


def _overloaded():
    from repro.serve import Overloaded
    return Overloaded


def _send(submit, frame, rec: Recorder, i: int, release=None,
          Overloaded=Exception) -> bool:
    rec.sent[i] = time.perf_counter()
    try:
        fut = submit(frame)
    except Overloaded:
        rec.shed[i] = True
        return False
    fut.add_done_callback(rec.callback(i, release))
    return True


def run_closed(submit, frames, idx, n_out: int, t_end: float,
               rec: Recorder) -> None:
    """Keep ``n_out`` frames in flight until ``t_end``; frame ``i`` is
    ``frames[idx[i]]``."""
    slots = threading.Semaphore(n_out)
    shed = _overloaded()
    i = 0
    while i < len(idx):
        if not slots.acquire(timeout=60.0):
            break                        # nothing completes: stop sending
        if time.perf_counter() >= t_end:
            slots.release()
            break
        rec.pool_idx[i] = idx[i]
        rec.due[i] = time.perf_counter()
        if not _send(submit, frames[idx[i]], rec, i, slots.release, shed):
            slots.release()
        i += 1
        rec.n = i
    if i == len(idx):
        raise RuntimeError("closed loop ran out of planned submissions")


def run_open(submit, frames, idx, due_abs: np.ndarray,
             rec: Recorder) -> None:
    """Submit frame ``i`` (``frames[idx[i]]``) at ``due_abs[i]`` on the
    host clock, late if the generator falls behind."""
    shed = _overloaded()
    for i, due in enumerate(due_abs):
        now = time.perf_counter()
        if due > now:
            time.sleep(due - now)
        rec.pool_idx[i] = idx[i]
        rec.due[i] = due
        _send(submit, frames[idx[i]], rec, i, None, shed)
        rec.n = i + 1


def wait_all(rec: Recorder, limit_s: float) -> None:
    """Wait until every sent frame is answered or failed, at most
    ``limit_s`` seconds."""
    t_stop = time.perf_counter() + limit_s
    while time.perf_counter() < t_stop:
        sent = ~np.isnan(rec.sent[:rec.n]) & ~rec.shed[:rec.n]
        if not np.any(sent & np.isnan(rec.done[:rec.n])):
            return
        time.sleep(0.01)
