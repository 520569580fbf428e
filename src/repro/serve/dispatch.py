"""Double-buffered batch dispatch through the lowering engine.

jax dispatches device computations asynchronously: ``submit()`` therefore
returns immediately after (a) starting the host→device transfer of the
batch (``device_put_batch``) and (b) enqueueing the compiled pipeline
programs on it (``run_batch_device``).  While batch N's programs run, the
server submits batch N+1 — its transfer and tracing overlap N's compute —
and only ``InflightBatch.wait()`` (the device→host readback) blocks.  The
server bounds the inflight FIFO at ``depth`` (2 = classic double
buffering), which is the backpressure point between batching and compute.

Donation (``donate=True``) routes through the engine's donate-able batched
call path: each program segment's dead input buffers are handed back to
XLA for output reuse.  Where the platform lacks donation support (CPU)
jax warns and ignores it; the warning is suppressed around the donating
call only (the fallback is exactly the non-donating behavior) rather than
process-globally.
"""
from __future__ import annotations

import contextlib
import warnings
from typing import Any, List, Optional

import jax

from .. import spans
from .batcher import FrameRequest, split_frames, stack_frames
from .sharding import device_put_batch


@contextlib.contextmanager
def _quiet_donation(donate: bool):
    if not donate:
        yield
        return
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


class InflightBatch:
    """A dispatched batch: device-side results plus the requests awaiting
    them.  ``wait()`` performs the blocking device→host readback and
    returns per-frame numpy outputs (padding rows dropped); its span
    carries the batch id ``bid`` and ``n`` real frames."""

    def __init__(self, reqs: List[FrameRequest], device_out: Any, bid: int):
        self.reqs = reqs
        self._out = device_out
        self.bid = bid

    def wait(self) -> List[Any]:
        with spans.span("serve.d2h", bid=self.bid, n=len(self.reqs)):
            if spans.enabled():
                spans.count("serve.d2h_bytes", sum(
                    x.nbytes for x in jax.tree_util.tree_leaves(self._out)))
            return split_frames(self._out, len(self.reqs))


class BatchDispatcher:
    """Dispatch stacked batches for one compiled pipeline."""

    def __init__(self, compiled, sharding=None, donate: bool = False):
        self.compiled = compiled        # CompiledPipeline (engine.py)
        self.sharding = sharding
        self.donate = donate

    def submit(self, reqs: List[FrameRequest], pad_to: Optional[int] = None,
               bid: int = -1) -> InflightBatch:
        """Transfer and enqueue one batch; ``bid`` tags its spans."""
        ids = {"bid": bid, "n": len(reqs)}
        with spans.span("serve.h2d", **ids):
            batch, _ = stack_frames(reqs, pad_to=pad_to)
            dev_batch, _n = device_put_batch(batch, self.sharding)
        mesh = self.sharding.mesh if self.sharding is not None else None
        with spans.span("serve.enqueue", **ids), \
                _quiet_donation(self.donate):
            out = self.compiled.run_batch_device(dev_batch,
                                                 donate=self.donate,
                                                 mesh=mesh)
        return InflightBatch(reqs, out, bid)
