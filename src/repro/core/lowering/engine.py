"""Pass 3 of the lowering compiler: the jit-compiled execution engine.

The scheduled, rewritten IR is compiled into a small number of programs
instead of eager per-node dispatch.  The schedule is partitioned into
segments; each segment becomes either

* a **megakernel** (pallas backend): one fused Pallas kernel that streams
  the frame row-block by row-block through VMEM-resident line buffers
  (megakernel.py), materializing no intermediate image at all — the
  software mirror of the paper's hardware dataflow; or
* a **generic XLA segment**: the segment's nodes traced into one XLA
  computation via the LOWERERS table (every backend; the only path on
  ``backend="jax"``).

Why segments split at all — the FMA story, now a *per-segment* decision:
XLA:CPU unconditionally allows FMA contraction (``AllowFPOpFusion::Fast``)
when an f32 multiply and a dependent add/subtract land in the same fused
loop, and neither XLA flags nor optimization barriers survive to codegen.
A contracted ``a*b - c*c`` diverges from the IEEE-exact numpy executor
(FLOW's 2x2 solve turns a det==0 into a tiny nonzero residual).  Each
segment resolves this its own way:

* Generic XLA segments close exactly where an f32 add/sub would consume a
  value that an f32 multiply earlier in the same segment produced
  (tracking taint through data-movement ops, which loop fusion makes
  transparent): the program boundary materializes the product, restoring
  op-at-a-time IEEE semantics.  Whether the active backend contracts at
  all is probed at runtime (``backend_contracts_fma``), not assumed.
* Megakernel segments never split: inside one Pallas kernel we control
  the FLOP order, and the emitter computes f32 multiplies exactly in a
  way contraction can't rewrite (megakernel._exact_f32_mul) — so fused
  f32 pipelines compile to a single program again.

This yields the two-tier verification contract: integer pipelines are
bit-exact on every backend under any fusion; float pipelines are bit-exact
on generic segments and within ``megakernel.FLOAT_ULP_BOUND`` ULPs of the
executor on megakernel segments (bit-exact on CPU today; the bound is the
documented promise for backends whose FMA behavior we don't control).

Compiled programs are cached per input-shape/dtype signature (jax's jit
cache; the engine keeps per-signature call stats for the lowering report)
and shared by ``run``/``run_batch``/``run_batch_device`` (batch mode jits
the vmapped trace — megakernel programs vmap like any other jit program,
so the serving path takes them unchanged).

``debug=True`` keeps the fully eager per-node path (``node_values``
exposes the whole environment) for node-level diffing against executor.py.
``per_node=True`` compiles every node as its own program — the per-op
dispatch baseline the bench's ``megakernel.speedup_vs_per_op`` row is
measured against.
"""
from __future__ import annotations

from collections import deque
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..dtypes import (ArrayT, Float, SparseT, TupleT, device_carrier,
                      host_carrier)
from ..hwimg import Val
from .ir import IRNode, LoweringIR
from .lowerers import LOWERERS, jnp_mask
from .megakernel import (Megakernel, MKUnsupported, emit_megakernel,
                         streamable, worth_emitting)
from .patterns import MK_SUBSUMED_RULES, RULES
from .rewrite import apply_rules


def _to_numpy(r):
    if isinstance(r, tuple):
        return tuple(_to_numpy(x) for x in r)
    return np.asarray(r)


def _to_host(r):
    """A pipeline result on the host, integer leaves back on the int64
    carrier (``host_carrier``)."""
    if isinstance(r, tuple):
        return tuple(_to_host(x) for x in r)
    return host_carrier(r, copy=False)


def _out_carriers(ty):
    """How a value of type ``ty`` leaves the device, leaf by leaf (the
    recursion mirrors ``jnp_mask``): a tuple per tuple level, and at each
    leaf its declared scalar type where ``device_carrier`` narrows it,
    else None."""
    if isinstance(ty, TupleT):
        return tuple(_out_carriers(t) for t in ty.elems)
    if isinstance(ty, ArrayT) and isinstance(ty.elem, TupleT):
        return tuple(_out_carriers(t) for t in ty.elem.elems)
    while isinstance(ty, ArrayT):
        ty = ty.elem
    return ty if device_carrier(ty) is not None else None


def _narrowed(carriers):
    """The narrowed leaves' declared types, in leaf order."""
    if isinstance(carriers, tuple):
        return [s for c in carriers for s in _narrowed(c)]
    return [] if carriers is None else [carriers]


def _to_device_carrier(r, carriers):
    """Cast each narrowed leaf of ``r`` to its ``device_carrier``.
    Lossless: the value is already wrapped to its declared width
    (``jnp_mask``), which that dtype holds."""
    if isinstance(carriers, tuple):
        return tuple(_to_device_carrier(x, c) for x, c in zip(r, carriers))
    return r if carriers is None else r.astype(device_carrier(carriers))


def _spec(v) -> Any:
    if isinstance(v, tuple):
        return tuple(_spec(e) for e in v)
    if hasattr(v, "shape") and hasattr(v, "dtype"):
        return (tuple(v.shape), str(v.dtype))   # metadata only: no host sync
    a = np.asarray(v)
    return (a.shape, str(a.dtype))


def _as_input(raw):
    """Input coercion for the jit call path.  ndarrays pass through
    untouched — ``jax.jit``'s C++ fastpath takes numpy arrays directly,
    and an eager ``jnp.asarray`` here costs more than the whole compute
    of a small pipeline (the PYRAMID dispatch-overhead lesson)."""
    if isinstance(raw, (np.ndarray, jax.Array)):
        return raw
    return np.asarray(raw)


# the mesh axis a sharded serving batch splits its frames over
FRAME_AXIS = "frames"

_FMA_PROBE: Dict[str, bool] = {}


def backend_contracts_fma() -> bool:
    """Runtime probe: does the active XLA backend contract an f32 multiply
    feeding an add/subtract into a fused FMA inside one compiled program?

    The partitioner used to hardcode the XLA:CPU answer (yes —
    ``AllowFPOpFusion::Fast`` survives every flag we tried); this probe
    measures it instead, so the partition rule tracks the actual backend
    (ROADMAP "known gaps": TPU rounds differently and needs revalidation).
    The test is the classic residual: with x = 1 + 2^-12 in f32 and p the
    f32-rounded x*x, the IEEE two-step x*x - p is exactly 0, while a fused
    fma(x, x, -p) returns the true rounding residual 2^-24."""
    key = jax.default_backend()
    if key not in _FMA_PROBE:
        x = np.float32(1.0 + 2.0 ** -12)
        p = np.float32(x * x)
        with jax.enable_x64(True):
            r = jax.jit(lambda a, b: a * a - b)(jnp.float32(x),
                                                jnp.float32(p))
        _FMA_PROBE[key] = bool(np.asarray(r) != np.float32(0.0))
    return _FMA_PROBE[key]


def _has_float(ty) -> bool:
    if isinstance(ty, TupleT):
        return any(_has_float(t) for t in ty.elems)
    if isinstance(ty, (ArrayT, SparseT)):
        return _has_float(ty.elem)
    return isinstance(ty, Float)


def _touches_float(n: IRNode) -> bool:
    return _has_float(n.ty) or any(_has_float(t) for t in n.input_tys)


# Contraction-safety classification (see module docstring).  Within one XLA
# program, an f32 multiply whose result reaches a dependent add/subtract —
# possibly through pure data movement, which loop fusion makes transparent
# at scalar level — gets contracted to FMA.  Ops that *compute* something
# else (div, sqrt, compare, convert) break the pattern.
_MUL_FNS = frozenset({"Mul", "FloatMul"})
_ADDSUB_FNS = frozenset({"Add", "AddAsync", "Sub", "FloatAdd", "FloatSub",
                         "AbsDiff"})
_SAFE_FNS = frozenset({"FloatDiv", "FloatSqrt", "ToFloat", "Max", "Min",
                       "Gt", "And", "Abs", "Rshift", "AddMSBs",
                       "RemoveMSBs"})
_MOVE_OPS = frozenset({"Stencil", "Pad", "Crop", "Downsample", "Upsample",
                       "Replicate", "Stack", "Concat", "TupleIndex",
                       "FanOut", "FanIn", "Filter", "SparseTake"})


def _float_kind(n: IRNode) -> str:
    """'mul' (taints its value), 'addsub' (must not consume a tainted
    value in the same program), 'move' (propagates taint), 'safe', or
    'unknown' (treated as both mul and addsub)."""
    if not _touches_float(n):
        return "safe"
    if n.op in _MOVE_OPS:
        return "move"
    if n.op in ("Map", "Reduce", "ReducePatch"):
        name = n.params["fn"].name
        if name in _MUL_FNS:
            return "mul"
        if name in _ADDSUB_FNS:
            return "addsub"
        if name in _SAFE_FNS:
            return "safe"
        return "unknown"            # user PointFn: be conservative
    return "safe"                   # ArgMin / Const / External / ...


def _eval_node(n: IRNode, env: Dict[int, Any]) -> Any:
    if n.dispatch is not None:
        r = n.dispatch.apply(*[env[u] for u in n.dispatch.leaves])
    else:
        r = LOWERERS[n.op](n, n.params, [env[u] for u in n.inputs])
    return jnp_mask(r, n.ty)


class _Task:
    """One schedulable unit: a generic XLA segment (many nodes traced into
    one program) or, via _MKTask, a megakernel segment."""

    def __init__(self, nodes: List[IRNode], in_uids: Tuple[int, ...],
                 out_uids: Tuple[int, ...]):
        self.nodes = nodes
        self.in_uids = in_uids
        self.out_uids = out_uids
        # indices into in_uids whose values die after this task (filled by
        # the planner's liveness pass): the donate-able batched call path
        # hands these buffers back to XLA for reuse
        self.dead_in: Tuple[int, ...] = ()
        # per out uid, how its value leaves the program (_out_carriers);
        # only the pipeline root's narrows (set by the planner)
        self.out_carriers: Tuple[Any, ...] = (None,) * len(out_uids)
        self._jit: Dict[str, Any] = {}

    def _fn(self, *invals):
        return tuple(_to_device_carrier(r, c) for r, c in
                     zip(self._outs(*invals), self.out_carriers))

    def _outs(self, *invals):
        env = dict(zip(self.in_uids, invals))
        for n in self.nodes:
            env[n.uid] = _eval_node(n, env)
        return tuple(env[u] for u in self.out_uids)

    def call(self, mode: str, invals, in_axes, donate: bool = False,
             mesh=None):
        if mode == "batch" and not any(a == 0 for a in in_axes):
            mode = "frame"              # constant subgraph: no frame axis
        donate_idx = self.dead_in if (donate and mode == "batch") else ()
        key = (mode, donate_idx) if mode == "frame" \
            else ("batch", in_axes, donate_idx, mesh)
        if key not in self._jit:
            fn = self._fn if mode == "frame" else jax.vmap(self._fn,
                                                           in_axes=in_axes)
            if mode == "batch" and mesh is not None:
                # one shard of the frame axis per device: a Pallas kernel
                # is not partitioned by XLA, so every device runs the
                # vmapped program on its local frames
                fn = jax.shard_map(
                    fn, mesh=mesh, in_specs=tuple(
                        P(FRAME_AXIS) if a == 0 else P() for a in in_axes),
                    out_specs=P(FRAME_AXIS), check_vma=False)
            self._jit[key] = jax.jit(fn, donate_argnums=donate_idx)
        return self._jit[key](*invals)


class _MKTask(_Task):
    """A megakernel segment: the whole span is one fused Pallas program
    (jit/vmap wrap it exactly like a generic segment, so every call path —
    frame, batch, serve — takes it unchanged)."""

    def __init__(self, nodes: List[IRNode], in_uids: Tuple[int, ...],
                 out_uids: Tuple[int, ...], mk: Megakernel):
        super().__init__(nodes, in_uids, out_uids)
        self.mk = mk

    def _outs(self, *invals):
        return self.mk.apply(*invals)


class CompiledPipeline:
    """Executable lowering of an HWImg DAG, bit-exact vs executor.py on
    integer pipelines and generic segments, bounded-ULP on megakernel
    float segments (megakernel.FLOAT_ULP_BOUND).

    Pipeline: build the IR (ir.py), rewrite it to fixpoint against the
    resident rule library (rewrite.py / patterns.py; the pallas backend
    additionally enables the Pallas-kernel dispatch rules, and megakernel
    emission skips the rules its streaming subsumes), partition the
    schedule, and compile one program per segment.  ``notes`` is the
    lowering report; ``fusions`` maps pattern-root uid -> Dispatch;
    ``megakernels`` lists the emitted segment kernels."""

    def __init__(self, out: Val, backend: str = "jax", debug: bool = False,
                 megakernel: str = "auto", per_node: bool = False):
        if backend not in ("jax", "pallas"):
            raise ValueError(f"unknown lowering backend {backend!r}")
        if megakernel not in ("auto", "off"):
            raise ValueError(f"unknown megakernel mode {megakernel!r}")
        self.out = out
        self.backend = backend
        self.debug = debug
        self.per_node = per_node
        # megakernels are a pallas-backend feature: the jax backend is the
        # pure-XLA reference lowering and stays per-op + FMA-split
        self.megakernel_on = (backend == "pallas" and megakernel == "auto"
                              and not debug and not per_node)
        self.megakernels: List[Megakernel] = []
        self.ir = LoweringIR(out)
        rules = [r for r in RULES
                 if not (self.megakernel_on and r.name in MK_SUBSUMED_RULES)]
        self.fusions, self.notes, self.graph_rewrites = apply_rules(
            self.ir, rules, backend)
        self._inputs = [n for n in self.ir.order if n.op == "Input"]
        self._plan = self._partition()
        # the root leaves the device on its device_carrier; the host
        # widens it back to the int64 carrier (_to_host, split_frames)
        root_ty = self.ir.nodes[self.ir.root].ty
        carriers = _out_carriers(root_ty)
        for t in self._plan:
            t.out_carriers = tuple(carriers if u == self.ir.root else None
                                   for u in t.out_uids)
        self.notes.append(
            f"lowering backend={backend}: {len(self.fusions)} fused "
            f"dispatch(es), {self.graph_rewrites} graph rewrite(s); "
            + ("eager debug mode" if debug else
               f"jit engine: {len(self._plan)} program segment(s) over "
               f"{sum(len(t.nodes) for t in self._plan)} nodes"
               + (f", {len(self.megakernels)} megakernel(s)"
                  if self.megakernels else "")))
        for mk in self.megakernels:
            self.notes.append("  " + mk.report_line())
        if not debug:
            for s in _narrowed(carriers):
                self.notes.append(
                    f"output carrier: {np.dtype(device_carrier(s)).name} "
                    f"for {s!r} (was int64)")
        # per-signature call counts; the first call at a signature traces
        # and XLA-compiles, later calls hit the jit cache
        self.signatures: Dict[Tuple[str, Any], int] = {}

    # ---- planning ----
    def _segment_io(self, nodes: List[IRNode]
                    ) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
        produced = {n.uid for n in nodes}
        in_uids: List[int] = []
        for n in nodes:
            for u in self.ir.effective_inputs(n):
                if u not in produced and u not in in_uids:
                    in_uids.append(u)
        out_uids = tuple(
            n.uid for n in nodes
            if n.uid == self.ir.root
            or any(c not in produced for c in n.consumers))
        return tuple(in_uids), out_uids

    def _fma_groups(self, body: List[IRNode]) -> List[List[IRNode]]:
        """Greedy maximal generic segments over ``body``: a segment closes
        only when the next node is an f32 add/sub consuming a value that an
        f32 multiply *in the same segment* produced (directly or through
        data movement) — the one adjacency a contracting backend would fuse
        into an FMA.  Integer pipelines never split either way."""
        split_fma = backend_contracts_fma()
        groups: List[List[IRNode]] = []
        cur: List[IRNode] = []
        taint: Dict[int, bool] = {}     # uid -> mul-reachable in cur
        for n in body:
            kind = _float_kind(n)
            ins = self.ir.effective_inputs(n)
            if (split_fma and kind in ("addsub", "unknown")
                    and any(taint.get(u, False) for u in ins) and cur):
                groups.append(cur)      # program boundary materializes the
                cur = []                # product before the add sees it
                taint = {}
            cur.append(n)
            taint[n.uid] = (kind in ("mul", "unknown")
                            or (kind == "move"
                                and any(taint.get(u, False) for u in ins)))
        if cur:
            groups.append(cur)
        return groups

    def _clustered_body(self) -> List[IRNode]:
        """Topological order over non-Input nodes that groups streamable
        nodes into maximal contiguous runs (Kahn's algorithm preferring to
        stay in the current class; FIFO within a class preserves the
        schedule's relative order)."""
        body = [n for n in self.ir.order if n.op != "Input"]
        in_body = {n.uid for n in body}
        deps = {n.uid: {u for u in self.ir.effective_inputs(n)
                        if u in in_body} for n in body}
        ndep = {u: len(vs) for u, vs in deps.items()}
        cons: Dict[int, List[int]] = {n.uid: [] for n in body}
        for n in body:
            for u in deps[n.uid]:
                cons[u].append(n.uid)
        ready: Dict[bool, deque] = {True: deque(), False: deque()}
        for n in body:                  # ir.order: deterministic seeding
            if ndep[n.uid] == 0:
                ready[streamable(n)].append(n)
        out: List[IRNode] = []
        cur = True
        while ready[True] or ready[False]:
            if not ready[cur]:
                cur = not cur
            n = ready[cur].popleft()
            out.append(n)
            for cuid in cons[n.uid]:
                ndep[cuid] -= 1
                if ndep[cuid] == 0:
                    cn = self.ir.nodes[cuid]
                    ready[streamable(cn)].append(cn)
        return out

    def _partition(self) -> List[_Task]:
        """Segment the schedule.  Megakernel mode carves maximal streamable
        spans and emits one fused Pallas kernel per span (falling back to
        the generic path per span on MKUnsupported); everything else —
        including the whole schedule on ``backend="jax"`` — becomes maximal
        generic XLA segments split per _fma_groups.  ``per_node=True``
        compiles every node separately (the bench's per-op baseline)."""
        body = [n for n in self.ir.order if n.op != "Input"]
        if self.per_node:
            groups: List[Tuple[bool, List[IRNode]]] = \
                [(False, [n]) for n in body]
        elif not self.megakernel_on:
            groups = [(False, g) for g in self._fma_groups(body)]
        else:
            ordered = self._clustered_body()
            spans: List[Tuple[bool, List[IRNode]]] = []
            for n in ordered:
                cls = streamable(n)
                if spans and spans[-1][0] == cls:
                    spans[-1][1].append(n)
                else:
                    spans.append((cls, [n]))
            groups = []
            pending: List[IRNode] = []  # spans that stay on the XLA path
            for is_stream, nodes in spans:
                task_nodes = None
                if is_stream and worth_emitting(nodes):
                    task_nodes = nodes
                if task_nodes is None:
                    pending.extend(nodes)
                    continue
                if pending:
                    groups.extend((False, g)
                                  for g in self._fma_groups(pending))
                    pending = []
                groups.append((True, task_nodes))
            if pending:
                groups.extend((False, g) for g in self._fma_groups(pending))

        tasks: List[_Task] = []
        for want_mk, nodes in groups:
            in_uids, out_uids = self._segment_io(nodes)
            if want_mk:
                try:
                    mk = emit_megakernel(
                        self.ir, nodes, in_uids, out_uids,
                        name=f"mk{len(self.megakernels)}")
                except MKUnsupported as exc:
                    self.notes.append(f"megakernel fallback ({exc}); "
                                      f"generic XLA segment(s) instead")
                    tasks.extend(self._build_tasks(
                        self._fma_groups(nodes)))
                    continue
                self.megakernels.append(mk)
                tasks.append(_MKTask(nodes, in_uids, out_uids, mk))
            else:
                tasks.append(_Task(nodes, in_uids, out_uids))

        # liveness: an input value dies at its last consuming task (and is
        # not the pipeline root) — those buffers are safe to donate on the
        # batched serving path, letting XLA reuse them for outputs
        for i, t in enumerate(tasks):
            live_later = {u for lt in tasks[i + 1:] for u in lt.in_uids}
            t.dead_in = tuple(j for j, u in enumerate(t.in_uids)
                              if u not in live_later and u != self.ir.root)
        return tasks

    def _build_tasks(self, groups: List[List[IRNode]]) -> List[_Task]:
        out = []
        for nodes in groups:
            in_uids, out_uids = self._segment_io(nodes)
            out.append(_Task(nodes, in_uids, out_uids))
        return out

    # ---- execution ----
    def _load_inputs(self, inputs: Dict[str, Any], env: Dict[int, Any]):
        for n in self._inputs:
            raw = inputs[n.params["name"]]
            if isinstance(n.ty, TupleT):
                env[n.uid] = tuple(_as_input(e) for e in raw)
            else:
                env[n.uid] = _as_input(raw)

    def _run(self, inputs: Dict[str, Any], mode: str, donate: bool = False,
             mesh=None):
        env: Dict[int, Any] = {}
        self._load_inputs(inputs, env)
        # batch mode: inputs carry the frame axis; a vmapped task broadcasts
        # ALL its outputs onto it (vmap's out_axes=0), including outputs
        # derived only from constants — so batchedness is tracked per task
        # call, not per IR node
        batched = {n.uid: True for n in self._inputs}
        for t in self._plan:
            axes = tuple(0 if batched.get(u, False) else None
                         for u in t.in_uids)
            outs = t.call(mode, [env[u] for u in t.in_uids], axes,
                          donate=donate, mesh=mesh)
            env.update(zip(t.out_uids, outs))
            vmapped = mode == "batch" and any(a == 0 for a in axes)
            for u in t.out_uids:
                batched[u] = vmapped
        return env[self.ir.root]

    def _eval(self, inputs: Dict[str, Any]):
        """Eager per-node evaluation (debug path / node-level diffing)."""
        env: Dict[int, Any] = {}
        self._load_inputs(inputs, env)
        for n in self.ir.order:
            if n.op != "Input":
                env[n.uid] = _eval_node(n, env)
        return env[self.ir.root]

    def _record(self, inputs, mode: str) -> None:
        sig = (mode, self.frame_signature(inputs))
        self.signatures[sig] = self.signatures.get(sig, 0) + 1

    def __call__(self, inputs: Dict[str, Any]):
        with jax.enable_x64(True):
            if self.debug:
                return _to_numpy(self._eval(inputs))
            self._record(inputs, "frame")
            return _to_host(self._run(inputs, "frame"))

    def run_batch(self, inputs: Dict[str, Any]):
        """vmap over a leading frame axis on every input (the throughput /
        serving entry point), through the same jit program cache."""
        with jax.enable_x64(True):
            if self.debug:
                return _to_numpy(jax.vmap(self._eval)(inputs))
            self._record(inputs, "batch")
            return _to_host(self._run(inputs, "batch"))

    def run_batch_device(self, inputs: Dict[str, Any], donate: bool = False,
                         mesh=None):
        """The serving call path: batched execution that keeps results on
        device (jax arrays, asynchronously dispatched — no host sync), so a
        caller can overlap host→device transfer of the next batch with this
        batch's compute before converging on the result.  ``donate=True``
        additionally donates each segment's dead input buffers to XLA
        (frame buffers are single-use in a server, so their pages can be
        reused for outputs; a no-op where the platform lacks donation).
        Callers must not reuse donated input arrays afterwards.  ``mesh``
        (a 1-d mesh over the ``frames`` axis, serve/sharding.py) runs every
        program once per device on that device's shard of the frames.
        Integer leaves of the result are on ``device_carrier`` (32 bits
        where the declared type fits), not on the int64 carrier:
        ``split_frames`` widens them back on the host."""
        with jax.enable_x64(True):
            self._record(inputs, "serve")
            return self._run(inputs, "batch", donate=donate, mesh=mesh)

    @staticmethod
    def frame_signature(inputs: Dict[str, Any]) -> Tuple:
        """Hashable per-frame (shape, dtype) signature of an input dict —
        the micro-batcher's bucketing key: frames sharing a signature stack
        into one batch whose jit-cache entry is shared by every equal-sized
        batch at that signature."""
        return tuple(sorted((k, _spec(v)) for k, v in inputs.items()))

    def node_values(self, inputs: Dict[str, Any]) -> Dict[int, Any]:
        """Eager per-node evaluation returning every live node's value
        keyed by uid — the node-level diffing hook (debug tooling)."""
        vals: Dict[int, Any] = {}
        with jax.enable_x64(True):
            env: Dict[int, Any] = {}
            self._load_inputs(inputs, env)
            for n in self.ir.order:
                if n.op != "Input":
                    env[n.uid] = _eval_node(n, env)
                vals[n.uid] = _to_numpy(env[n.uid])
        return vals

    # ---- reporting ----
    def megakernel_stats(self) -> Dict[str, Any]:
        """Per-pipeline megakernel roll-up (bench rows + regression gate):
        segment counts, fused-node total, VMEM line-buffer bytes, and a
        per-segment roofline table (scalar ops vs kernel-boundary bytes —
        arithmetic intensity shows which segments fusion actually feeds
        and which are bandwidth-bound data movement)."""
        return {
            "segments": len(self.megakernels),
            "total_segments": len(self._plan),
            "fused_nodes": sum(m.n_nodes for m in self.megakernels),
            "linebuf_bytes": sum(m.linebuf_bytes
                                 for m in self.megakernels),
            "float_nodes": sum(m.float_nodes for m in self.megakernels),
            "rooflines": [
                {"segment": m.name, "flops": m.flops,
                 "io_bytes": m.io_bytes,
                 "arithmetic_intensity":
                     round(m.arithmetic_intensity, 4)}
                for m in self.megakernels],
        }

    def cache_stats(self) -> List[str]:
        """Per-signature jit cache stats (mode, shapes, calls)."""
        lines = []
        for (mode, spec), calls in sorted(self.signatures.items(),
                                          key=lambda kv: repr(kv[0])):
            shapes = ", ".join(f"{name}={s}" for name, s in spec)
            lines.append(f"jit[{mode}] {shapes}: calls={calls} "
                         f"(first call compiled, {calls - 1} cache hit(s))")
        return lines

    def report_lines(self) -> List[str]:
        return list(self.notes) + self.cache_stats()


class LoweredPipeline(CompiledPipeline):
    """Back-compat alias for the pre-refactor class name."""


def lower_pipeline(out: Val, backend: str = "jax", debug: bool = False,
                   megakernel: str = "auto",
                   per_node: bool = False) -> CompiledPipeline:
    return CompiledPipeline(out, backend=backend, debug=debug,
                            megakernel=megakernel, per_node=per_node)
