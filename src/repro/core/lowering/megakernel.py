"""Megakernel emission: one fused Pallas kernel per schedule segment.

The engine's generic path executes one XLA op per IR node, materializing
every intermediate image in memory.  Hardware doesn't work that way — the
paper's pipelines stream rows through line buffers and FIFOs — and neither
does this emitter: for an eligible segment it generates a *single* Pallas
kernel whose grid walks the output frame in row blocks.  Segment inputs
arrive as row strips (a BlockSpec per strip, the conv2d kernel's halo
scheme); every interior node keeps only the rowful *window* its consumers
demand (its line buffer); the point-op/stencil/reduce chain is applied
block by block, so no intermediate image is ever written back whole.

Row-demand propagation.  At grid step ``i`` each node's window is rows
``[a*i + c, a*i + c + size)`` of its virtual frame: an affine offset with
integer rate ``a`` (rows per grid step) and static ``c`` and ``size``.
Demands propagate backward through the segment's geometry: stencils shift
by their window base and widen by the window height, pad/crop shift,
downsampling scales the rate, upsampling divides it (exactly — the block
is chosen so every division is whole, which keeps resampling pyramids'
skewed row phases static).  Reconvergent demands on one producer must
share a rate and merge into the union window.  Because every rate and
offset is static, a consumer finds its rows in its producer's window at a
static position: the kernel body has no data-dependent indexing at all,
only the strips' BlockSpec index maps move with ``i``.

The kernel's value layout is *planar*: a node of type ``T[w, h]`` whose
elements are themselves arrays (stencil patches, stacked channels) is held
as a numpy object array, indexed by the element's trailing dims, of 2-D
``(rows, w)`` planes.  Every in-kernel operation is then a 2-D one in a
32-bit dtype, the form a TPU vector unit computes in: integers ride int32
wherever the node's declared width, or the value range that
analysis/ranges.py proves for it, fits (the engine's int64 carrier is
converted at the kernel boundary), floats ride f32.  A segment that would
need a wider carrier raises ``MKUnsupported`` and keeps the generic path.

Virtual rows outside a node's frame read as zero (the executor's stencil
zero-fill): segment inputs are zero-padded around the frame, and an
interior node that feeds a stencil is masked on the rows of its window
that leave its frame.

Verification contract (two tiers, see engine.py): integer segments are
bit-exact — each node's result is wrapped to its declared width exactly
like the generic path, and int32 arithmetic wraps like the hardware does.
Float segments are promised within ``FLOAT_ULP_BOUND`` ULPs of the
reference executor.  Inside the kernel every f32 multiply is an IEEE
multiply that no backend may contract into a following add
(``_exact_f32_mul``), which is what lets the engine drop the FMA segment
split for fused f32 segments; the ULP bound is there for divide and
square root, which an accelerator may round differently.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ...kernels.stream import (LANES, MK_BLOCK_ROWS, SUBLANES,
                               decimate_lanes, interpret_default, nbytes,
                               pad_lanes, pick_rows, shift_lanes)
from ..dtypes import Bits, BoolT, Float, Int, TupleT, UInt, mask_to_width
from ..hwimg import map_reshape_plans, scalar_of, type_shape
from .ir import IRNode, LoweringIR
from .lowerers import _JNP_FNS, jnp_point_fn

# the float tier of the verification contract: megakernel outputs are
# within this many ULPs of the reference executor.  Tests and the chip
# smoke check enforce it.
FLOAT_ULP_BOUND = 4

# a node whose element type fans out into more planes than this (e.g. a
# wide stencil of a wide stencil) would unroll into an unreasonably large
# kernel; such segments keep the generic path
MAX_PLANES = 512

_INT32 = (-(1 << 31), (1 << 31) - 1)


class MKUnsupported(Exception):
    """Segment not eligible for megakernel emission (the engine keeps the
    generic per-op XLA path for it)."""


# ops the emitter can stream row-block-wise.  Dispatch nodes (opaque fused
# kernels), Filter/SparseTake (data-dependent global gather) and External
# (host callback) stay on the generic path.
STREAM_OPS = frozenset({
    "Map", "Reduce", "ReducePatch", "ArgMin", "Stencil", "Pad", "Crop",
    "Downsample", "Upsample", "Replicate", "Stack", "Concat", "FanOut",
    "FanIn", "TupleIndex", "Const",
})
# arithmetic/geometry: a span of pure tuple plumbing isn't worth a kernel
_COMPUTE_OPS = frozenset({
    "Map", "Reduce", "ReducePatch", "ArgMin", "Stencil", "Pad", "Crop",
    "Downsample", "Upsample",
})

# float-touching point-functions with known-safe behavior inside a fused
# kernel: the _JNP_FNS lowerings (FloatMul rides the contraction-proof
# multiply, add/sub/div/sqrt can't start an FMA pattern once every
# multiply is protected) plus int->float converts and compares.  An
# unknown user PointFn touching float could hide an f32 mul->add
# composition, so it stays on the generic path, where the engine's FMA
# split protects it.
_KNOWN_FLOAT_FNS = frozenset(_JNP_FNS) | frozenset({"ToFloat", "Gt"})


def _is_float(s) -> bool:
    return isinstance(s, Float)


def _elems(ty) -> List:
    """Image leaves of a node type (tuple fan points carry several)."""
    return list(ty.elems) if isinstance(ty, TupleT) else [ty]


def streamable(n: IRNode) -> bool:
    """Node-level eligibility: the emitter knows the op, every tuple leg
    is a plain image (equal heights at fan points), and any float
    point-function has a known contraction-safe lowering."""
    if n.dispatch is not None or n.op not in STREAM_OPS:
        return False
    for ty in (n.ty,) + tuple(n.input_tys):
        if isinstance(ty, TupleT):
            if any(isinstance(t, TupleT) for t in ty.elems):
                return False            # nested tuples
            hs = {type_shape(t)[0] for t in ty.elems
                  if len(type_shape(t)) >= 2}
            if len(hs) > 1:
                return False            # fan of unequal heights
    if n.op in ("Map", "Reduce", "ReducePatch"):
        fn = n.params["fn"]
        if fn.name not in _KNOWN_FLOAT_FNS and any(
                _is_float(scalar_of(t))
                for t in (n.ty,) + tuple(n.input_tys)):
            return False    # unknown float fn: np_fn may hide a mul→add
    if n.op == "Downsample":
        # executor semantics stride-slice (ceil) while the typed shape
        # floors; they agree only when the strides divide the frame — the
        # generic path keeps the odd-size case.  Lanes decimate within one
        # 128-lane register, so the lane stride must divide it.
        shape = type_shape(n.input_tys[0])
        if (shape[0] % n.params["sy"] or shape[1] % n.params["sx"]
                or LANES % n.params["sx"]):
            return False
    return True


def worth_emitting(nodes: List[IRNode]) -> bool:
    """A span earns a kernel when it fuses at least two nodes and does
    some arithmetic/geometry (not just tuple plumbing)."""
    return len(nodes) >= 2 and any(n.op in _COMPUTE_OPS for n in nodes)


# --------------------------------------------------------------------------
# carriers and point functions (the in-kernel value semantics)

def _carrier_dtype(ty):
    """The engine's carrier outside kernels (int64 for every integer)."""
    s = scalar_of(ty)
    if isinstance(s, (UInt, Bits, Int)):
        return jnp.int64
    return jnp.dtype(s.np_dtype())


def _declared_range(s) -> Optional[Tuple[int, int]]:
    if isinstance(s, (UInt, Bits)):
        return 0, (1 << s.bits()) - 1
    if isinstance(s, Int):
        return -(1 << (s.bits() - 1)), (1 << (s.bits() - 1)) - 1
    if isinstance(s, BoolT):
        return 0, 1
    return None


def _fits32(lo: int, hi: int) -> bool:
    return _INT32[0] <= lo and hi <= _INT32[1]


def _kernel_dtype(s):
    """In-kernel carrier of a scalar type (int range checked separately)."""
    if _is_float(s):
        if jnp.dtype(s.np_dtype()) != jnp.float32:
            raise MKUnsupported(f"no 32-bit kernel carrier for {s!r}")
        return jnp.float32
    if isinstance(s, BoolT):
        return jnp.bool_
    return jnp.int32


def _io_dtype(dt):
    """Kernel operand/result dtype: bool crosses the boundary as int32."""
    return jnp.int32 if dt == jnp.bool_ else dt


# one quiet-NaN bit pattern: the fence below flips its payload bit
_FENCE_NAN = 0x7FC00001


def _exact_f32_mul(a, b):
    # the IEEE f32 product, fenced off from a consuming add: the product's
    # bits pass through an integer select that differs from the identity
    # only on one NaN payload, so neither XLA nor LLVM can fold it away,
    # and no backend can contract the multiply with the add behind it
    # into an FMA — the drift that turns FLOW's det == 0 into a tiny
    # nonzero residual
    bits = jax.lax.bitcast_convert_type(a * b, jnp.int32)
    bits = jnp.where(bits == _FENCE_NAN, bits ^ 2, bits)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


_MK_FNS: Dict[str, Callable[[Dict[str, Any]], Callable]] = {
    "FloatMul": lambda p: _exact_f32_mul,
    "AbsDiff": lambda p: (lambda a, b: jnp.abs(a - b)),
}


def mk_point_fn(fn) -> Callable:
    if fn.name in _MK_FNS:
        return _MK_FNS[fn.name](dict(fn.params))
    return jnp_point_fn(fn)


def _mask_k(x, s):
    """jnp_mask on the 32-bit kernel carrier: wrap to the declared width.
    Widths of 32 bits and more are identities here — the carrier check
    admitted such a node only with its values proven inside int32."""
    if isinstance(s, (UInt, Bits)) and s.bits() < 32:
        return x & ((1 << s.bits()) - 1)
    if isinstance(s, Int) and s.bits() < 32:
        sh = 32 - s.bits()
        return (x << sh) >> sh
    return x


# --------------------------------------------------------------------------
# planar values: object arrays of 2-D planes

def _obj(shape) -> np.ndarray:
    return np.empty(shape, dtype=object)


def _wrap(x) -> np.ndarray:
    """numpy returns 0-d object results as bare objects; re-box them."""
    if isinstance(x, np.ndarray) and x.dtype == object:
        return x
    a = _obj(())
    a[()] = x
    return a


def _pmap(f, *xs) -> np.ndarray:
    """Apply ``f`` leafwise with numpy broadcasting over the planes."""
    return _wrap(np.frompyfunc(f, len(xs), 1)(*xs))


def _tmap(f, v):
    """``_pmap`` through a tuple-typed value."""
    return tuple(_pmap(f, e) for e in v) if isinstance(v, tuple) \
        else _pmap(f, v)


def _n_planes(ty) -> int:
    return sum(math.prod(type_shape(t)[2:]) for t in _elems(ty))


# --------------------------------------------------------------------------
# row-demand propagation

@dataclass(frozen=True)
class Demand:
    """Window rows ``[a*i + c, a*i + c + size)`` of a node's virtual frame
    at grid step ``i``."""

    a: int
    c: int
    size: int

    def stays_inside(self, grid: int, h: int) -> bool:
        """Does the window stay within rows [0, h) at every grid step?"""
        return self.c >= 0 and self.a * (grid - 1) + self.c + self.size <= h


class _RowRate(MKUnsupported):
    """An upsampling stride doesn't divide the block's row rate."""


def _shift(d: Demand, k: int, grow: int = 0) -> Demand:
    return Demand(d.a, d.c + k, d.size + grow)


def _scale(d: Demand, sy: int) -> Demand:
    return Demand(d.a * sy, d.c * sy, sy * (d.size - 1) + 1)


def _floordiv(d: Demand, sy: int) -> Demand:
    if d.a % sy:
        raise _RowRate(f"row rate {d.a} is not a multiple of stride {sy}")
    lo, hi = d.c // sy, (d.c + d.size - 1) // sy
    return Demand(d.a // sy, lo, hi - lo + 1)


def _merge(x: Optional[Demand], y: Demand) -> Demand:
    if x is None:
        return y
    if x.a != y.a:
        raise MKUnsupported(f"reconvergent row rates {x.a} and {y.a}")
    c = min(x.c, y.c)
    return Demand(x.a, c, max(x.c + x.size, y.c + y.size) - c)


def _map_streams_input(n: IRNode, j: int) -> bool:
    """Does Map input j ride the row stream (leading (h, w) matches the
    output) or broadcast whole (coefficient arrays, scalars)?"""
    s_in = type_shape(n.input_tys[j])
    return len(s_in) >= 2 and s_in[:2] == type_shape(n.ty)[:2]


def _input_demands(n: IRNode, d: Demand) -> List[Optional[Demand]]:
    """Per-input row demand implied by demand ``d`` on node ``n`` (None:
    a broadcast operand, read whole)."""
    p = n.params
    if n.op == "Map":
        return [d if _map_streams_input(n, j) else None
                for j in range(len(n.inputs))]
    if n.op in ("Reduce", "ReducePatch", "ArgMin", "Replicate", "Stack",
                "Concat", "FanOut", "FanIn", "TupleIndex"):
        return [d] * len(n.inputs)
    if n.op == "Stencil":
        sh = abs(p["t"] - p["b"]) + 1
        return [_shift(d, p["b"], grow=sh - 1)]
    if n.op == "Pad":
        return [_shift(d, -p["t"])]
    if n.op == "Crop":
        return [_shift(d, p["t"])]
    if n.op == "Downsample":
        return [_scale(d, p["sy"])]
    if n.op == "Upsample":
        return [_floordiv(d, p["sy"])]
    raise MKUnsupported(f"no demand rule for {n.op}")


def _demand_pass(nodes: List[IRNode], out_uids, consts,
                 block: int) -> Dict[int, Demand]:
    """Reverse pass: the row window of every streamed node and of every
    segment input."""
    demand: Dict[int, Demand] = {u: Demand(block, 0, block)
                                 for u in out_uids}
    for n in reversed(nodes):
        if n.uid in consts:
            continue
        for u, di in zip(n.inputs, _input_demands(n, demand[n.uid])):
            if (di is None) != (u in consts):
                raise MKUnsupported(
                    f"%{n.uid}:{n.op} operand %{u} is neither a streamed "
                    f"image nor a constant")
            if di is not None:
                demand[u] = _merge(demand.get(u), di)
    return demand


def _plan_rows(nodes, out_uids, in_uids, consts, h_out: int,
               block_rows: Optional[int]) -> Tuple[int, Dict[int, Demand]]:
    """Pick the row block and run the demand pass.  The default block is
    the smallest multiple of MK_BLOCK_ROWS for which every upsampling
    stride divides its row rate and every input strip is whole 32-bit
    register rows; an explicit ``block_rows`` is taken as given."""
    cands = ([block_rows] if block_rows is not None
             else [MK_BLOCK_ROWS * m for m in (1, 2, 4, 8)])
    why = "no candidate block"
    for block in cands:
        block = min(block, h_out)
        try:
            demand = _demand_pass(nodes, out_uids, consts, block)
        except _RowRate as exc:
            why = str(exc)
            continue
        if block_rows is None and block < h_out and any(
                demand[u].a % SUBLANES for u in in_uids):
            why = f"input strips of {block}-row blocks are not whole " \
                  f"{SUBLANES}-row tiles"
            continue
        return block, demand
    raise MKUnsupported(f"no row block fits: {why}")


# --------------------------------------------------------------------------
# emission

@dataclass
class Megakernel:
    """One emitted segment kernel + its report card."""

    name: str
    apply: Callable                     # (*leaf values) -> tuple of outs
    n_nodes: int
    n_leaves: int
    block_rows: int
    grid: int
    linebuf_bytes: int                  # windowed (line-buffered) bytes
    float_nodes: int                    # nodes under the ULP tier
    n_winsum: int = 0                   # box-sum chains summed separably
    note: str = ""
    flops: int = 0                      # scalar ops per frame (int ops too)
    io_bytes: int = 0                   # kernel-boundary bytes per frame

    @property
    def arithmetic_intensity(self) -> float:
        """Roofline x-axis: scalar ops per byte crossing the kernel
        boundary.  High intensity = fusion is paying (work stays in
        VMEM); near zero = the segment is bandwidth-bound movement."""
        return self.flops / self.io_bytes if self.io_bytes else 0.0

    def report_line(self) -> str:
        tier = (f"float tier (ULP<={FLOAT_ULP_BOUND})" if self.float_nodes
                else "integer tier (bit-exact)")
        ws = (f", {self.n_winsum} box-sum chain(s) as separable sums"
              if self.n_winsum else "")
        return (f"{self.name}: {self.n_nodes} fused nodes, "
                f"grid={self.grid}x{self.block_rows}rows, "
                f"linebuf={self.linebuf_bytes}B, {tier}{ws}")


def _check_carriers(ir: LoweringIR, nodes: List[IRNode],
                    in_uids) -> Dict[int, List[Any]]:
    """Per-node, per-leaf kernel dtype; raises MKUnsupported where an
    integer value (or an intermediate of a non-wrapping op) can leave
    int32 — those segments need the engine's int64 carrier."""
    ranges = ir.value_ranges()

    def rng(u: int, s) -> Tuple[int, int]:
        lo, hi = _declared_range(s)
        if not isinstance(ir.nodes[u].ty, TupleT) and u in ranges:
            lo, hi = max(lo, ranges[u][0]), min(hi, ranges[u][1])
        return lo, hi

    dts: Dict[int, List[Any]] = {}
    for u in list(in_uids) + [n.uid for n in nodes]:
        n = ir.nodes[u]
        leaves = []
        for t in _elems(n.ty):
            s = scalar_of(t)
            dt = _kernel_dtype(s)
            if dt == jnp.int32 and not _fits32(*rng(u, s)):
                raise MKUnsupported(
                    f"%{u}:{n.op} values {rng(u, s)} need the int64 carrier")
            leaves.append(dt)
        dts[u] = leaves
        if n.op == "Map" and n.params["fn"].name in ("AbsDiff", "Abs") \
                and u not in in_uids:
            ins = [rng(v, scalar_of(ir.nodes[v].ty)) for v in n.inputs]
            if len(ins) == 2:
                bound = max(abs(ins[0][1] - ins[1][0]),
                            abs(ins[1][1] - ins[0][0]))
            else:
                bound = max(abs(ins[0][0]), abs(ins[0][1]))
            if bound > _INT32[1]:
                raise MKUnsupported(f"%{u}:{n.op} can overflow int32")
    return dts


def _const_planes(n: IRNode, dt) -> np.ndarray:
    """A Const as planes of numpy scalars of its kernel dtype (baked into
    the kernel as literals: no operand, no captured array)."""
    s = scalar_of(n.ty)
    v = np.asarray(n.params["value"])
    if _declared_range(s) is not None and not isinstance(s, BoolT):
        v = mask_to_width(v, s)
    v = v.astype(dt)
    out = _obj(v.shape)
    for idx in np.ndindex(v.shape):
        out[idx] = v[idx]
    return out


def _winsum_chains(ir: LoweringIR, nodes: List[IRNode], span,
                   out_uids) -> Tuple[Dict[int, IRNode], set]:
    """Peephole: integer box-sum chains -> separable sums.

    Stencil -> (Map(AddMSBs))* -> Reduce(Add|AddAsync), single-consumer
    all the way, integer-carried, plain 2-D frames.  Integer addition wraps
    consistently in the 32-bit carrier (AddMSBs only widens), so summing
    the patch as rows-then-lanes is bit-exact while replacing sh*sw taps
    with sh + sw adds — the in-kernel mirror of the window_sum rewrite
    rule that megakernel emission subsumes (FLOW's five 8x8
    second-moment sums are the poster child)."""
    out_set = set(out_uids)
    winsum: Dict[int, IRNode] = {}      # Reduce uid -> its Stencil node
    skip: set = set()                   # chain interiors: never computed
    for n in nodes:
        if (n.op != "Stencil" or _is_float(scalar_of(n.ty))
                or len(type_shape(n.input_tys[0])) != 2):
            continue
        chain, cur, tail = [n], n, None
        while (len(set(cur.consumers)) == 1 and cur.uid not in out_set
               and cur.consumers[0] in span):
            c = ir.nodes[cur.consumers[0]]
            if (c.op == "Map" and len(c.inputs) == 1
                    and c.params["fn"].name == "AddMSBs"):
                chain.append(c)
                cur = c
                continue
            if (c.op == "Reduce" and not _is_float(scalar_of(c.ty))
                    and c.params["fn"].name in ("Add", "AddAsync")):
                tail = c
            break
        if tail is not None:
            winsum[tail.uid] = n
            skip.update(x.uid for x in chain)
    return winsum, skip


def emit_megakernel(ir: LoweringIR, nodes: List[IRNode],
                    in_uids: Tuple[int, ...], out_uids: Tuple[int, ...],
                    name: str = "mk",
                    block_rows: int | None = None) -> Megakernel:
    """Build the fused row-streaming Pallas kernel for one segment.

    ``nodes`` is the segment in schedule order; ``in_uids`` are values
    produced outside it (whole frames at call time), ``out_uids`` the
    values it must materialize.  Raises MKUnsupported when the segment's
    geometry defeats static windows or its values need a carrier wider
    than 32 bits (the engine then keeps the generic XLA path).

    ``block_rows`` overrides the streaming granularity (rows of output per
    grid step); by default it is chosen by ``_plan_rows``."""
    for n in nodes:
        if not streamable(n):
            raise MKUnsupported(f"%{n.uid}:{n.op} is not streamable")
    span = {n.uid for n in nodes}
    out_nodes = [ir.nodes[u] for u in out_uids]
    consts = set()
    for n in nodes:
        if n.op == "Const":
            consts.add(n.uid)
        elif n.inputs and all(u in consts for u in n.inputs):
            raise MKUnsupported(f"%{n.uid}:{n.op} is a constant expression")
    heights = set()
    for o in out_nodes:
        if o.uid in consts:
            raise MKUnsupported(f"output %{o.uid} is a constant")
        for ty in _elems(o.ty):
            shape = type_shape(ty)
            if len(shape) < 2:
                raise MKUnsupported(f"output %{o.uid} is not an image")
            heights.add(shape[0])
    if len(heights) != 1:
        raise MKUnsupported(f"outputs disagree on height: {heights}")
    h_out = heights.pop()
    for n in nodes:
        if _n_planes(n.ty) > MAX_PLANES:
            raise MKUnsupported(f"%{n.uid}:{n.op} unrolls into "
                                f"{_n_planes(n.ty)} planes")

    block, demand = _plan_rows(nodes, out_uids, in_uids, consts, h_out,
                               block_rows)
    grid = -(-h_out // block)
    dts = _check_carriers(ir, nodes, in_uids)
    winsum, skip = _winsum_chains(ir, nodes, span, out_uids)
    const_vals = {n.uid: _const_planes(n, dts[n.uid][0])
                  for n in nodes if n.uid in consts}
    # interior values a stencil reads must be zero outside their frame
    zero_fill = {n.uid for n in nodes if n.uid not in consts
                 and any(ir.nodes[c].op == "Stencil" for c in n.consumers
                         if c in span)
                 and not demand[n.uid].stays_inside(
                     grid, type_shape(_elems(n.ty)[0])[0])}

    # ---- accounting: line buffers, roofline (per frame) ----
    # flops counts scalar arithmetic ops (integer ops included at weight
    # 1): Map = one op per output scalar, Reduce/ReducePatch = one op per
    # input scalar (the add/cmp tree), fused box-sum chains = window size
    # per output scalar; geometry ops move data, 0 ops.  io_bytes is
    # traffic across the kernel boundary at the kernel's 32-bit carriers.
    def _scalars(ty) -> int:
        return sum(math.prod(type_shape(t)) for t in _elems(ty))

    linebuf = flops = float_nodes = 0
    for n in nodes:
        if any(_is_float(scalar_of(t)) for t in _elems(n.ty)):
            float_nodes += 1
        if n.uid in consts or n.uid in skip:
            continue
        for t in _elems(n.ty):
            shape = type_shape(t)
            linebuf += nbytes((demand[n.uid].size,) + tuple(shape[1:]),
                              jnp.int32)
        if n.uid in winsum:
            _l, _b, sh, sw = _winsum_geometry(winsum[n.uid])
            flops += sh * sw * _scalars(n.ty)
        elif n.op == "Map":
            flops += _scalars(n.ty)
        elif n.op in ("Reduce", "ReducePatch"):
            flops += _scalars(n.input_tys[0])
    io_bytes = sum(nbytes(type_shape(t), jnp.int32)
                   for u in list(in_uids) + list(out_uids)
                   for t in _elems(ir.nodes[u].ty))

    # ---- operand layout: every input leaf plane as row strips ----
    strips = {}                         # uid -> (k0, n_strips, top rows)
    for u in in_uids:
        d = demand[u]
        k0, k1 = d.c // d.a, (d.c + d.size - 1) // d.a
        strips[u] = (k0, k1 - k0 + 1, max(0, -k0) * d.a)
    in_layout = []                      # (uid, leaf, plane index, width)
    for u in in_uids:
        for k, t in enumerate(_elems(ir.nodes[u].ty)):
            shape = type_shape(t)
            for idx in np.ndindex(shape[2:]):
                in_layout.append((u, k, idx, shape[1]))
    out_layout = []                     # (uid, leaf, plane index, width)
    for o in out_nodes:
        for k, t in enumerate(_elems(o.ty)):
            shape = type_shape(t)
            for idx in np.ndindex(shape[2:]):
                out_layout.append((o.uid, k, idx, shape[1]))
    node_list = [n for n in nodes if n.uid not in consts
                 and n.uid not in skip]

    def kernel(*refs):
        i = pl.program_id(0)
        refs = iter(refs)
        val: Dict[int, Any] = dict(const_vals)
        planes: Dict[Tuple[int, int], np.ndarray] = {}
        for u, k, idx, _w in in_layout:
            d, (k0, ns, _top) = demand[u], strips[u]
            win = jnp.concatenate([next(refs)[...] for _ in range(ns)],
                                  axis=0) if ns > 1 else next(refs)[...]
            lo = d.c - k0 * d.a
            win = win[lo:lo + d.size]
            if dts[u][k] == jnp.bool_:
                win = win != 0
            if (u, k) not in planes:
                shape = type_shape(_elems(ir.nodes[u].ty)[k])
                planes[(u, k)] = _obj(shape[2:])
            planes[(u, k)][idx] = win
        for u in in_uids:
            legs = tuple(planes[(u, k)]
                         for k in range(len(_elems(ir.nodes[u].ty))))
            val[u] = legs if isinstance(ir.nodes[u].ty, TupleT) else legs[0]
        masks: Dict[Tuple, Any] = {}

        def rows(u: int, c: int, size: int):
            """Rows [c, c+size) (window coordinates: a*i + c) of u."""
            rel = c - demand[u].c
            assert 0 <= rel and rel + size <= demand[u].size, (u, c, size)
            return _tmap(lambda x: x[rel:rel + size], val[u])

        def inside(d: Demand, shape, lo: int, hi: int):
            """Bool (size, w): window rows whose virtual row is in [lo, hi)."""
            key = (d, shape, lo, hi)
            if key not in masks:
                r = (jax.lax.broadcasted_iota(jnp.int32, shape, 0)
                     + (i * d.a + d.c))
                masks[key] = (r >= lo) & (r < hi)
            return masks[key]

        for n in node_list:
            d = demand[n.uid]
            if n.uid in winsum:
                raw = _window_winsum(winsum[n.uid], d, rows)
            else:
                raw = _window_node(n, d, rows, val, inside, grid)
            legs = list(raw) if isinstance(raw, tuple) else [raw]
            legs = [_pmap(lambda x, s=scalar_of(t): _mask_k(x, s), leg)
                    for leg, t in zip(legs, _elems(n.ty))]
            if n.uid in zero_fill:
                h_n = type_shape(_elems(n.ty)[0])[0]
                legs = [_pmap(lambda x: jnp.where(
                    inside(d, x.shape, 0, h_n), x,
                    jnp.zeros((), x.dtype)), leg) for leg in legs]
            val[n.uid] = tuple(legs) if isinstance(n.ty, TupleT) \
                else legs[0]

        # write output rows [a*i, a*i + block)
        for (u, k, idx, _w), ref in zip(out_layout, refs):
            v = val[u]
            x = (v[k] if isinstance(v, tuple) else v)[idx]
            rel = -demand[u].c
            x = x[rel:rel + block]
            ref[...] = x.astype(jnp.int32) if x.dtype == jnp.bool_ else x

    def apply(*leaf_vals):
        operands, in_specs = [], []
        for u, val in zip(in_uids, leaf_vals):
            d, (k0, ns, top) = demand[u], strips[u]
            legs = list(val) if isinstance(val, tuple) else [val]
            for k, leg in enumerate(legs):
                x = jnp.asarray(leg).astype(_io_dtype(dts[u][k]))
                h = x.shape[0]
                rows_total = (grid + k0 + ns - 1 + top // d.a) * d.a
                x = jnp.pad(x, ((top, max(0, rows_total - top - h)),)
                            + ((0, 0),) * (x.ndim - 1))[:rows_total]
                for idx in np.ndindex(x.shape[2:]):
                    plane = x[(slice(None), slice(None)) + idx]
                    for s in range(ns):
                        off = k0 + s + top // d.a
                        operands.append(plane)
                        in_specs.append(pl.BlockSpec(
                            (d.a, plane.shape[1]),
                            lambda g, _o=off: (g + _o, 0)))
        out_shape = [jax.ShapeDtypeStruct((grid * block, w),
                                          _io_dtype(dts[u][k]))
                     for u, k, _idx, w in out_layout]
        out_specs = [pl.BlockSpec((block, w), lambda g: (g, 0))
                     for _u, _k, _idx, w in out_layout]
        # the kernel is 32-bit throughout: trace it with x64 off, so index
        # arithmetic inside jnp helpers (gathers, repeats) stays int32
        with jax.enable_x64(False):
            outs = pl.pallas_call(
                kernel, grid=(grid,), in_specs=in_specs,
                out_specs=out_specs, out_shape=out_shape,
                interpret=interpret_default(), name=name)(*operands)

        # regroup planes into per-out values on the engine's carriers
        it = iter(outs)
        result = []
        for o in out_nodes:
            legs = []
            for t in _elems(o.ty):
                shape = type_shape(t)
                ps = [next(it)[:shape[0]] for _ in np.ndindex(shape[2:])]
                x = (jnp.stack(ps, axis=-1).reshape(shape) if shape[2:]
                     else ps[0])
                dt = _carrier_dtype(t)
                legs.append(x != 0 if dt == jnp.bool_ else x.astype(dt))
            result.append(tuple(legs) if isinstance(o.ty, TupleT)
                          else legs[0])
        return tuple(result)

    ops = [n.op for n in node_list]
    note = (f"{name}: fused {len(node_list)} nodes "
            f"({ops[0]}..{ops[-1]}) into one Pallas row-stream "
            f"(grid={grid} blocks x {block} rows)")
    return Megakernel(name, apply, len(nodes) - len(consts), len(in_uids),
                      block, grid, linebuf, float_nodes, len(winsum), note,
                      flops=flops, io_bytes=io_bytes)


def _winsum_geometry(stn: IRNode):
    p = stn.params
    l, r, b, t = p["l"], p["r"], p["b"], p["t"]
    return l, b, abs(t - b) + 1, abs(r - l) + 1      # (l, b, sh, sw)


def _window_winsum(stn: IRNode, d: Demand, rows):
    """Stencil->Reduce(Add) chain on a row window: out[j, x] sums input
    rows j+b..j+b+sh-1, cols x+l..x+l+sw-1 (zero outside the frame) — a
    row sum of the halo window, then a lane sum of shifted copies."""
    l, b, sh, sw = _winsum_geometry(stn)

    def box(x):
        v = x[0:d.size]
        for dy in range(1, sh):
            v = v + x[dy:dy + d.size]
        out = shift_lanes(v, l)
        for dx in range(1, sw):
            out = out + shift_lanes(v, l + dx)
        return out

    return _pmap(box, rows(stn.inputs[0], d.c + b, d.size + sh - 1))


def _fold(fn, flat: np.ndarray) -> np.ndarray:
    """Left fold of ``fn`` over the last axis of a planes array."""
    acc = flat[..., 0]
    for k in range(1, flat.shape[-1]):
        acc = _pmap(fn, acc, flat[..., k])
    return _wrap(acc)


def _argmin(flat: np.ndarray) -> np.ndarray:
    """First-minimum index over the last axis (np.argmin's tie rule)."""
    out = _obj(flat.shape[:-1])
    for idx in np.ndindex(out.shape):
        best = flat[idx + (0,)]
        arg = jnp.zeros(best.shape, jnp.int32)
        for k in range(1, flat.shape[-1]):
            take = flat[idx + (k,)] < best
            best = jnp.where(take, flat[idx + (k,)], best)
            arg = jnp.where(take, jnp.int32(k), arg)
        out[idx] = arg
    return out


def _window_node(n: IRNode, d: Demand, rows, val, inside, grid: int):
    """Compute node ``n``'s window (demand ``d``) as planes, from its
    inputs' windows (``rows``) and constant operands (``val``)."""
    p, s, c = n.params, d.size, d.c
    if n.op == "Map":
        plans = map_reshape_plans(n.ty, n.input_tys)
        args = []
        for j, (u, plan) in enumerate(zip(n.inputs, plans)):
            if _map_streams_input(n, j):
                x = rows(u, c, s)
                args.append(x if plan is None else x.reshape(plan[2:]))
            else:                       # broadcast constant operand
                x = val[u]
                args.append(x if plan is None else x.reshape(plan))
        return _pmap(mk_point_fn(p["fn"]), *args)
    if n.op == "Reduce":
        x = rows(n.inputs[0], c, s)
        return _fold(mk_point_fn(p["fn"]), x.reshape(x.shape[:-2] + (-1,)))
    if n.op == "ReducePatch":
        x = rows(n.inputs[0], c, s)
        flat = x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])
        return _fold(mk_point_fn(p["fn"]), np.moveaxis(flat, 0, -1))
    if n.op == "ArgMin":
        if _is_float(scalar_of(n.input_tys[0])):
            raise MKUnsupported(f"%{n.uid}: float ArgMin")
        x = rows(n.inputs[0], c, s)
        return _argmin(x.reshape(x.shape[:-2] + (-1,)))
    if n.op == "Replicate":
        x = rows(n.inputs[0], c, s)
        return np.broadcast_to(x[..., None, None], x.shape + (p["m"], p["n"]))
    if n.op == "Stack":
        ins = [rows(u, c, s) for u in n.inputs]
        return np.stack(ins, axis=-1)[..., None, :]
    if n.op == "Concat":
        return tuple(rows(u, c, s) for u in n.inputs)
    if n.op == "FanOut":
        x = rows(n.inputs[0], c, s)
        return tuple(x for _ in range(p["n"]))
    if n.op == "FanIn":
        return rows(n.inputs[0], c, s)
    if n.op == "TupleIndex":
        return rows(n.inputs[0], c, s)[p["i"]]
    if n.op == "Stencil":
        l, r, b, t = p["l"], p["r"], p["b"], p["t"]
        sw, sh = abs(r - l) + 1, abs(t - b) + 1
        x = rows(n.inputs[0], c + b, s + sh - 1)
        out = _obj((sh, sw) + x.shape)
        for dy in range(sh):
            for dx in range(sw):
                for idx in np.ndindex(x.shape):
                    out[(dy, dx) + idx] = shift_lanes(x[idx][dy:dy + s],
                                                      l + dx)
        return out
    if n.op == "Pad":
        l, rr, t = p["l"], p["r"], p["t"]
        h_in = type_shape(n.input_tys[0])[0]
        value = p.get("value", 0)
        x = rows(n.inputs[0], c - t, s)
        in_frame = _shift(d, -t).stays_inside(grid, h_in)

        def pad(e):
            e = pad_lanes(e, l, rr, value)
            if in_frame:
                return e
            return jnp.where(inside(d, e.shape, t, t + h_in), e,
                             jnp.full((), value, e.dtype))
        return _pmap(pad, x)
    if n.op == "Crop":
        x = rows(n.inputs[0], c + p["t"], s)
        return _pmap(lambda e: e[:, p["l"]:e.shape[1] - p["r"]], x)
    if n.op == "Downsample":
        sy, sx = p["sy"], p["sx"]
        x = rows(n.inputs[0], c * sy, sy * (s - 1) + 1)
        return _pmap(lambda e: decimate_lanes(
            pick_rows(e, [j * sy for j in range(s)]), sx), x)
    if n.op == "Upsample":
        sy, sx = p["sy"], p["sx"]
        base = c // sy
        x = rows(n.inputs[0], base, (c + s - 1) // sy - base + 1)
        src = [(c + j) // sy - base for j in range(s)]
        return _pmap(lambda e: jnp.repeat(pick_rows(e, src), sx, axis=1), x)
    raise MKUnsupported(f"no window lowering for {n.op}")
