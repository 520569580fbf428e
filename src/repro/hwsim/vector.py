"""Vectorized cycle engine: the scalar simulator's update rule as array ops.

``sim.CycleSim`` steps every module with Python-level bookkeeping — exact,
but ~50us/cycle, which makes a 1080p frame (~2M cycles) a two-minute run.
This module packs the whole simulation state into flat integer vectors —
per-edge occupancy/consumed counters, per-module launch/push/credit
counters, a ring-buffer launch history for latency maturation, and one
concatenated per-edge need lookup table — and advances ALL modules and
edges each cycle with a fixed sequence of array operations.

The per-cycle recurrence is a faithful transcription of the scalar engine's
two phases (see the equivalence notes inline); both engines produce
bit-identical per-FIFO high-water marks, stamps, and cycle counts, which
the tests and the ``hwsim-smoke`` CI job cross-check on the paper's four
apps.

Two backends execute the recurrence:

  - **jit** (default when jax is importable): the cycle loop is a
    ``lax.while_loop`` compiled by XLA:CPU, run in per-frame segments so
    frame-end cycles are recorded host-side between segments. All tensors
    are passed as dynamic jit arguments, so every simulation of a
    same-shaped netlist (re-simulations in the allocator, repeated tests)
    hits the same compiled program.
  - **numpy**: the same step as per-cycle numpy ops — slow, but dependency-
    free and the debugging reference for the jit path.

Key equivalence facts the packing relies on (all hold in the scalar
engine):

  - each edge has exactly one producer and one consumer, and phase A
    (pushes) completes before phase B (pops + launches), so neither phase
    has intra-phase ordering effects — module order inside a phase cannot
    matter, which is what makes a data-parallel update exact;
  - a module pushes at most one matured token per cycle, so the inflight
    deque can be replaced by counts: a token is pushable at cycle t iff
    ``pushed < launched_as_of(t - max(L, 1))`` (the max accounts for phase
    ordering: a latency-0 launch in phase B is first visible to phase A on
    the following cycle);
  - an edge's ``popped`` equals its ``consumed`` counter and its ``pushed``
    equals its producer's push count, so neither needs separate state.
"""
from __future__ import annotations

import math
from fractions import Fraction
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from ..core.buffers import Edge
from ..core.rigel import RModule
from .occupancy import EdgeOccupancy, OccupancyTrace
from .sim import PROFILED, EdgeKey, NeedSpec, SimResult, need_spec

_INF = np.int64(2 ** 62)

# stop codes the kernel reports back to the host-side segment loop
_RUNNING, _PAUSE, _DONE, _HORIZON, _STALL = 0, 1, 2, 3, 4


def _has_jax() -> bool:
    try:
        import jax  # noqa: F401
        return True
    except Exception:  # pragma: no cover - jax is a baked-in dependency
        return False


class VectorSim:
    """Packed-state cycle simulation over a mapped module netlist.

    Construction mirrors ``sim.build_sim``: ``depths`` maps (src, dst) to
    FIFO depths (capacity = depth + 1), ``unbounded`` lifts all caps, and
    ``frames`` runs back-to-back frames with per-frame need offsets.
    """

    def __init__(self, modules: Sequence[RModule], edges: Sequence[Edge],
                 depths: Mapping[EdgeKey, int], unbounded: bool = False,
                 frames: int = 1):
        if frames < 1:
            raise ValueError("frames must be >= 1")
        self.frames = frames
        self.keys = [(e.src, e.dst) for e in edges]
        self.token_bits = [e.token_bits for e in edges]
        M, E = len(modules), len(edges)
        self.M, self.E = M, E

        i64 = np.int64
        self.src = np.array([e.src for e in edges], i64)
        self.dst = np.array([e.dst for e in edges], i64)
        self.cap = np.array(
            [_INF if unbounded else int(depths.get((e.src, e.dst), 0)) + 1
             for e in edges], i64)
        self.unbounded = unbounded

        rates = [Fraction(m.rate) if m.rate > 0 else Fraction(1)
                 for m in modules]
        self.rnum = np.array([r.numerator for r in rates], i64)
        self.rden = np.array([r.denominator for r in rates], i64)
        self.throt = np.array(
            [m.kind not in PROFILED and 0 < rates[i] < 1
             for i, m in enumerate(modules)], bool)
        self.latency = np.array([m.latency for m in modules], i64)
        self.leff = np.maximum(self.latency, 1)

        has_in = np.zeros(M, bool)
        has_out = np.zeros(M, bool)
        has_in[self.dst] = True
        has_out[self.src] = True
        self.has_out = has_out
        active = has_in | has_out
        self.active = active
        self.is_sink = active & has_in & ~has_out
        # inactive modules (Const register banks) never step: zero their
        # token budget so they are born "done"
        out_frame = np.array([m.iface_out.sched.tokens_per_frame
                              for m in modules], i64)
        self.out_frame = np.where(active, out_frame, 0)
        self.tot = self.out_frame * frames

        self.names = [m.name for m in modules]
        sink_idx = np.flatnonzero(self.is_sink)
        self.sink0 = int(sink_idx[0]) if len(sink_idx) else -1
        self.frame_tokens = (int(self.out_frame[self.sink0])
                             if self.sink0 >= 0 else 0)

        # adjacency for the two segment reductions: blocked (any full
        # out-edge) and unmet (any in-edge short of its need)
        self.out_adj = np.zeros((M, E), i64)
        self.in_adj = np.zeros((M, E), i64)
        self.out_adj[self.src, np.arange(E)] = 1
        self.in_adj[self.dst, np.arange(E)] = 1

        # per-edge need lookup: one concatenated within-frame table, offsets
        # per edge; multi-frame needs are offset arithmetically in-kernel
        self.specs: List[NeedSpec] = [
            need_spec(modules[e.dst], modules[e.src],
                      int(out_frame[e.src])) for e in edges]
        tables = [s.need_array() for s in self.specs]
        self.need_off = np.zeros(E, i64)
        if tables:
            lens = np.array([len(t) for t in tables], i64)
            self.need_off[1:] = np.cumsum(lens)[:-1]
            self.need_buf = np.concatenate(tables).astype(i64)
        else:
            self.need_buf = np.zeros(1, i64)
        self.tpf = np.array([s.tpf for s in self.specs], i64) \
            if E else np.zeros(0, i64)
        self.ot = np.array([s.out_total for s in self.specs], i64) \
            if E else np.zeros(0, i64)

        # history ring: row t % H holds the cumulative launch counts as of
        # the end of cycle t; matured(t) = row (t - leff) % H
        self.H = int(self.leff.max()) + 2 if M else 2

    # -- scalar-engine formulas, verbatim ------------------------------
    def _stall_limit(self) -> int:
        act = self.active
        if not act.any():
            return 65
        gaps = -(-self.rden[act] // np.maximum(1, self.rnum[act]))
        return int(self.latency[act].max()) + int(gaps.max()) + 64

    def _default_horizon(self) -> int:
        est = 0
        for m in np.flatnonzero(self.active):
            rate = Fraction(int(self.rnum[m]), int(self.rden[m]))
            est = max(est, int(self.latency[m])
                      + math.ceil(int(self.tot[m]) / rate))
        return 8 * est + 16 * self._stall_limit()

    # -- state ----------------------------------------------------------
    def _initial_state(self):
        i64 = np.int64
        return dict(
            t=i64(0), last_progress=i64(0),
            occ=np.zeros(self.E, i64), consumed=np.zeros(self.E, i64),
            kf=np.ones(self.E, i64), fr=np.zeros(self.E, i64),
            launched=np.zeros(self.M, i64), pushed=np.zeros(self.M, i64),
            credit=np.zeros(self.M, i64),
            hist=np.zeros((self.H, self.M), i64),
            hwm=np.zeros(self.E, i64), hwm_cycle=np.zeros(self.E, i64),
            pflag=i64(1), skipped=i64(0), saved=i64(0),
        )

    # -- one cycle, numpy (the jit body is a transcription of this) -----
    def _step_numpy(self, s: dict) -> bool:
        """Advance one cycle in place; returns True if any token moved."""
        t = s["t"]
        # --- phase A: matured tokens push downstream ---
        full = s["occ"] >= self.cap
        blocked = (self.out_adj @ full.astype(np.int64)) > 0
        matured = s["hist"][(t - self.leff) % self.H, np.arange(self.M)]
        can_push = (s["pushed"] < matured) & ~blocked & self.has_out
        s["pushed"] = s["pushed"] + can_push
        s["occ"] = s["occ"] + can_push[self.src]
        new_hwm = s["occ"] > s["hwm"]
        s["hwm_cycle"] = np.where(new_hwm, t, s["hwm_cycle"])
        s["hwm"] = np.maximum(s["hwm"], s["occ"])
        # --- phase B: consume toward the next output, then launch ---
        done_m = s["launched"] >= self.tot
        done_dst = s["fr"] >= self.frames
        need = s["fr"] * self.tpf \
            + self.need_buf[self.need_off + s["kf"] - 1]
        pop = ~done_dst & (s["consumed"] < need) & (s["occ"] > 0)
        s["occ"] = s["occ"] - pop
        s["consumed"] = s["consumed"] + pop
        unmet = (s["consumed"] < need) & ~done_dst
        ready = (self.in_adj @ unmet.astype(np.int64)) == 0
        c = s["credit"] + self.rnum
        launch = ready & ~done_m & self.active \
            & (~self.throt | (c >= self.rden))
        s["credit"] = np.where(
            self.throt,
            np.where(launch, c - self.rden, np.minimum(c, self.rden)),
            s["credit"])
        s["launched"] = s["launched"] + launch
        s["pushed"] = s["pushed"] + (launch & self.is_sink)  # sinks absorb
        launch_e = launch[self.dst]
        wrap = launch_e & (s["kf"] == self.ot)
        s["kf"] = np.where(wrap, 1, s["kf"] + launch_e)
        s["fr"] = s["fr"] + wrap
        s["hist"][t % self.H] = s["launched"]
        s["t"] = t + 1
        return bool(can_push.any() or pop.any() or launch.any())

    # -- event-jump batching -------------------------------------------
    # During a stall plateau (a cycle with no token movement) the only
    # state that evolves is the cycle counter, the launch-history ring
    # (rewriting unchanged counts), and the throttle credit buckets
    # (min(credit + rnum, rden) per cycle).  Every enabling condition —
    # blocked, ready, pop eligibility — is therefore static until one of
    # exactly two event kinds fires:
    #
    #   * maturation: a non-blocked producer with pushed < launched becomes
    #     pushable at the first future cycle x where the ring row
    #     (x - leff) % H exceeds its push count.  Guaranteed within
    #     leff - 1 cycles: cycle t-1's row holds `launched` > pushed.
    #   * credit refill: a ready throttled module launches once its bucket
    #     reaches rden; credit after d no-op cycles is the closed form
    #     min(credit + d*rnum, rden), so the launch lands at
    #     d = max(0, ceil((rden - credit) / rnum) - 1).
    #
    # Jumping to the earliest such event (clamped to the stall-detect and
    # horizon boundaries so reported cycle counts stay bit-identical) and
    # backfilling the skipped ring rows reproduces per-cycle execution
    # exactly — verified by the engines-equal signature gate.
    def _next_event_numpy(self, s: dict) -> int:
        t = int(s["t"])
        te = int(_INF)
        full = s["occ"] >= self.cap
        blocked = (self.out_adj @ full.astype(np.int64)) > 0
        cand = self.active & self.has_out & ~blocked \
            & (s["pushed"] < s["launched"])
        for j in np.flatnonzero(cand):
            leff_j = int(self.leff[j])
            pj = int(s["pushed"][j])
            for d in range(leff_j):
                if int(s["hist"][(t + d - leff_j) % self.H, j]) > pj:
                    te = min(te, t + d)
                    break
        need = s["fr"] * self.tpf \
            + self.need_buf[self.need_off + s["kf"] - 1]
        done_dst = s["fr"] >= self.frames
        unmet = (s["consumed"] < need) & ~done_dst
        ready = (self.in_adj @ unmet.astype(np.int64)) == 0
        done_m = s["launched"] >= self.tot
        cred = self.throt & ready & ~done_m & self.active
        for j in np.flatnonzero(cred):
            gap = int(self.rden[j]) - int(s["credit"][j])
            d = max(0, -(-gap // int(self.rnum[j])) - 1)
            te = min(te, t + d)
        return te

    def _jump_numpy(self, s: dict, horizon: int, stall_limit: int) -> None:
        t = int(s["t"])
        ev = self._next_event_numpy(s)
        te = min(ev, int(s["last_progress"]) + stall_limit + 1, horizon)
        te = max(te, t)
        dt = te - t
        if dt == 0:
            return
        if ev > te:
            # no future event before the clamp: a provably dead state —
            # these skipped cycles are the deadlock early-abort's win
            s["saved"] = np.int64(int(s["saved"]) + dt)
        # ring slot r's most recent cycle <= te-1; rows belonging to the
        # skipped cycles [t, te-1] are rewritten with the frozen counts
        r = np.arange(self.H)
        x_r = (te - 1) - ((te - 1 - r) % self.H)
        s["hist"][x_r >= t] = s["launched"]
        s["credit"] = np.where(
            self.throt,
            np.minimum(s["credit"] + dt * self.rnum, self.rden),
            s["credit"])
        s["t"] = np.int64(te)
        s["skipped"] = np.int64(int(s["skipped"]) + dt)

    def _run_numpy(self, horizon: int, stall_limit: int,
                   event_jump: bool = True
                   ) -> Tuple[dict, List[int], Optional[int]]:
        s = self._initial_state()
        frame_ends: List[int] = []
        code: Optional[int] = None
        while True:
            done = bool((s["launched"] >= self.tot)[self.is_sink].all())
            if done:
                break
            if s["t"] >= horizon:
                code = _HORIZON
                break
            if s["t"] - s["last_progress"] > stall_limit:
                code = _STALL
                break
            if self._step_numpy(s):
                s["last_progress"] = s["t"] - 1
            elif event_jump:
                # skipped cycles have no movement, so the frame-boundary
                # bookkeeping below cannot be crossed by a jump
                self._jump_numpy(s, horizon, stall_limit)
            if self.sink0 >= 0 and self.frame_tokens:
                while (len(frame_ends) <
                       s["launched"][self.sink0] // self.frame_tokens):
                    frame_ends.append(int(s["t"]) - 1)
        return s, frame_ends, code

    # -- jit path -------------------------------------------------------
    def _consts(self):
        import jax.numpy as jnp
        as_j = jnp.asarray
        return (as_j(self.src), as_j(self.dst), as_j(self.cap),
                as_j(self.rnum), as_j(self.rden), as_j(self.throt),
                as_j(self.leff), as_j(self.has_out), as_j(self.active),
                as_j(self.is_sink), as_j(self.tot), as_j(self.out_adj),
                as_j(self.in_adj), as_j(self.need_buf), as_j(self.need_off),
                as_j(self.tpf), as_j(self.ot))

    def _run_jit(self, horizon: int, stall_limit: int,
                 event_jump: bool = True
                 ) -> Tuple[dict, List[int], Optional[int]]:
        import jax
        with jax.enable_x64(True), jax.default_device(host_device()):
            consts = self._consts()
            s0 = self._initial_state()
            state = tuple(jax.numpy.asarray(s0[k]) for k in _STATE_KEYS)
            frame_ends: List[int] = []
            code: Optional[int] = None
            # one kernel call per frame: the pause on the sink's frame
            # boundary lets the host record frame-end cycles without any
            # in-kernel scatter bookkeeping
            targets = [f * self.frame_tokens
                       for f in range(1, self.frames + 1)] \
                if self.sink0 >= 0 and self.frame_tokens else []
            args = (np.int64(self.frames), np.int64(self.H),
                    np.int64(horizon), np.int64(stall_limit),
                    np.int64(self.sink0), np.int64(1 if event_jump else 0))
            t_i = _STATE_KEYS.index("t")
            launched_i = _STATE_KEYS.index("launched")
            for target in targets:
                state, kcode = _segment(consts, state, np.int64(target),
                                        *args)
                kcode = int(kcode)
                at_target = int(np.asarray(
                    state[launched_i])[self.sink0]) >= target
                # the stop-code priority masks a PAUSE when the horizon
                # lands on the very cycle-end that crossed the frame
                # boundary — the boundary is still real (the scalar engine
                # records it during that last executed cycle), so append
                # it on any stop code once the sink reached the target
                if kcode != _RUNNING and at_target:
                    frame_ends.append(int(state[t_i]) - 1)
                if kcode in (_HORIZON, _STALL):
                    code = kcode
                    break
                if kcode == _DONE:
                    break
            else:
                # multi-sink stragglers (or no sink): run to completion
                state, kcode = _segment(consts, state, _INF, *args)
                kcode = int(kcode)
                if kcode in (_HORIZON, _STALL):
                    code = kcode
            s = {k: np.asarray(v) for k, v in zip(_STATE_KEYS, state)}
            s["t"] = np.int64(s["t"])
            return s, frame_ends, code

    # -- diagnosis (stalled runs) --------------------------------------
    def _diagnose(self, s: dict, cap: Optional[np.ndarray] = None) -> str:
        """``cap`` overrides the per-edge capacities (PopulationSim runs
        many capacity vectors over this one packed netlist)."""
        if cap is None:
            cap = self.cap
        why = []
        need = s["fr"] * self.tpf \
            + self.need_buf[self.need_off + s["kf"] - 1]
        inflight = s["launched"] - s["pushed"]
        for m in range(self.M):
            if not self.active[m]:
                continue
            if s["launched"][m] >= self.tot[m] and inflight[m] <= 0:
                continue
            starved = [self.keys[e] for e in np.flatnonzero(self.dst == m)
                       if s["launched"][m] < self.tot[m]
                       and s["consumed"][e] < need[e] and s["occ"][e] == 0]
            full = [self.keys[e] for e in np.flatnonzero(self.src == m)
                    if inflight[m] > 0 and not self.unbounded
                    and s["occ"][e] >= cap[e]]
            if starved or full:
                why.append(f"{self.names[m]}[{m}]"
                           + (f" starved on {starved}" if starved else "")
                           + (f" blocked on full {full}" if full else ""))
        return "; ".join(why) or "no token movement"

    # -- entry ----------------------------------------------------------
    def run(self, max_cycles: Optional[int] = None,
            jit: Optional[bool] = None,
            event_jump: bool = True) -> SimResult:
        horizon = max_cycles or self._default_horizon()
        stall_limit = self._stall_limit()
        use_jit = _has_jax() if jit is None else jit
        runner = self._run_jit if use_jit else self._run_numpy
        s, frame_ends, code = runner(horizon, stall_limit,
                                     event_jump=event_jump)
        t = int(s["t"])
        deadlock = None
        if code == _HORIZON:
            deadlock = f"horizon exceeded ({horizon} cycles)"
        elif code == _STALL:
            deadlock = self._diagnose(s)
        fe = np.asarray(frame_ends, np.int64)
        # frame stamp of a mark = frames drained at the sink when it was
        # reached (same definition the scalar engine tracks inline)
        hwm_frame = np.searchsorted(fe, s["hwm_cycle"], side="left") \
            if len(fe) else np.zeros(self.E, np.int64)
        pushed_e = s["pushed"][self.src]
        per_edge = [EdgeOccupancy(
            self.keys[e], None if self.unbounded else int(self.cap[e]) - 1,
            int(s["hwm"][e]), int(s["hwm_cycle"][e]), int(pushed_e[e]),
            int(s["consumed"][e]), self.token_bits[e],
            hwm_frame=int(hwm_frame[e])) for e in range(self.E)]
        occ = OccupancyTrace(per_edge, t)
        sink_tokens = int(s["launched"][self.is_sink].sum())
        return SimResult(t, sink_tokens, deadlock, occ, frames=self.frames,
                         frame_ends=[int(x) for x in frame_ends],
                         engine="vector",
                         cycles_skipped=int(s["skipped"]),
                         cycles_saved=int(s["saved"]))


_STATE_KEYS = ("t", "last_progress", "occ", "consumed", "kf", "fr",
               "launched", "pushed", "credit", "hist", "hwm", "hwm_cycle",
               "pflag", "skipped", "saved")


def _segment_impl(consts, state, seg_target, frames, H, horizon,
                  stall_limit, sink0, jump):
    """One while_loop over cycles until frame-target / completion / horizon
    / stall. Everything (including the netlist tensors) is a dynamic jit
    argument, so the compiled program is shared by every simulation whose
    netlist has the same shape — including ``jump`` (the event-jump
    enable flag), which is branched on with ``lax.cond`` at runtime.

    Structure: an inner while_loop steps plain cycles for as long as each
    cycle moves a token (``pflag``); when a no-op cycle is executed the
    inner loop yields and — once per plateau, not per cycle — the jump
    branch computes the next event horizon (see ``_next_event_numpy`` for
    the derivation) and fast-forwards the counter, ring, and credit
    buckets in one step. The outer loop resumes stepping at the event."""
    import jax.numpy as jnp
    from jax import lax

    (src, dst, cap, rnum, rden, throt, leff, has_out, active, is_sink,
     tot, out_adj, in_adj, need_buf, need_off, tpf, ot) = consts
    M = rnum.shape[0]
    E = need_off.shape[0]

    # XLA:CPU's general gather degrades ~60x when the operand is a large
    # (>~64KB) buffer inside a while loop; E and M are small and static, so
    # both per-cycle gathers unroll into scalar dynamic_slices instead
    def pick(arr, idx, n):
        if n == 0:
            return jnp.zeros((0,), arr.dtype)
        return jnp.stack([lax.dynamic_slice(arr, (idx[j],), (1,))[0]
                          for j in range(n)])

    def code_of(state):
        (t, last_progress, occ, consumed, kf, fr, launched, pushed,
         credit, hist, hwm, hwm_cycle, pflag, skipped, saved) = state
        done = jnp.all(jnp.where(is_sink, launched >= tot, True))
        at_target = jnp.where(
            sink0 >= 0, launched[jnp.maximum(sink0, 0)] >= seg_target, False)
        code = jnp.where(at_target, _PAUSE, _RUNNING)
        code = jnp.where(t - last_progress > stall_limit, _STALL, code)
        code = jnp.where(t >= horizon, _HORIZON, code)
        code = jnp.where(done, _DONE, code)
        return code

    def body(state):
        (t, last_progress, occ, consumed, kf, fr, launched, pushed,
         credit, hist, hwm, hwm_cycle, pflag, skipped, saved) = state
        # phase A (order matters: mirrors the scalar engine exactly)
        full = occ >= cap
        blocked = (out_adj @ full.astype(jnp.int64)) > 0
        # per-module scalar dynamic_slices (NOT a gather/reshape: both
        # degrade ~70x on a large carried ring at 1080p)
        matured = jnp.stack(
            [lax.dynamic_slice(hist, ((t - leff[j]) % H, j), (1, 1))[0, 0]
             for j in range(M)]) if M else jnp.zeros((0,), hist.dtype)
        can_push = (pushed < matured) & ~blocked & has_out
        pushed = pushed + can_push
        occ = occ + can_push[src]
        new_hwm = occ > hwm
        hwm_cycle = jnp.where(new_hwm, t, hwm_cycle)
        hwm = jnp.maximum(hwm, occ)
        # phase B
        done_m = launched >= tot
        done_dst = fr >= frames
        need = fr * tpf + pick(need_buf, need_off + kf - 1, E)
        pop = ~done_dst & (consumed < need) & (occ > 0)
        occ = occ - pop
        consumed = consumed + pop
        unmet = (consumed < need) & ~done_dst
        ready = (in_adj @ unmet.astype(jnp.int64)) == 0
        c = credit + rnum
        launch = ready & ~done_m & active & (~throt | (c >= rden))
        credit = jnp.where(
            throt, jnp.where(launch, c - rden, jnp.minimum(c, rden)), credit)
        launched = launched + launch
        pushed = pushed + (launch & is_sink)
        launch_e = launch[dst]
        wrap = launch_e & (kf == ot)
        kf = jnp.where(wrap, 1, kf + launch_e)
        fr = fr + wrap
        hist = lax.dynamic_update_slice(hist, launched[None, :], (t % H, 0))
        progress = jnp.any(can_push) | jnp.any(pop) | jnp.any(launch)
        last_progress = jnp.where(progress, t, last_progress)
        return (t + 1, last_progress, occ, consumed, kf, fr, launched,
                pushed, credit, hist, hwm, hwm_cycle,
                progress.astype(jnp.int64), skipped, saved)

    def jump_fn(state):
        # transcription of VectorSim._next_event_numpy + _jump_numpy: the
        # last executed cycle was a no-op, so every enabling condition is
        # frozen until a maturation or credit-refill event
        (t, last_progress, occ, consumed, kf, fr, launched, pushed,
         credit, hist, hwm, hwm_cycle, pflag, skipped, saved) = state
        full = occ >= cap
        blocked = (out_adj @ full.astype(jnp.int64)) > 0
        cand = active & has_out & ~blocked & (pushed < launched)
        Hs = hist.shape[0]  # static twin of the traced H argument
        if M:
            d_ar = jnp.arange(Hs, dtype=jnp.int64)
            rows = (t + d_ar[:, None] - leff[None, :]) % H        # (H, M)
            vals = jnp.take_along_axis(hist, rows, axis=0)
            hit = (d_ar[:, None] < leff[None, :]) \
                & (vals > pushed[None, :]) & cand[None, :]
            d_first = jnp.argmax(hit, axis=0)                     # first True
            te_mat = jnp.where(jnp.any(hit, axis=0), t + d_first, _INF)
        else:
            te_mat = jnp.full((0,), _INF)
        need = fr * tpf + pick(need_buf, need_off + kf - 1, E)
        done_dst = fr >= frames
        unmet = (consumed < need) & ~done_dst
        ready = (in_adj @ unmet.astype(jnp.int64)) == 0
        done_m = launched >= tot
        cred = throt & ready & ~done_m & active
        gap = rden - credit
        d_cred = jnp.maximum(0, -((-gap) // jnp.maximum(rnum, 1)) - 1)
        te_cred = jnp.where(cred, t + d_cred, _INF)
        ev = jnp.minimum(jnp.min(te_mat, initial=_INF),
                         jnp.min(te_cred, initial=_INF))
        te = jnp.minimum(jnp.minimum(ev, last_progress + stall_limit + 1),
                         horizon)
        te = jnp.maximum(te, t)
        dt = te - t
        # no event before the clamp => provably dead state: the skipped
        # cycles are the deadlock early-abort's win, reported separately
        saved = saved + jnp.where(ev > te, dt, 0)
        r = jnp.arange(Hs, dtype=jnp.int64)
        x_r = (te - 1) - ((te - 1 - r) % H)
        hist = jnp.where((x_r >= t)[:, None], launched[None, :], hist)
        credit = jnp.where(
            throt, jnp.minimum(credit + dt * rnum, rden), credit)
        return (te, last_progress, occ, consumed, kf, fr, launched,
                pushed, credit, hist, hwm, hwm_cycle,
                jnp.int64(1), skipped + dt, saved)

    def resume_fn(state):
        # jump disabled: just rearm the inner loop to step the next cycle
        return state[:12] + (jnp.int64(1), state[13], state[14])

    def stepping(state):
        return state[12] == 1

    def outer(state):
        state = lax.while_loop(
            lambda st: (code_of(st) == _RUNNING) & stepping(st), body, state)
        return lax.cond(
            code_of(state) == _RUNNING,
            lambda st: lax.cond(jump != 0, jump_fn, resume_fn, st),
            lambda st: st, state)

    out = lax.while_loop(lambda st: code_of(st) == _RUNNING, outer, state)
    return out, code_of(out)


# AOT-compiled kernels keyed by the flattened arg signature (shapes+dtypes):
# every simulation of a same-shaped netlist shares one executable.  The
# cycle simulator is a host tool: its operands are committed to the CPU
# device, so the int64 while-loop compiles and runs there even in a
# process whose default device is an accelerator.
_SEG_CACHE: Dict[Tuple, object] = {}


def host_device():
    """The device every cycle-simulator kernel compiles and runs on."""
    import jax
    return jax.devices("cpu")[0]


def _segment(consts, state, seg_target, frames, H, horizon, stall_limit,
             sink0, jump):
    import jax

    args = jax.device_put((consts, state, seg_target, frames, H, horizon,
                           stall_limit, sink0, jump), host_device())
    flat, _ = jax.tree_util.tree_flatten(args)
    key = tuple((np.shape(x), str(x.dtype)) for x in flat)
    compiled = _SEG_CACHE.get(key)
    if compiled is None:
        compiled = jax.jit(_segment_impl).lower(*args).compile()
        _SEG_CACHE[key] = compiled
    return compiled(*args)
