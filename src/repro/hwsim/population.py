"""Design-population batching: many FIFO capacity vectors, one kernel.

The design-space explorer evaluates dozens of FIFO-depth variants of the
same mapped netlist.  Each variant changes only the per-edge capacity
vector — the module graph, rates, latencies, and need tables are shared —
so the packed-state recurrence of ``vector.VectorSim`` can be batched over
a population axis K: one XLA ``while_loop`` advances every candidate
design each cycle, with per-design stop codes and a masked state merge so
finished designs freeze while the rest keep streaming.

Layout choices that keep XLA:CPU fast (same ~64KB-gather cliff the
single-design kernel dodges):

  - the cycle counter is **global**: finished designs stop updating state
    but time marches on for everyone, so the launch-history ring can be
    laid out ``(H, K, M)`` and both the per-cycle row write and the
    per-module maturation reads stay ``dynamic_slice``s instead of
    scatters/gathers;
  - the per-edge need lookup is pre-sliced into one small table per edge,
    so the per-cycle batched lookup is E gathers over tiny operands;
  - event-jump batching goes global too: when *every* still-running
    design sits in a no-op plateau, the kernel jumps to the earliest next
    event across the population (computed per design exactly as in
    ``VectorSim._next_event_numpy``, clamped per design to its own
    stall/horizon boundary).

Results are bit-identical to running each capacity vector through
``VectorSim`` serially — same ``edge_signature``, cycle counts, frame
ends, and deadlock codes — which ``tests/test_explore.py`` verifies.
"""
from __future__ import annotations

from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from ..core.buffers import Edge
from ..core.rigel import RModule
from .occupancy import EdgeOccupancy, OccupancyTrace
from .sim import EdgeKey, SimResult
from .vector import (_DONE, _HORIZON, _INF, _RUNNING, _STALL, VectorSim,
                     _has_jax, host_device)

_POP_STATE_KEYS = ("t", "last_progress", "occ", "consumed", "kf", "fr",
                   "launched", "pushed", "credit", "hist", "hwm",
                   "hwm_cycle", "pflag", "skipped", "code_rec",
                   "cycles_rec", "fe", "nfe")


class PopulationSim:
    """Batched cycle simulation of K capacity vectors over one netlist.

    ``depth_sets`` is a sequence of per-edge depth mappings (missing keys
    default to depth 0, capacity 1, exactly like ``VectorSim``); all other
    netlist structure is shared.  ``run()`` returns one ``SimResult`` per
    depth set, in order.
    """

    def __init__(self, modules: Sequence[RModule], edges: Sequence[Edge],
                 depth_sets: Sequence[Mapping[EdgeKey, int]],
                 frames: int = 1):
        if not depth_sets:
            raise ValueError("depth_sets must be non-empty")
        self.base = VectorSim(modules, edges, depth_sets[0], frames=frames)
        self.K = len(depth_sets)
        self.frames = frames
        b = self.base
        self.caps = np.array(
            [[int(ds.get(k, 0)) + 1 for k in b.keys] for ds in depth_sets],
            np.int64)

    # -- serial reference (and the no-jax fallback) ---------------------
    def _run_serial(self, max_cycles: Optional[int], jit: bool,
                    event_jump: bool) -> List[SimResult]:
        b = self.base
        out = []
        for k in range(self.K):
            depths = {key: int(self.caps[k, e]) - 1
                      for e, key in enumerate(b.keys)}
            r = _rebuilt(b, depths, self.frames).run(
                max_cycles=max_cycles, jit=jit, event_jump=event_jump)
            r.engine = "population-serial"
            out.append(r)
        return out

    # -- entry ----------------------------------------------------------
    def run(self, max_cycles: Optional[int] = None,
            jit: Optional[bool] = None,
            event_jump: bool = True) -> List[SimResult]:
        use_jit = _has_jax() if jit is None else jit
        if not use_jit:
            return self._run_serial(max_cycles, False, event_jump)
        b = self.base
        horizon = max_cycles or b._default_horizon()
        stall_limit = b._stall_limit()
        state = self._run_batched(horizon, stall_limit, event_jump)
        return [self._result(state, k, horizon) for k in range(self.K)]

    def _run_batched(self, horizon: int, stall_limit: int,
                     event_jump: bool) -> Dict[str, np.ndarray]:
        import jax

        b, K = self.base, self.K
        with jax.enable_x64(True), jax.default_device(host_device()):
            i64 = np.int64
            as_j = jax.numpy.asarray
            # per-edge need tables pre-sliced so the batched per-cycle
            # lookup gathers from one small operand per edge
            tables = tuple(
                as_j(b.need_buf[int(b.need_off[e]):
                                int(b.need_off[e]) + max(int(b.ot[e]), 1)])
                for e in range(b.E))
            consts = (b._consts() + (as_j(self.caps),), tables)
            s0 = dict(
                t=i64(0), last_progress=np.zeros(K, i64),
                occ=np.zeros((K, b.E), i64), consumed=np.zeros((K, b.E), i64),
                kf=np.ones((K, b.E), i64), fr=np.zeros((K, b.E), i64),
                launched=np.zeros((K, b.M), i64),
                pushed=np.zeros((K, b.M), i64),
                credit=np.zeros((K, b.M), i64),
                hist=np.zeros((b.H, K, b.M), i64),
                hwm=np.zeros((K, b.E), i64), hwm_cycle=np.zeros((K, b.E), i64),
                pflag=np.ones(K, i64), skipped=np.zeros(K, i64),
                code_rec=np.full(K, _RUNNING, i64),
                cycles_rec=np.full(K, -1, i64),
                fe=np.full((K, max(self.frames, 1)), -1, i64),
                nfe=np.zeros(K, i64),
            )
            state = tuple(as_j(s0[k]) for k in _POP_STATE_KEYS)
            args = (i64(self.frames), i64(b.H), i64(horizon),
                    i64(stall_limit), i64(b.sink0), i64(b.frame_tokens),
                    i64(1 if event_jump else 0))
            out = _pop_kernel(consts, state, *args)
            return {k: np.asarray(v)
                    for k, v in zip(_POP_STATE_KEYS, out)}

    def _result(self, s: Dict[str, np.ndarray], k: int,
                horizon: int) -> SimResult:
        b = self.base
        code = int(s["code_rec"][k])
        cycles = int(s["cycles_rec"][k])
        deadlock = None
        if code == _HORIZON:
            deadlock = f"horizon exceeded ({horizon} cycles)"
        elif code == _STALL:
            view = {key: s[key][k] for key in
                    ("occ", "consumed", "kf", "fr", "launched", "pushed")}
            deadlock = b._diagnose(view, cap=self.caps[k])
        nfe = int(s["nfe"][k])
        fe = s["fe"][k, :nfe].astype(np.int64)
        hwm_frame = np.searchsorted(fe, s["hwm_cycle"][k], side="left") \
            if nfe else np.zeros(b.E, np.int64)
        pushed_e = s["pushed"][k][b.src]
        per_edge = [EdgeOccupancy(
            b.keys[e], int(self.caps[k, e]) - 1,
            int(s["hwm"][k, e]), int(s["hwm_cycle"][k, e]),
            int(pushed_e[e]), int(s["consumed"][k, e]), b.token_bits[e],
            hwm_frame=int(hwm_frame[e])) for e in range(b.E)]
        occ = OccupancyTrace(per_edge, cycles)
        sink_tokens = int(s["launched"][k][b.is_sink].sum())
        return SimResult(cycles, sink_tokens, deadlock, occ,
                         frames=self.frames,
                         frame_ends=[int(x) for x in fe],
                         engine="population",
                         cycles_skipped=int(s["skipped"][k]))


def _rebuilt(base: VectorSim, depths: Mapping[EdgeKey, int],
             frames: int) -> VectorSim:
    """A VectorSim sharing ``base``'s packed netlist with new capacities
    (avoids re-deriving need tables per serial-fallback design)."""
    import copy
    vs = copy.copy(base)
    vs.cap = np.array([int(depths.get(key, 0)) + 1 for key in base.keys],
                      np.int64)
    vs.frames = frames
    return vs


def _pop_impl(consts, state, frames, H, horizon, stall_limit, sink0,
              frame_tokens, jump):
    """One while_loop advancing all K designs until every per-design stop
    code is set.  Mirrors ``vector._segment_impl`` with a leading
    population axis on all per-design state, a global cycle counter, and
    in-kernel frame-end recording (no host-side segmentation)."""
    import jax.numpy as jnp
    from jax import lax

    base_consts, tables = consts
    (src, dst, _cap, rnum, rden, throt, leff, has_out, active, is_sink,
     tot, out_adj, in_adj, _need_buf, need_off, tpf, ot, caps) = base_consts
    M = rnum.shape[0]
    E = need_off.shape[0]
    K = caps.shape[0]
    # static twin of the traced H argument, from the ring's shape
    Hs = state[_POP_STATE_KEYS.index("hist")].shape[0]

    def unpack(state):
        return dict(zip(_POP_STATE_KEYS, state))

    def pack(d):
        return tuple(d[k] for k in _POP_STATE_KEYS)

    def need_of(kf, fr):
        # (K, E): per-edge gather over its own small pre-sliced table
        if E == 0:
            return jnp.zeros((K, 0), jnp.int64)
        cols = [jnp.take(tables[e], kf[:, e] - 1, mode="clip")
                for e in range(E)]
        return fr * tpf[None, :] + jnp.stack(cols, axis=1)

    def code_now(d):
        done = jnp.all(jnp.where(is_sink[None, :],
                                 d["launched"] >= tot[None, :], True), axis=1)
        code = jnp.where(d["t"] - d["last_progress"] > stall_limit,
                         _STALL, _RUNNING)
        code = jnp.where(d["t"] >= horizon, _HORIZON, code)
        code = jnp.where(done, _DONE, code)
        return code

    def step(d):
        """One batched cycle at global time t for every design; caller
        masks the merge so stopped designs stay frozen."""
        t = d["t"]
        occ, consumed = d["occ"], d["consumed"]
        kf, fr = d["kf"], d["fr"]
        launched, pushed, credit = d["launched"], d["pushed"], d["credit"]
        hist = d["hist"]
        # phase A
        full = occ >= caps
        blocked = (full.astype(jnp.int64) @ out_adj.T) > 0        # (K, M)
        if M:
            # per-module dynamic_slice on the (H, K, M) ring: the row is
            # global (shared t), so no per-design gather is needed
            matured = jnp.concatenate(
                [lax.dynamic_slice(hist, ((t - leff[j]) % H, 0, j),
                                   (1, K, 1))[0, :, :]
                 for j in range(M)], axis=1)                      # (K, M)
        else:
            matured = jnp.zeros((K, 0), jnp.int64)
        can_push = (pushed < matured) & ~blocked & has_out[None, :]
        pushed = pushed + can_push
        occ = occ + can_push[:, src]
        new_hwm = occ > d["hwm"]
        hwm_cycle = jnp.where(new_hwm, t, d["hwm_cycle"])
        hwm = jnp.maximum(d["hwm"], occ)
        # phase B
        done_m = launched >= tot[None, :]
        done_dst = fr >= frames
        need = need_of(kf, fr)
        pop = ~done_dst & (consumed < need) & (occ > 0)
        occ = occ - pop
        consumed = consumed + pop
        unmet = (consumed < need) & ~done_dst
        ready = (unmet.astype(jnp.int64) @ in_adj.T) == 0
        c = credit + rnum[None, :]
        launch = ready & ~done_m & active[None, :] \
            & (~throt[None, :] | (c >= rden[None, :]))
        credit = jnp.where(
            throt[None, :],
            jnp.where(launch, c - rden[None, :],
                      jnp.minimum(c, rden[None, :])), credit)
        launched = launched + launch
        pushed = pushed + (launch & is_sink[None, :])
        launch_e = launch[:, dst]
        wrap = launch_e & (kf == ot[None, :])
        kf = jnp.where(wrap, 1, kf + launch_e)
        fr = fr + wrap
        progress = (jnp.any(can_push, axis=1) | jnp.any(pop, axis=1)
                    | jnp.any(launch, axis=1))
        last_progress = jnp.where(progress, t, d["last_progress"])
        # frame-end recording (the sink launches at most one token per
        # cycle, so at most one boundary can be crossed)
        sink_l = jnp.take(launched, jnp.maximum(sink0, 0), axis=1)
        crossed = (frame_tokens > 0) \
            & (sink_l // jnp.maximum(frame_tokens, 1) > d["nfe"])
        F = d["fe"].shape[1]
        femask = (jnp.arange(F)[None, :] == d["nfe"][:, None]) \
            & crossed[:, None]
        fe = jnp.where(femask, t, d["fe"])
        nfe = d["nfe"] + crossed
        return dict(d, occ=occ, consumed=consumed, kf=kf, fr=fr,
                    launched=launched, pushed=pushed, credit=credit,
                    hwm=hwm, hwm_cycle=hwm_cycle,
                    last_progress=last_progress,
                    pflag=progress.astype(jnp.int64), fe=fe, nfe=nfe)

    def mwhere(mask, new, old):
        return jnp.where(mask.reshape((K,) + (1,) * (new.ndim - 1)),
                         new, old)

    def jump_fn(d):
        """Global event jump: every running design is mid-plateau, so the
        earliest next event across the population bounds an exact skip."""
        t = d["t"]
        running = d["code_rec"] == _RUNNING
        launched, pushed, credit = d["launched"], d["pushed"], d["credit"]
        hist = d["hist"]
        full = d["occ"] >= caps
        blocked = (full.astype(jnp.int64) @ out_adj.T) > 0
        cand = active[None, :] & has_out[None, :] & ~blocked \
            & (pushed < launched)
        if M:
            d_ar = jnp.arange(Hs, dtype=jnp.int64)
            rows = (t + d_ar[:, None] - leff[None, :]) % H          # (H, M)
            vals = jnp.take_along_axis(
                hist, jnp.broadcast_to(rows[:, None, :], (Hs, K, M)),
                axis=0)                                             # (H, K, M)
            hit = (d_ar[:, None, None] < leff[None, None, :]) \
                & (vals > pushed[None, :, :]) & cand[None, :, :]
            d_first = jnp.argmax(hit, axis=0)                       # (K, M)
            te_mat = jnp.min(
                jnp.where(jnp.any(hit, axis=0), t + d_first, _INF), axis=1)
        else:
            te_mat = jnp.full((K,), _INF)
        need = need_of(d["kf"], d["fr"])
        done_dst = d["fr"] >= frames
        unmet = (d["consumed"] < need) & ~done_dst
        ready = (unmet.astype(jnp.int64) @ in_adj.T) == 0
        done_m = launched >= tot[None, :]
        cred = throt[None, :] & ready & ~done_m & active[None, :]
        gap = rden[None, :] - credit
        d_cred = jnp.maximum(
            0, -((-gap) // jnp.maximum(rnum[None, :], 1)) - 1)
        te_cred = jnp.min(jnp.where(cred, t + d_cred, _INF), axis=1)
        te_k = jnp.minimum(te_mat, te_cred)
        te_k = jnp.minimum(
            jnp.minimum(te_k, d["last_progress"] + stall_limit + 1),
            horizon)
        te = jnp.min(jnp.where(running, te_k, _INF), initial=_INF)
        te = jnp.clip(te, t, horizon)
        dt = te - t
        r = jnp.arange(Hs, dtype=jnp.int64)
        x_r = (te - 1) - ((te - 1 - r) % H)
        hist = jnp.where((x_r >= t)[:, None, None],
                         launched[None, :, :], hist)
        credit = mwhere(running,
                        jnp.where(throt[None, :],
                                  jnp.minimum(credit + dt * rnum[None, :],
                                              rden[None, :]),
                                  credit),
                        credit)
        return dict(d, t=te, hist=hist, credit=credit,
                    skipped=d["skipped"] + jnp.where(running, dt, 0),
                    pflag=jnp.where(running, 1, d["pflag"]))

    def body(state):
        d = unpack(state)
        running = d["code_rec"] == _RUNNING
        code = code_now(d)
        newly = running & (code != _RUNNING)
        d["code_rec"] = jnp.where(newly, code, d["code_rec"])
        d["cycles_rec"] = jnp.where(newly, d["t"], d["cycles_rec"])
        run2 = d["code_rec"] == _RUNNING
        new = step(d)
        for key in _POP_STATE_KEYS:
            if key in ("t", "hist", "code_rec", "cycles_rec"):
                continue
            d[key] = mwhere(run2, new[key], d[key])
        # the ring row is global: frozen designs write their frozen counts
        d["hist"] = lax.dynamic_update_slice(
            d["hist"], d["launched"][None, :, :], (d["t"] % H, 0, 0))
        d["t"] = d["t"] + 1
        plateau = jnp.any(run2) & jnp.all(~run2 | (d["pflag"] == 0)) \
            & (jump != 0)
        d = lax.cond(plateau, jump_fn, lambda x: x, d)
        return pack(d)

    def cond(state):
        return jnp.any(unpack(state)["code_rec"] == _RUNNING)

    return lax.while_loop(cond, body, state)


# AOT cache, same rationale as vector._SEG_CACHE: every population whose
# netlist + K match shares one executable, compiled for the host device
_POP_CACHE: Dict[Tuple, object] = {}


def _pop_kernel(consts, state, frames, H, horizon, stall_limit, sink0,
                frame_tokens, jump):
    import jax

    args = jax.device_put((consts, state, frames, H, horizon, stall_limit,
                           sink0, frame_tokens, jump), host_device())
    flat, _ = jax.tree_util.tree_flatten(args)
    key = tuple((np.shape(x), str(x.dtype)) for x in flat)
    compiled = _POP_CACHE.get(key)
    if compiled is None:
        compiled = jax.jit(_pop_impl).lower(*args).compile()
        _POP_CACHE[key] = compiled
    return compiled(*args)
