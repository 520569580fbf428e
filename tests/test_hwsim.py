"""Cycle-level simulator + simulation-guided FIFO allocator (repro/hwsim).

The simulator is the dynamic mirror of the analytic schedule solve: these
tests pin token conservation, throughput consistency, deadlock/starvation
detection, and the allocator's shrink-and-prove contract on the paper's
four apps at small frame sizes — plus the vectorized engine's bit-exact
equivalence to the scalar reference (both backends), multi-frame
steady-state marks, and the ``fifo_solver="sim"`` compile path.
"""
from fractions import Fraction

import numpy as np
import pytest

from repro.apps import SIM_CASES
from repro.core import CompileOptions, SimOptions, compile_pipeline
from repro.hwsim import VectorSim, allocate_fifos, area_units, compare, \
    fifo_area
from repro.hwsim.sim import (CycleSim, _need_proportional, _SimEdge,
                             _SimMod, build_sim, simulate)

# smaller-than-bench instances: tier-1 steps every module every cycle
SIZES = {
    "convolution": dict(w=48, h=20),
    "stereo": dict(w=32, h=12, nd=8),
    "flow": dict(w=24, h=12),
    "descriptor": dict(w=32, h=24, n_features=16, filter_burst=64),
}
PAPER_APPS = tuple(SIZES)


def _design(name):
    uf, T, hand = SIM_CASES[name](**SIZES[name])
    return compile_pipeline(uf, T=T), T, hand


@pytest.fixture(scope="module")
def designs():
    return {name: _design(name) for name in PAPER_APPS}


@pytest.mark.parametrize("name", PAPER_APPS)
def test_simulate_completes_and_conserves(designs, name):
    design, _, _ = designs[name]
    res = design.simulate()
    assert res.deadlock is None
    # the sink absorbed exactly one frame
    assert res.sink_tokens == design.out_tokens_per_frame
    assert 0 < float(res.throughput) <= 1
    for e in res.occupancy.per_edge:
        # conservation: nothing vanishes; a consumer that never needs its
        # trailing tokens (crop's dropped borders) may leave a bounded
        # residue resident in the FIFO at frame end
        assert 0 <= e.pushed - e.popped <= e.hwm
        # capacity respected: hwm <= depth + producer output register
        assert e.hwm <= e.depth + 1


@pytest.mark.parametrize("name", PAPER_APPS)
def test_allocator_shrinks_and_proves(designs, name):
    design, _, _ = designs[name]
    alloc = allocate_fifos(design)
    assert alloc.proven
    assert alloc.verified.cycles == alloc.baseline.cycles
    assert alloc.verified.deadlock is None
    bits = {(e.src, e.dst): e.token_bits for e in design.edges}
    for key, d in alloc.depths.items():
        assert d <= alloc.analytic[key]
    assert alloc.total_bits(bits) <= sum(
        d * bits[k] for k, d in alloc.analytic.items())
    # the area gate the CI job enforces
    assert area_units(fifo_area(alloc.depths, design.edges)) <= \
        area_units(fifo_area(alloc.analytic, design.edges))


def test_allocator_actually_saves_something(designs):
    """Across the four paper apps the simulation must tighten at least one
    FIFO — the slack-cycles-vs-resident-tokens gap is the paper's §7.3
    auto-vs-hand story, not a no-op."""
    saved = 0
    for name in PAPER_APPS:
        design, _, _ = designs[name]
        alloc = allocate_fifos(design)
        bits = {(e.src, e.dst): e.token_bits for e in design.edges}
        saved += sum(d * bits[k] for k, d in alloc.analytic.items()) \
            - alloc.total_bits(bits)
    assert saved > 0


def test_area_rows_reproduce_auto_vs_hand(designs):
    for name in PAPER_APPS:
        design, T, hand = designs[name]
        alloc = allocate_fifos(design)
        uf2, T2, _ = SIM_CASES[name](**SIZES[name])
        hand_design = compile_pipeline(
            uf2, T=T2, options=CompileOptions(manual_fifo_overrides=hand))
        row = compare(name, design, alloc, hand_design)
        r = row.ratios()
        # hand never costs more than fully-automatic; simulated sits at or
        # below analytic (full-design ratios, modules included)
        assert r["auto_vs_hand"] >= 1.0 or not hand
        assert r["sim_vs_analytic"] <= 1.0
        assert row.deadlocks == 0 and row.throughput_unchanged


def test_simulate_feeds_report(designs):
    design, _, _ = designs["convolution"]
    design.simulate()
    assert " -- hwsim --" in design.report()
    design.optimize_fifos()
    assert "simulated allocation" in design.report()


def test_guard_margin_respected(designs):
    design, _, _ = designs["convolution"]
    a0 = allocate_fifos(design, guard=0)
    a2 = allocate_fifos(design, guard=2)
    assert a2.proven
    for key in a0.depths:
        assert a2.depths[key] >= min(a0.depths[key],
                                     a2.analytic[key])


def test_filter_burst_floor_kept(designs):
    """Descriptor's Filter burst is data-dependent and user-annotated; the
    deterministic sim cannot exercise it, so the allocator must keep the
    annotated slots (paper §4.3)."""
    design, _, _ = designs["descriptor"]
    alloc = allocate_fifos(design)
    kept = [key for key, d in alloc.depths.items()
            if design.modules[key[0]].kind in ("Filter", "SparseTake")
            and d >= design.edges_map[key].src_burst]
    assert kept  # every bursty-sparse out-edge keeps its burst floor


def test_unbounded_sim_matches_bounded_throughput(designs):
    """The analytic depths are sufficient: capping FIFOs at them must not
    slow the frame vs an unbounded run (same cycle count)."""
    for name in ("convolution", "stereo"):
        design, _, _ = designs[name]
        bounded = simulate(design)
        free = simulate(design, unbounded=True)
        assert bounded.cycles == free.cycles


# ---- vectorized engine: bit-exact equivalence with the scalar model ----


def _edge_sig(res):
    """The engine-equivalence contract lives on SimResult so the tests and
    the hwsim-smoke CI gate compare the same fields."""
    return res.edge_signature()


@pytest.mark.parametrize("name", PAPER_APPS)
@pytest.mark.parametrize("frames", [1, 2])
def test_vector_engine_bit_identical_to_scalar(designs, name, frames):
    """Cross-check the packed-state engine (both its jit and numpy
    backends) against the scalar reference: identical cycle counts,
    per-FIFO high-water marks, stamps, push/pop totals and frame
    boundaries, single- and multi-frame."""
    design, _, _ = designs[name]
    ref = simulate(design, engine="scalar", frames=frames)
    depths = dict(design.fifo.depth)
    for jit in (True, False):
        got = VectorSim(design.modules, design.edges, depths,
                        frames=frames).run(jit=jit)
        assert got.cycles == ref.cycles
        assert got.sink_tokens == ref.sink_tokens
        assert got.frame_ends == ref.frame_ends
        assert got.deadlock is None
        assert _edge_sig(got) == _edge_sig(ref)


def test_vector_engine_is_default(designs):
    design, _, _ = designs["convolution"]
    res = design.simulate()
    assert res.engine == "vector"
    # sampling is scalar-only: auto falls back, explicit vector raises
    assert design.simulate(sample_every=64).engine == "scalar"
    with pytest.raises(ValueError):
        design.simulate(sample_every=64,
                        options=SimOptions(engine="vector"))


def test_cycle_simulator_compiles_for_the_host_device(designs):
    """The cycle simulator is a host tool: the vector and population
    engines compile and run on the CPU device, so simulate, optimize_fifos
    and explore never take the accelerator of a process that holds one."""
    import jax

    from repro.hwsim import PopulationSim, population, vector
    design, _, _ = designs["stereo"]
    depths = dict(design.fifo.depth)
    VectorSim(design.modules, design.edges, depths).run(jit=True)
    PopulationSim(design.modules, design.edges, [depths, depths]).run()
    cpu = jax.devices("cpu")[0]
    compiled = list(vector._SEG_CACHE.values()) \
        + list(population._POP_CACHE.values())
    assert len(compiled) >= 2
    for exe in compiled:
        devs = {d for s in jax.tree_util.tree_leaves(exe.input_shardings)
                for d in s.device_set}
        assert devs == {cpu}


def test_vector_unbounded_matches_scalar(designs):
    design, _, _ = designs["stereo"]
    ref = simulate(design, engine="scalar", unbounded=True)
    got = VectorSim(design.modules, design.edges, {}, unbounded=True).run()
    assert got.cycles == ref.cycles and _edge_sig(got) == _edge_sig(ref)


def test_vector_starvation_diagnosed():
    """Forcing an inconsistent need table (needs exceed what the producer
    ever makes) must stall and name the starved module/edge, like the
    scalar engine's diagnosis."""
    from repro.core.buffers import Edge
    from repro.core.dtypes import UInt
    from repro.core.rigel import Interface, RModule, ScheduleType

    def mod(name, total):
        st = ScheduleType(UInt(8), total, 1)
        return RModule(name, "Map", Interface("Static", st),
                       Interface("Static", st), Fraction(1), 0)

    mods = [mod("src", 5), mod("snk", 10)]
    edges = [Edge(0, 1, 8, 0, 0)]
    for jit in (True, False):
        vs = VectorSim(mods, edges, {(0, 1): 3})
        vs.need_buf = np.arange(1, 11, dtype=np.int64)   # need(k) = k
        res = vs.run(jit=jit)
        assert res.deadlock is not None
        assert "starved" in res.deadlock and "snk" in res.deadlock
        assert res.sink_tokens == 5


@pytest.mark.parametrize("name", PAPER_APPS)
@pytest.mark.parametrize("jit", [True, False])
def test_event_jump_bit_identical(designs, name, jit):
    """Event-jump batching (skipping provably idle cycles in one hop) must
    change nothing observable: identical cycle counts, frame boundaries
    and edge signatures vs a jump-off run of the same engine — and vs the
    scalar reference, which never jumps."""
    design, _, _ = designs[name]
    depths = dict(design.fifo.depth)
    ref = simulate(design, engine="scalar", frames=2)
    on = VectorSim(design.modules, design.edges, depths,
                   frames=2).run(jit=jit, event_jump=True)
    off = VectorSim(design.modules, design.edges, depths,
                    frames=2).run(jit=jit, event_jump=False)
    assert on.cycles == off.cycles == ref.cycles
    assert on.frame_ends == off.frame_ends == ref.frame_ends
    assert _edge_sig(on) == _edge_sig(off) == _edge_sig(ref)
    # the counter is diagnostic only: jump-off never skips, and skipped
    # cycles are excluded from the equivalence contract by construction
    assert off.cycles_skipped == 0
    assert on.cycles_skipped >= 0


def test_event_jump_pyramid_deadlock_path():
    """PYRAMID's analytic depths are deadlock-free since the cross-arm
    broadcast provisioning; shrinking the fanout's residue edge back to
    depth 0 reinstates the classic broadcast-residue wedge.  The
    event-jump must leap the stall tail on this real netlist and still
    report the identical diagnosis and signature as scalar and jump-off
    runs — and both fast paths (the vector event-jump and the scalar
    frozen-state early-abort) must report their savings."""
    uf, T, _ = SIM_CASES["pyramid"]()
    design = compile_pipeline(uf, T=T)
    assert simulate(design, engine="scalar").deadlock is None  # as shipped
    depths = dict(design.fifo.depth)
    depths[(6, 1)] = 0                 # reinstate the residue deadlock
    ref = build_sim(design.modules, design.edges, depths).run()
    patient = build_sim(design.modules, design.edges, depths).run(
        early_abort=False)
    on = VectorSim(design.modules, design.edges,
                   depths).run(event_jump=True)
    off = VectorSim(design.modules, design.edges,
                    depths).run(event_jump=False)
    assert ref.deadlock is not None
    assert on.deadlock == off.deadlock == ref.deadlock == patient.deadlock
    assert on.cycles == off.cycles == ref.cycles == patient.cycles
    assert _edge_sig(on) == _edge_sig(off) == _edge_sig(ref) \
        == _edge_sig(patient)
    assert on.cycles_skipped > 0 and off.cycles_skipped == 0
    assert ref.cycles_saved > 0 and patient.cycles_saved == 0
    assert on.cycles_saved > 0       # the clamped jump is the dead tail


@pytest.mark.parametrize("jit", [True, False])
def test_event_jump_skips_stall_tail(jit):
    """A starved netlist ends with a long no-progress tail (the engine
    waits out stall_limit before diagnosing): the event-jump must leap it
    in one hop — same diagnosis, same cycle count, skipped > 0."""
    from repro.core.buffers import Edge
    from repro.core.dtypes import UInt
    from repro.core.rigel import Interface, RModule, ScheduleType

    def mod(name, total):
        st = ScheduleType(UInt(8), total, 1)
        return RModule(name, "Map", Interface("Static", st),
                       Interface("Static", st), Fraction(1), 0)

    mods = [mod("src", 5), mod("snk", 10)]
    edges = [Edge(0, 1, 8, 0, 0)]
    runs = {}
    for jump in (True, False):
        vs = VectorSim(mods, edges, {(0, 1): 3})
        vs.need_buf = np.arange(1, 11, dtype=np.int64)   # need(k) = k
        runs[jump] = vs.run(jit=jit, event_jump=jump)
    on, off = runs[True], runs[False]
    assert on.deadlock == off.deadlock and "starved" in on.deadlock
    assert on.cycles == off.cycles
    assert _edge_sig(on) == _edge_sig(off)
    assert on.cycles_skipped > 0 and off.cycles_skipped == 0


def test_vector_horizon_matches_scalar(designs):
    design, _, _ = designs["flow"]
    ref = simulate(design, engine="scalar", max_cycles=40)
    got = simulate(design, engine="vector", max_cycles=40)
    assert ref.deadlock == got.deadlock == "horizon exceeded (40 cycles)"
    assert ref.cycles == got.cycles == 40


def test_vector_horizon_on_frame_boundary_keeps_frame_end(designs):
    """Regression: the jit stop-code priority masks a frame-boundary PAUSE
    when the horizon lands on the very cycle-end that crossed it — the
    boundary must still be recorded, like the scalar engine does during
    the last executed cycle."""
    design, _, _ = designs["convolution"]
    full = simulate(design, engine="scalar", frames=2)
    horizon = full.frame_ends[0] + 1     # cut exactly after frame 0 ends
    ref = simulate(design, engine="scalar", frames=2, max_cycles=horizon)
    got = simulate(design, engine="vector", frames=2, max_cycles=horizon)
    assert ref.frame_ends == got.frame_ends == [full.frame_ends[0]]
    assert ref.cycles == got.cycles
    assert _edge_sig(ref) == _edge_sig(got)


# ---- multi-frame steady state ----


@pytest.mark.parametrize("name", PAPER_APPS)
def test_multiframe_steady_state_marks(designs, name):
    """N back-to-back frames: the sink absorbs N frames, frame boundaries
    are strictly increasing, every steady-state high-water mark is >= its
    single-frame mark, and each mark's (cycle, frame) stamps are mutually
    consistent — the cycle stamp falls inside its frame stamp's window."""
    design, _, _ = designs[name]
    one = design.simulate(options=SimOptions(frames=1))
    multi = design.simulate(options=SimOptions(frames=3))
    assert multi.sink_tokens == 3 * design.out_tokens_per_frame
    assert multi.frame_ends == sorted(set(multi.frame_ends))
    assert len(multi.frame_ends) == 3
    h1, h3 = one.hwm_by_key(), multi.hwm_by_key()
    assert all(h3[k] >= h1[k] for k in h1)
    fe = np.asarray(multi.frame_ends)
    for e in multi.occupancy.per_edge:
        # monotonic stamps: the frame index recorded with the mark is
        # exactly the number of frame boundaries before its cycle stamp
        assert e.hwm_frame == int(np.searchsorted(fe, e.hwm_cycle,
                                                  side="left"))
        assert 0 <= e.hwm_frame < 3


def test_multiframe_residue_exceeds_single_frame(designs):
    """CONVOLUTION's crop leaves dropped-border residue resident at frame
    end; the next frame's early consumption drains it while new tokens
    arrive, so the steady-state mark on the crop's drain FIFO exceeds the
    single-frame mark — the effect single-frame simulation cannot see."""
    design, _, _ = designs["convolution"]
    one = design.simulate(unbounded=True, options=SimOptions(frames=1))
    multi = design.simulate(unbounded=True, options=SimOptions(frames=3))
    h1, h3 = one.hwm_by_key(), multi.hwm_by_key()
    grew = [k for k in h1 if h3[k] > h1[k]]
    assert grew, "steady state must exceed single-frame somewhere"
    # and the grown mark was first reached after frame 0 completed
    by_key = {e.key: e for e in multi.occupancy.per_edge}
    assert any(by_key[k].hwm_frame >= 1 for k in grew)


def test_allocator_steady_state_depths(designs):
    """allocate_fifos(frames=N) sizes against the steady state: depths are
    still <= analytic, the run re-verifies, and the residue FIFO keeps
    more slots than the single-frame allocation would grant it."""
    design, _, _ = designs["convolution"]
    a1 = allocate_fifos(design, frames=1)
    a3 = allocate_fifos(design, frames=3)
    assert a3.proven and a3.frames == 3
    assert all(a3.depths[k] <= a3.analytic[k] for k in a3.depths)
    assert any(a3.depths[k] > a1.depths[k] for k in a1.depths)


# ---- fifo_solver="sim" (the compiler wiring) ----


def test_fifo_solver_sim_installs_proven_depths(designs):
    design, _, _ = designs["convolution"]
    uf, T, _ = SIM_CASES["convolution"](**SIZES["convolution"])
    sim_design = compile_pipeline(
        uf, T=T, options=CompileOptions(fifo_solver="sim", sim_frames=2))
    assert sim_design.fifo.solver == "sim"
    assert sim_design.fifo_analytic == design.fifo.depth
    assert sim_design.fifo.total_bits <= design.fifo.total_bits
    for k, d in sim_design.fifo.depth.items():
        assert d <= design.fifo.depth[k]
    # schedule untouched: frame time identical to the analytic design
    assert sim_design.cycles_per_frame() == design.cycles_per_frame()
    assert sim_design.fifo.start == design.fifo.start
    # the proven depths complete a steady-state run at the same cycle
    # count as the analytic depths
    ref = design.simulate(options=SimOptions(frames=2))
    got = sim_design.simulate(options=SimOptions(frames=2))
    assert got.completed and got.cycles == ref.cycles
    rep = sim_design.report()
    assert "solver=sim" in rep
    assert "fifo solve: analytic" in rep and "proven by re-simulation" in rep


def test_fifo_solver_sim_area_never_exceeds_analytic(designs):
    for name in ("stereo", "descriptor"):
        design, _, _ = designs[name]
        uf, T, _ = SIM_CASES[name](**SIZES[name])
        sim_design = compile_pipeline(
            uf, T=T, options=CompileOptions(fifo_solver="sim"))
        assert area_units(fifo_area(sim_design.fifo.depth,
                                    sim_design.edges)) <= \
            area_units(fifo_area(design.fifo.depth, design.edges))


def test_fifo_solver_sim_repairs_pyramid_deadlock():
    """PYRAMID's analytic depths used to deadlock (the fanout edge of the
    reconvergent down/up-sample diamond must absorb a whole resampling
    phase of cross-arm residue); the trace-algebra provisioning closed
    that gap, so the sim solver now starts from a live baseline and needs
    no repair.  The allocator's upward search is still load-bearing for
    externally-supplied broken depths, so reinstate the residue deadlock
    by zeroing the fanout's residue edge and check the search grows it
    back to a proven allocation."""
    uf, T, _ = SIM_CASES["pyramid"]()
    design = compile_pipeline(uf, T=T)
    res = design.simulate()
    assert res.completed                         # analytic is live now
    free_cycles = design.simulate(unbounded=True).cycles
    assert res.cycles == free_cycles

    uf2, T2, _ = SIM_CASES["pyramid"]()
    sim_design = compile_pipeline(uf2, T=T2,
                                  options=CompileOptions(fifo_solver="sim"))
    assert sim_design.fifo.solver == "sim" and sim_design.fifo_sim_proven
    assert not any("grown past a deadlocked analytic depth" in n
                   for n in sim_design.notes)    # nothing left to repair
    assert sim_design.simulate().completed
    from repro.analysis.handshake import cross_check
    assert cross_check(sim_design).ok

    # reinstate the broadcast-residue wedge and exercise the repair path
    design.fifo.depth[(6, 1)] = 0
    assert not design.simulate().completed
    alloc = allocate_fifos(design)
    assert alloc.grown_edges > 0 and alloc.proven
    assert alloc.depths[(6, 1)] > 0              # grown back past the wedge
    assert alloc.verified.completed
    assert alloc.verified.cycles == free_cycles
    assert any("upward search grew" in n for n in alloc.notes)


# ---- needs() cache sentinel (regression) ----


def test_needs_cache_none_sentinel():
    """_SimMod.needs cached with sentinel ``_need_k = 0``, which only
    worked because launches start at k=1: a later needs(0) call would get
    the stale pre-warm empty list. The sentinel is now None — needs(0)
    must compute real values."""
    m = _SimMod(0, "m", "Map", Fraction(1), 0, 10, False)
    e = _SimEdge(0, (1, 0), cap=4, token_bits=8)
    m.in_edges.append((e, _need_proportional(10, 10)))
    m.consumed.append(0)
    assert m.needs(0) == [0]          # not the stale []
    assert m.needs(1) == [1]
    assert m.needs(0) == [0]          # flips back, no stale direction bias
    assert m.needs(1) == [1]


# ---- detection machinery on hand-built graphs ----


def _mod(idx, name, total, rate=Fraction(1), latency=0, throttled=False):
    return _SimMod(idx, name, "Map", rate, latency, total, throttled)


def test_starvation_detected_as_deadlock():
    """A consumer whose declared needs exceed what its producer will ever
    make must be reported as a starvation deadlock, naming the edge."""
    src = _mod(0, "src", total=5)
    sink = _mod(1, "snk", total=10)
    e = _SimEdge(0, (0, 1), cap=4, token_bits=8)
    src.out_edges.append(e)
    sink.in_edges.append((e, _need_proportional(10, 10)))
    sink.consumed.append(0)
    res = CycleSim([src, sink], [e]).run()
    assert res.deadlock is not None
    assert "starved" in res.deadlock and "snk" in res.deadlock
    assert res.sink_tokens == 5        # everything produced got through


def test_horizon_exceeded_reported():
    src = _mod(0, "src", total=50, rate=Fraction(1, 4), throttled=True)
    sink = _mod(1, "snk", total=50)
    e = _SimEdge(0, (0, 1), cap=2, token_bits=8)
    src.out_edges.append(e)
    sink.in_edges.append((e, _need_proportional(50, 50)))
    sink.consumed.append(0)
    res = CycleSim([src, sink], [e]).run(max_cycles=10)
    assert res.deadlock and "horizon" in res.deadlock


def test_rate_throttle_is_exact():
    """A rate-R source into an always-ready sink finishes in ceil(n/R)
    cycles (depth-one token bucket: no drift, no catch-up bursts)."""
    n, rate = 30, Fraction(2, 3)
    src = _mod(0, "src", total=n, rate=rate, throttled=True)
    sink = _mod(1, "snk", total=n)
    e = _SimEdge(0, (0, 1), cap=4, token_bits=8)
    src.out_edges.append(e)
    sink.in_edges.append((e, _need_proportional(n, n)))
    sink.consumed.append(0)
    res = CycleSim([src, sink], [e]).run()
    assert res.deadlock is None
    # launches happen at ceil(k/R)-spaced cycles; +1 for the push phase
    assert res.cycles <= -(-n * rate.denominator // rate.numerator) + 2
