"""Serving-layer suite: micro-batcher policy (signature bucketing, deadline
flush), frame-axis sharding fallback, the donate-able batched engine path,
the live asyncio server (bit-exact round trips over mixed-signature
traffic on two apps), and the CI bench-regression gate logic."""
import numpy as np
import pytest

from repro.core.executor import evaluate
from repro.serve import (HIGH, LOW, NORMAL, AdmissionController,
                         FrameRequest, FrameServer, MicroBatcher,
                         Overloaded, QoSPolicy, ServeConfig, ServeTrace,
                         device_put_batch, frame_sharding, frame_signature,
                         pad_frames, split_frames, stack_frames)


def _req(app, inputs, t=0.0):
    return FrameRequest(app, inputs, frame_signature(inputs), t)


def _frame(shape=(8, 6), dtype=np.int64, seed=0):
    return {"in": np.random.RandomState(seed).randint(
        0, 100, shape).astype(dtype)}


# ---- batcher policy ----

def test_bucketing_never_mixes_shapes_dtypes_or_apps():
    """Every flushed batch is uniform in (app, signature) no matter how
    interleaved the arrivals are."""
    b = MicroBatcher(max_batch=4, max_delay_s=10.0)
    variants = [("a", (8, 6), np.int64), ("a", (4, 4), np.int64),
                ("a", (8, 6), np.int32), ("b", (8, 6), np.int64)]
    batches = []
    for i in range(40):
        app, shape, dt = variants[i % 4]
        batches += b.add(_req(app, _frame(shape, dt, seed=i)), now=0.0)
    batches += b.flush_all()
    assert sum(len(r) for r in batches) == 40
    for reqs in batches:
        assert len({(r.app, r.signature) for r in reqs}) == 1
        stacked, n = stack_frames(reqs)         # stackable by construction
        assert n == len(reqs)


def test_size_flush_at_max_batch():
    b = MicroBatcher(max_batch=3, max_delay_s=10.0)
    f = _frame()
    assert b.add(_req("a", f), 0.0) == []
    assert b.add(_req("a", f), 0.0) == []
    (reqs,) = b.add(_req("a", f), 0.0)
    assert len(reqs) == 3 and b.pending == 0 and b.size_flushes == 1


def test_deadline_flush_fires_on_partial_batch():
    """A partial bucket flushes once its oldest frame has waited
    max_delay_s; the clock is injected so the policy is deterministic."""
    b = MicroBatcher(max_batch=8, max_delay_s=0.5)
    f = _frame()
    b.add(_req("a", f), now=100.0)
    b.add(_req("a", f), now=100.2)
    assert b.due(now=100.4) == []               # oldest has waited 0.4 < 0.5
    assert b.next_deadline() == pytest.approx(100.5)
    (reqs,) = b.due(now=100.5)
    assert len(reqs) == 2
    assert b.deadline_flushes == 1 and b.pending == 0
    assert b.next_deadline() is None


def test_occupancy_high_water_accounting():
    b = MicroBatcher(max_batch=8, max_delay_s=10.0)
    for i in range(5):
        b.add(_req("a", _frame()), 0.0)
        b.add(_req("b", _frame()), 0.0)
    assert b.pending == 10 and b.pending_hw == 10
    b.flush_all()
    assert b.pending == 0 and b.pending_hw == 10


def test_stack_pad_split_roundtrip():
    reqs = [_req("a", _frame(seed=i)) for i in range(3)]
    batch, n = stack_frames(reqs, pad_to=4)     # pow2 padding bucket
    assert n == 3 and batch["in"].shape == (4, 8, 6)
    assert np.array_equal(batch["in"][3], batch["in"][2])  # repeat last
    outs = split_frames(batch["in"], n)
    assert len(outs) == 3
    assert all(np.array_equal(o, r.inputs["in"])
               for o, r in zip(outs, reqs))


def test_stack_frames_rejects_mixed_signature():
    with pytest.raises(AssertionError):
        stack_frames([_req("a", _frame((8, 6))), _req("a", _frame((4, 4)))])


# ---- sharding fallback + engine serving path ----

def test_single_device_sharding_is_transparent():
    import jax
    assert frame_sharding([jax.devices()[0]]) is None
    batch = {"in": np.arange(12, dtype=np.int64).reshape(3, 4),
             "pair": (np.ones((3, 2), np.int64), np.zeros((3, 2), np.int64))}
    dev, n = device_put_batch(batch, None)
    assert n == 3
    assert np.array_equal(np.asarray(dev["in"]), batch["in"])
    assert str(dev["in"].dtype) == "int64"      # x64 transport preserved
    padded, n2 = pad_frames(batch, 4)
    assert n2 == 3 and padded["in"].shape[0] == 4
    assert np.array_equal(padded["in"][3], batch["in"][2])


def _check_run_batch_device(design, inputs_fn, donate):
    batch = inputs_fn(np.random.RandomState(5), frames=3)
    ref = design.run_batch(batch, backend="jax")
    lp = design.lower("jax")
    dev_batch, n = device_put_batch(batch, None)
    out = lp.run_batch_device(dev_batch, donate=donate)
    got = split_frames(out, n)
    for i in range(n):
        a = ref[i] if not isinstance(ref, tuple) else tuple(
            e[i] for e in ref)
        ga = got[i]
        if isinstance(ga, tuple):
            assert all(np.array_equal(np.asarray(x), np.asarray(y))
                       for x, y in zip(ga, a))
        else:
            assert np.array_equal(np.asarray(ga), np.asarray(a))


def test_run_batch_device_matches_run_batch(lowering_cases):
    """The serving call path (device results, single-device fallback) is
    bit-identical to run_batch for every app."""
    for name, (design, inputs_fn) in lowering_cases.items():
        _check_run_batch_device(design, inputs_fn, donate=False)


def test_run_batch_device_donation_bit_exact(lowering_cases):
    """Donating dead segment inputs cannot change results (donation is a
    buffer-reuse hint; a no-op where unsupported).  One app suffices —
    the donate key recompiles every program segment."""
    design, inputs_fn = lowering_cases["flow"]
    _check_run_batch_device(design, inputs_fn, donate=True)
    lp = design.lower("jax")
    assert any(t.dead_in for t in lp._plan)     # liveness pass found deads


def test_engine_exposes_frame_signature(lowering_cases):
    design, inputs_fn = lowering_cases["convolution"]
    lp = design.lower("jax")
    a = lp.frame_signature(inputs_fn(np.random.RandomState(0)))
    b = lp.frame_signature(inputs_fn(np.random.RandomState(9)))
    assert a == b                                # same shapes/dtypes
    assert isinstance(hash(a), int)


# ---- declared-width readback ----

# the leaves run_batch_device returns: integer roots of at most 32 bits
# on the 32-bit integer of their signedness, float roots untouched
DEVICE_DTYPES = {"convolution": ["uint32"], "stereo": ["uint32"],
                 "flow": ["float32", "float32"],
                 "descriptor": ["float32", "uint32"], "pyramid": ["uint32"]}


def _leaves(v):
    return list(v) if isinstance(v, tuple) else [v]


def _assert_executor_identical(got, want):
    """Same leaves, same dtypes (int64 for integers), same values."""
    got, want = _leaves(got), _leaves(want)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert isinstance(g, np.ndarray) and g.dtype == np.asarray(w).dtype
        assert np.array_equal(g, w)


@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("app", sorted(DEVICE_DTYPES))
def test_run_batch_device_leaves_on_device_carrier(app, backend,
                                                   lowering_cases):
    """Also through megakernel roots (FLOW, PYRAMID on pallas)."""
    import jax
    design, inputs_fn = lowering_cases[app]
    batch = inputs_fn(np.random.RandomState(4), frames=2)
    out = design.lower(backend).run_batch_device(batch)
    assert [str(x.dtype) for x in jax.tree_util.tree_leaves(out)] \
        == DEVICE_DTYPES[app]


@pytest.mark.parametrize("app", sorted(DEVICE_DTYPES))
def test_results_return_on_int64_carrier(app, lowering_cases):
    """split_frames, run and run_batch widen the narrow device leaves back:
    every caller sees the executor's dtypes and values."""
    design, inputs_fn = lowering_cases[app]
    batch = inputs_fn(np.random.RandomState(4), frames=2)
    ref = design.run_batch(batch, backend="numpy")
    lp = design.lower("jax")
    _assert_executor_identical(lp.run_batch(batch), ref)
    for i, got in enumerate(split_frames(lp.run_batch_device(batch), 2)):
        one = {k: tuple(e[i] for e in v) if isinstance(v, tuple) else v[i]
               for k, v in batch.items()}
        want = evaluate(design.out_val, one)
        _assert_executor_identical(got, want)
        _assert_executor_identical(design.run(one, backend="jax"), want)


def test_server_answers_stay_int64():
    """FrameServer answers of a narrowed integer root are int64 and equal
    to the executor's."""
    from repro.apps import BENCH_CASES
    from repro.core import compile_pipeline
    uf, inputs_fn = BENCH_CASES["stereo"]()
    design = compile_pipeline(uf)
    frames = [inputs_fn(np.random.RandomState(20 + i)) for i in range(3)]
    with FrameServer(ServeConfig(max_batch=2, max_delay_ms=5.0)) as srv:
        srv.register(design, name="stereo")
        outs = [f.result(timeout=300)
                for f in [srv.submit(x, app="stereo") for x in frames]]
    for inp, out in zip(frames, outs):
        _assert_executor_identical(out, evaluate(design.out_val, inp))


# ---- live server round trips ----

def test_server_round_trip_bit_exact_two_apps(lowering_cases):
    """Mixed-signature traffic (two apps, two sizes each per-frame RNG)
    through one live server: every response bit-exact vs the numpy
    executor; stats and report() surface the FIFO accounting."""
    conv, conv_in = lowering_cases["convolution"]
    stereo, stereo_in = lowering_cases["stereo"]
    frames = []
    for i in range(14):                          # not divisible by max_batch:
        app = ("convolution", "stereo")[i % 2]   # exercises deadline flushes
        fn = conv_in if app == "convolution" else stereo_in
        frames.append((app, fn(np.random.RandomState(i))))
    with FrameServer(ServeConfig(max_batch=4, max_delay_ms=20.0)) as srv:
        srv.register(conv, name="convolution")
        srv.register(stereo, name="stereo")
        futs = [(app, inp, srv.submit(inp, app=app)) for app, inp in frames]
        outs = [(app, inp, f.result(timeout=300)) for app, inp, f in futs]
    for app, inp, out in outs:
        d = conv if app == "convolution" else stereo
        assert np.array_equal(np.asarray(out), evaluate(d.out_val, inp))
    st = srv.stats
    assert st.frames_in == st.frames_out == 14
    assert st.batches >= 4 and st.inflight_hw >= 1
    assert any("fifo occupancy" in ln for ln in st.report_lines())


def test_design_serve_entrypoint_and_report(lowering_cases):
    design, inputs_fn = lowering_cases["descriptor"]
    frames = [inputs_fn(np.random.RandomState(i)) for i in range(5)]
    with design.serve(config=ServeConfig(max_batch=4,
                                         max_delay_ms=10.0)) as srv:
        outs = [f.result(timeout=300) for f in srv.submit_many(frames)]
    for inp, out in zip(frames, outs):
        ref = evaluate(design.out_val, inp)    # tuple-valued output app
        assert isinstance(out, tuple) and len(out) == len(ref)
        assert all(np.array_equal(np.asarray(a), np.asarray(b))
                   for a, b in zip(out, ref))
    assert " -- serve --" in design.report()
    assert any("latency p50" in ln for ln in design.report().splitlines())


def test_simulate_ingest_prediction_in_stats(lowering_cases):
    """The hwsim cycle engine predicts the request FIFO's steady-state
    occupancy from the observed arrival/service rates; the prediction lands
    in ServeStats next to the observed high-water mark."""
    design, inputs_fn = lowering_cases["convolution"]
    frames = [inputs_fn(np.random.RandomState(i)) for i in range(8)]
    with design.serve(config=ServeConfig(max_batch=4,
                                         max_delay_ms=2.0)) as srv:
        for f in srv.submit_many(frames):
            f.result(timeout=300)
        res = srv.simulate_ingest(frames=256, seed=1)
        assert res.completed
        assert srv.stats.predicted_queue_hw == res.hwm >= 1
        rep = "\n".join(srv.stats.report_lines())
        assert "predicted" in rep and "rho=" in rep
        # deterministic: same seed + explicit rates -> same prediction
        r1 = srv.simulate_ingest(frames=256, seed=1, arrival_fps=200.0,
                                 service_fps=400.0)
        r2 = srv.simulate_ingest(frames=256, seed=1, arrival_fps=200.0,
                                 service_fps=400.0)
        assert r1.hwm == r2.hwm and r1.cycles == r2.cycles


def test_ingest_sim_overload_hits_capacity():
    """rho > 1 (arrivals faster than service) pins the simulated ingest
    FIFO at its capacity — the backpressure regime where submit() blocks."""
    from fractions import Fraction

    from repro.hwsim import simulate_ingest
    res = simulate_ingest(200, mean_gap_cycles=32,
                          service_rate=Fraction(1, 64), capacity=16, seed=3)
    assert res.completed                      # backpressure, not deadlock
    assert res.utilization > 1.5
    assert res.hwm >= 16                      # queue pinned at its bound
    lo = simulate_ingest(200, mean_gap_cycles=32,
                         service_rate=Fraction(1, 16), capacity=16, seed=3)
    assert lo.hwm < res.hwm                   # faster service, lower marks


def test_serve_config_validates():
    for bad in (dict(depth=0), dict(max_batch=0), dict(max_queue=0),
                dict(max_delay_ms=0)):
        with pytest.raises(ValueError):
            ServeConfig(**bad)


def test_server_submit_unknown_app_raises(lowering_cases):
    design, _ = lowering_cases["pyramid"]
    with FrameServer(ServeConfig(max_batch=2)) as srv:
        srv.register(design)
        with pytest.raises(KeyError):
            srv.submit({"x": np.zeros((2, 2))}, app="nope")
    with pytest.raises(RuntimeError):
        srv.submit({"x": np.zeros((2, 2))})      # closed


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_multi_device_sharded_serving_bit_exact(backend):
    """Frame-axis sharding across 8 (forced host) devices stays bit-exact
    on both backends (on pallas every device runs the megakernel on its
    own frames); runs in a subprocess so this process keeps its
    single-device view."""
    import os
    import subprocess
    import sys
    import textwrap
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = "src"
    # don't contend with the parent process on the persistent XLA cache
    # (conftest.py points both at .cache/jax, and the 8-device layout's
    # entries are useless to the single-device parent anyway)
    for k in list(env):
        if k.startswith("JAX_COMPILATION_CACHE") or \
                k.startswith("JAX_PERSISTENT_CACHE"):
            env.pop(k)
    code = f"BACKEND = {backend!r}\n" + textwrap.dedent("""
        import numpy as np, jax
        assert len(jax.devices()) == 8
        from repro.apps import BENCH_CASES
        from repro.core import compile_pipeline
        from repro.core.executor import evaluate
        from repro.serve import ServeConfig
        uf, inputs_fn = BENCH_CASES['flow']()
        d = compile_pipeline(uf)
        frames = [inputs_fn(np.random.RandomState(i)) for i in range(11)]
        cfg = ServeConfig(max_batch=8, max_delay_ms=20.0, donate=True)
        with d.serve(backend=BACKEND, config=cfg) as srv:
            outs = [f.result(timeout=300) for f in srv.submit_many(frames)]
        for fr, o in zip(frames, outs):
            ref = evaluate(d.out_val, fr)
            if isinstance(ref, tuple):
                assert all(np.array_equal(np.asarray(a), b)
                           for a, b in zip(o, ref))
            else:
                assert np.array_equal(np.asarray(o), ref)
        assert srv.stats.devices == 8
        print('SHARDED_SERVE_OK')
    """)
    cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for attempt in (0, 1):        # one retry: 8 fake devices + full-suite
        r = subprocess.run([sys.executable, "-c", code],  # load can OOM/stall
                           capture_output=True, text=True, timeout=600,
                           env=env, cwd=cwd)
        if r.returncode == 0:
            break
    assert r.returncode == 0, r.stderr[-2000:]
    assert "SHARDED_SERVE_OK" in r.stdout


# ---- bench-regression gate logic ----

def test_check_regression_logic():
    from benchmarks.check_regression import find_regressions
    base = {"apps": {"a": {"speedup_jax_vs_numpy": 4.0},
                     "b": {"speedup_jax_vs_numpy": 2.0}}}
    fresh = {"apps": {"a": {"speedup_jax_vs_numpy": 3.2},   # -20%: ok
                      "b": {"speedup_jax_vs_numpy": 1.4}}}  # -30%: regressed
    rows, bad = find_regressions(base, fresh, threshold=0.25)
    assert bad == ["b:speedup_jax_vs_numpy"]
    assert any("REGRESSED" in r for r in rows)
    # serve metric absent from BOTH sides everywhere -> no extra rows at all
    assert len(rows) == 2


def test_check_regression_gates_serve_rows():
    """The gate also covers serve throughput (nested dotted metric)."""
    from benchmarks.check_regression import find_regressions
    base = {"apps": {
        "a": {"speedup_jax_vs_numpy": 4.0,
              "serve": {"throughput_x_vs_run": 10.0}}}}
    fresh = {"apps": {
        "a": {"speedup_jax_vs_numpy": 4.0,
              "serve": {"throughput_x_vs_run": 5.0}}}}  # -50%: regressed
    rows, bad = find_regressions(base, fresh, threshold=0.25)
    assert bad == ["a:serve.throughput_x_vs_run"]


@pytest.mark.parametrize("in_base,in_fresh,expect_row,expect_fail", [
    (True, True, True, False),     # both present, no regression: OK row
    (True, False, True, True),     # baseline-only: bench stopped producing
    (False, True, True, True),     # fresh-only: baseline never committed
    (False, False, False, False),  # both missing: metric not tracked, skip
])
def test_check_regression_presence_combinations(in_base, in_fresh,
                                                expect_row, expect_fail):
    """All four metric-presence combinations: only both-sides-missing may
    skip silently; either one-sided-missing case must hard-fail the gate
    with a clear message (a silently vanished metric is exactly what the
    gate exists to catch)."""
    from benchmarks.check_regression import find_regressions
    base = {"apps": {"a": ({"speedup_jax_vs_numpy": 4.0} if in_base
                           else {})}}
    fresh = {"apps": {"a": ({"speedup_jax_vs_numpy": 4.0} if in_fresh
                            else {})}}
    rows, bad = find_regressions(base, fresh, threshold=0.25)
    assert bool(rows) == expect_row
    assert bool(bad) == expect_fail
    if in_base != in_fresh:
        assert bad == ["a:speedup_jax_vs_numpy"]
        assert any("MISSING" in r for r in rows)
        missing_side = ("fresh run" if in_base else "committed baseline")
        assert any(missing_side in r for r in rows)


def test_check_regression_lower_is_better_metrics():
    """shed_rate / p99_ms regress on a RISE past the threshold, not a
    drop; improvements (drops) always pass."""
    from benchmarks.check_regression import find_regressions
    base = {"apps": {"control_plane": {"serve": {"shed_rate": 0.25,
                                                 "p99_ms": 25.0}}}}
    fresh = {"apps": {"control_plane": {"serve": {"shed_rate": 0.35,
                                                  "p99_ms": 10.0}}}}
    rows, bad = find_regressions(
        base, fresh, threshold=0.25,
        metrics=("serve.shed_rate", "serve.p99_ms"))
    assert bad == ["control_plane:serve.shed_rate"]
    assert any("ceil=" in r for r in rows)


# ---- continuous (rolling) batching ----

def test_rolling_take_never_mixes_signatures():
    """The pull API drains exactly one bucket per take(): no batch ever
    mixes (app, signature) no matter how interleaved the window is, and
    the un-taken remainder keeps rolling."""
    b = MicroBatcher(max_batch=4, max_delay_s=1e9)
    variants = [("a", (8, 6), np.int64), ("a", (4, 4), np.int64),
                ("a", (8, 6), np.int32), ("b", (8, 6), np.int64)]
    for i in range(37):                       # ragged: buckets end partial
        app, shape, dt = variants[i % 4]
        b.put(_req(app, _frame(shape, dt, seed=i), t=float(i)), now=float(i))
    taken = []
    while b.has_pending():
        reqs = b.take(now=1e9 + 1, allow_partial=True)
        assert reqs is not None and 1 <= len(reqs) <= 4
        assert len({(r.app, r.signature) for r in reqs}) == 1
        taken.append(reqs)
    assert sum(len(r) for r in taken) == 37
    assert b.take(now=1e9 + 1, allow_partial=True) is None


def test_rolling_take_tiers_and_remainder():
    """Selection order: full bucket beats expired beats partial; a partial
    is only released with allow_partial; an over-full bucket leaves its
    remainder as the new window head with its deadline re-anchored."""
    b = MicroBatcher(max_batch=2, max_delay_s=10.0)
    f = _frame()
    b.put(_req("full", f, t=5.0), now=5.0)
    b.put(_req("full", f, t=6.0), now=6.0)    # bucket "full" has 2 == max
    b.put(_req("old", f, t=0.0), now=0.0)     # expired at now=11
    b.put(_req("new", f, t=10.9), now=10.9)   # partial, not expired
    first = b.take(now=11.0)
    assert [r.app for r in first] == ["full", "full"]
    second = b.take(now=11.0)                 # deadline tier
    assert [r.app for r in second] == ["old"]
    assert b.take(now=11.0) is None           # partial needs allow_partial
    third = b.take(now=11.0, allow_partial=True)
    assert [r.app for r in third] == ["new"] and b.topup_flushes == 1
    # remainder semantics: 3 frames in a max_batch=2 bucket
    for i in range(3):
        b.put(_req("r", f, t=20.0 + i), now=20.0 + i)
    got = b.take(now=21.9, allow_partial=True)
    assert len(got) == 2 and b.pending == 1
    rest = b.take(now=22.0, allow_partial=True)
    assert len(rest) == 1 and rest[0].enqueue_t == 22.0


def test_rolling_partial_prefers_priority_then_fullness():
    b = MicroBatcher(max_batch=8, max_delay_s=1e9)
    f = _frame()
    for i in range(3):                        # fuller, but low priority
        r = _req("lo", f, t=float(i))
        r.priority = LOW
        b.put(r, now=float(i))
    hi = _req("hi", f, t=5.0)
    hi.priority = HIGH
    b.put(hi, now=5.0)
    first = b.take(now=6.0, allow_partial=True)
    assert [r.app for r in first] == ["hi"]
    assert [r.app for r in b.take(now=6.0, allow_partial=True)] == ["lo"] * 3


def test_continuous_server_drains_partials_without_deadline(lowering_cases):
    """With a deadline far beyond the test timeout, flush-the-bucket would
    stall partial buckets forever; continuous batching must pull them as
    soon as a slot frees and still be bit-exact."""
    design, inputs_fn = lowering_cases["convolution"]
    frames = [inputs_fn(np.random.RandomState(i)) for i in range(5)]
    cfg = ServeConfig(max_batch=4, max_delay_ms=3600 * 1e3, continuous=True)
    with design.serve(config=cfg) as srv:
        outs = [f.result(timeout=300) for f in srv.submit_many(frames)]
        assert srv.stats.topup_flushes > 0
    for inp, out in zip(frames, outs):
        assert np.array_equal(np.asarray(out), evaluate(design.out_val, inp))


# ---- admission control / load shedding ----

def test_admission_watermarks_and_priority():
    adm = AdmissionController(max_queue=100)
    adm.set_policy("app", QoSPolicy(priority="normal"))
    # below every watermark: all classes admitted
    for lvl in (HIGH, NORMAL, LOW):
        assert adm.admit("app", depth=10, now=0.0, priority=lvl) == lvl
    # above the low watermark (50): low shed, normal/high admitted
    with pytest.raises(Overloaded) as ei:
        adm.admit("app", depth=60, now=0.0, priority=LOW)
    assert ei.value.reason == "queue" and ei.value.priority == LOW
    assert ei.value.app == "app" and ei.value.depth == 60
    assert adm.admit("app", depth=60, now=0.0) == NORMAL  # policy default
    # above the normal watermark (85): only high admitted
    with pytest.raises(Overloaded):
        adm.admit("app", depth=90, now=0.0, priority=NORMAL)
    assert adm.admit("app", depth=90, now=0.0, priority=HIGH) == HIGH
    # a truly full queue sheds even high (typed error, not a silent stall)
    with pytest.raises(Overloaded):
        adm.admit("app", depth=100, now=0.0, priority=HIGH)
    st = adm.stats["app"]
    assert st.admitted == 5 and st.shed_queue == 3 and st.shed_rate == 0
    assert adm.total_shed() == 3
    assert any("admission[app]" in ln for ln in adm.report_lines())


def test_admission_token_bucket_rate_cap():
    adm = AdmissionController(max_queue=100)
    adm.set_policy("capped", QoSPolicy(priority="low", rate_fps=10.0,
                                       burst=2))
    assert adm.admit("capped", 0, now=0.0) == LOW
    assert adm.admit("capped", 0, now=0.0) == LOW    # burst slack
    with pytest.raises(Overloaded) as ei:
        adm.admit("capped", 0, now=0.0)              # bucket empty
    assert ei.value.reason == "rate"
    # tokens regenerate at rate_fps: admitted again 0.1s later
    assert adm.admit("capped", 0, now=0.1) == LOW
    assert adm.stats["capped"].shed_rate == 1


def test_qos_policy_validates():
    with pytest.raises(ValueError):
        QoSPolicy(priority="urgent")
    with pytest.raises(ValueError):
        QoSPolicy(rate_fps=0)
    with pytest.raises(ValueError):
        QoSPolicy(burst=0)
    assert QoSPolicy(priority="high").priority_level == HIGH


def test_live_server_sheds_low_priority_with_typed_error(lowering_cases):
    """A rate-capped app sheds excess submissions with Overloaded while
    admitted frames complete bit-exact; counters land in stats/health."""
    design, inputs_fn = lowering_cases["convolution"]
    frames = [inputs_fn(np.random.RandomState(i)) for i in range(6)]
    srv = FrameServer(ServeConfig(max_batch=4, max_delay_ms=10.0))
    srv.register(design, name="conv", backend="jax",
                 warm_inputs=[frames[0]],
                 policy=QoSPolicy(priority="low", rate_fps=1e-3, burst=2))
    with srv:
        futs, shed = [], []
        for inp in frames:                    # back-to-back: no regen time
            try:
                futs.append((inp, srv.submit(inp, app="conv")))
            except Overloaded as e:
                shed.append(e)
        outs = [(inp, f.result(timeout=300)) for inp, f in futs]
    assert len(futs) == 2 and len(shed) == 4  # burst=2 admitted, rest shed
    for e in shed:
        assert e.app == "conv" and e.reason == "rate" and e.priority == LOW
    for inp, out in outs:
        assert np.array_equal(np.asarray(out), evaluate(design.out_val, inp))
    assert srv.stats.shed == 4
    assert srv.admission.stats["conv"].shed_rate == 4
    assert any("shed=4" in ln for ln in srv.health.report_lines())


# ---- warmup-before-traffic ----

def test_warmup_compiles_every_bucket_before_traffic(lowering_cases):
    """start() pre-compiles every (signature, pow2-batch) bucket of the
    registered warm inputs; live traffic then adds no new jit entries."""
    design, inputs_fn = lowering_cases["stereo"]
    warm = inputs_fn(np.random.RandomState(0))
    srv = FrameServer(ServeConfig(max_batch=4))
    srv.register(design, name="stereo", backend="jax", warm_inputs=[warm])
    assert srv.stats.warmup_done == 0
    srv.start()
    try:
        # pow2 buckets for max_batch=4: sizes 1, 2, 4
        assert srv.stats.warmup_total == 3
        assert srv.stats.warmup_done == 3
        assert srv.stats.warmup_s > 0
        assert srv.health.ready
        lp = srv._apps["stereo"].compiled
        keys_before = {k for k in lp.signatures if k[0] == "serve"}
        assert keys_before, "warmup left no serve-mode jit entries"
        frames = [inputs_fn(np.random.RandomState(i)) for i in range(7)]
        for f in srv.submit_many(frames):
            f.result(timeout=300)
        keys_after = {k for k in lp.signatures if k[0] == "serve"}
        assert keys_after == keys_before  # traffic compiled nothing new
        assert any("warmup: 3/3" in ln for ln in srv.stats.report_lines())
    finally:
        srv.close()


def test_no_warmup_config_skips_precompile(lowering_cases):
    design, _ = lowering_cases["convolution"]
    srv = FrameServer(ServeConfig(warmup=False))
    srv.register(design, name="conv", backend="jax",
                 warm_inputs=[{"convolution.in": np.zeros((8, 8),
                                                          np.int64)}])
    with srv:
        assert srv.stats.warmup_done == 0 and srv.stats.warmup_total == 0


# ---- trace capture / replay ----

def test_trace_roundtrip_and_scaling(tmp_path):
    tr = ServeTrace()
    for i, (app, pri) in enumerate([("a", HIGH), ("b", LOW), ("a", NORMAL)]):
        tr.record(0.5 * i, app, pri)
    p = str(tmp_path / "trace.json")
    tr.save(p)
    back = ServeTrace.load(p)
    assert [(e.t, e.app, e.priority) for e in back.events] == \
        [(e.t, e.app, e.priority) for e in tr.events]
    assert back.mean_gap_s() == pytest.approx(0.5)
    fast = back.scaled(4)
    assert fast.mean_gap_s() == pytest.approx(0.125)
    assert [e.app for e in fast.events] == ["a", "b", "a"]
    # cycle mapping: mean gap lands exactly on mean_gap_cycles
    cyc = back.arrival_cycles(mean_gap_cycles=64.0)
    assert list(cyc) == [0, 64, 128]


def test_replay_ingest_burst_vs_spread():
    """Measured burstiness matters: the same frame count arriving as one
    burst marks the ingest FIFO far higher than evenly spread arrivals —
    the information a Poisson mean would wash out."""
    from fractions import Fraction

    from repro.hwsim import replay_ingest
    spread = replay_ingest(np.arange(32) * 16, Fraction(1, 8), capacity=64)
    burst = replay_ingest(np.zeros(32, np.int64), Fraction(1, 8),
                          capacity=64)
    assert spread.completed and burst.completed
    assert burst.source == "trace"
    assert burst.hwm > spread.hwm
    assert burst.hwm >= 24                 # nearly the whole burst resident
    # deterministic: identical inputs, identical marks
    again = replay_ingest(np.zeros(32, np.int64), Fraction(1, 8),
                          capacity=64)
    assert (again.hwm, again.cycles) == (burst.hwm, burst.cycles)


def test_server_records_trace_and_replays_through_ingest(lowering_cases):
    design, inputs_fn = lowering_cases["convolution"]
    frames = [inputs_fn(np.random.RandomState(i)) for i in range(8)]
    cfg = ServeConfig(max_batch=4, max_delay_ms=5.0)
    with design.serve(config=cfg) as srv:
        for f in srv.submit_many(frames):
            f.result(timeout=300)
        assert len(srv.trace) == 8
        assert all(e.app for e in srv.trace.events)
        res = srv.replay_trace_ingest(service_fps=400.0)
        assert res.source == "trace" and res.completed
        assert srv.stats.predicted_queue_hw == res.hwm
        # deterministic for a fixed trace + explicit service rate
        res2 = srv.replay_trace_ingest(service_fps=400.0)
        assert (res2.hwm, res2.cycles) == (res.hwm, res.cycles)
    with pytest.raises(ValueError):
        FrameServer(ServeConfig()).replay_trace_ingest(trace=ServeTrace())


# ---- typed options API ----

def test_frame_server_loose_kwargs_deprecated():
    with pytest.warns(DeprecationWarning):
        srv = FrameServer(max_batch=2)
    assert srv.config.max_batch == 2
    with pytest.raises(TypeError):
        FrameServer(ServeConfig(), max_batch=2)
    with pytest.raises(TypeError):
        ServeConfig(max_bach=2)               # typo: typed config catches it


def test_design_serve_loose_kwargs_deprecated(lowering_cases):
    design, inputs_fn = lowering_cases["convolution"]
    with pytest.warns(DeprecationWarning):
        srv = design.serve(max_batch=2)
    try:
        assert srv.config.max_batch == 2
    finally:
        srv.close()
    with pytest.raises(TypeError):
        design.serve(config=ServeConfig(), max_batch=2)


def test_serve_numpy_backend_swap_noted():
    """serve() on a numpy-backend design serves through jax — and says so
    in design.notes / ServeStats instead of swapping silently."""
    from repro.apps import BENCH_CASES
    from repro.core import compile_pipeline
    uf, inputs_fn = BENCH_CASES["convolution"]()
    design = compile_pipeline(uf)                # fresh: fixture is shared
    assert design.backend == "numpy"
    notes_before = list(design.notes)
    with design.serve(config=ServeConfig(max_batch=2)) as srv:
        f = srv.submit(inputs_fn(np.random.RandomState(0)))
        f.result(timeout=300)
        assert srv.stats.backend == "jax"
    note = [n for n in design.notes if "swapped to 'jax'" in n]
    assert len(note) == 1
    assert any("backend=jax" in ln for ln in srv.stats.report_lines())
    # idempotent: a second serve() does not duplicate the note
    with design.serve(config=ServeConfig(max_batch=2)):
        pass
    assert design.notes.count(note[0]) == 1
    assert note[0] not in notes_before


def test_rolling_partial_hold_window():
    """A partial bucket is top-up eligible only after partial_hold_s —
    the batching window that keeps burst arrivals from shattering into
    singleton batches; full and deadline-expired buckets are unaffected."""
    b = MicroBatcher(max_batch=4, max_delay_s=10.0)
    f = _frame()
    b.put(_req("a", f, t=100.0), now=100.0)
    assert b.take(now=100.001, allow_partial=True,
                  partial_hold_s=0.002) is None      # 1ms < 2ms hold
    assert b.next_topup_ready(0.002) == pytest.approx(100.002)
    got = b.take(now=100.0021, allow_partial=True, partial_hold_s=0.002)
    assert [r.app for r in got] == ["a"] and b.topup_flushes == 1
    # a full bucket ignores the hold entirely
    for i in range(4):
        b.put(_req("b", f, t=200.0), now=200.0)
    assert len(b.take(now=200.0, allow_partial=True,
                      partial_hold_s=9.0)) == 4
    # so does a deadline-expired one
    b.put(_req("c", f, t=300.0), now=300.0)
    assert b.take(now=310.0, partial_hold_s=9e9) is not None
