"""The trace reduction against a small trace recorded on a TPU v5e: FLOW
at 256x32 served through the frame server, batches of 2 to 8 frames, with
the window span ``bench.window`` on the host."""
import os

import pytest

import devtrace

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "flow_256x32.xplane.pb")


@pytest.fixture(scope="module")
def planes():
    from jax.profiler import ProfileData
    return list(ProfileData.from_file(TRACE).planes)


def brute_union(intervals):
    """Busy time by walking every boundary: independent of merge()."""
    pts = sorted({p for iv in intervals for p in iv})
    total = 0
    for a, b in zip(pts, pts[1:]):
        if any(s <= a and b <= e for s, e in intervals):
            total += b - a
    return total


def test_merge():
    assert devtrace.merge([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert devtrace.merge([]) == []


def test_op_names_and_kernels():
    name = ('%vmap_mk0_.1 = (f32[8,32,256]) custom-call(s32[8,48,256] %p), '
            'custom_call_target="tpu_custom_call", api_version=API_VERSION')
    assert devtrace.is_pallas(name)
    assert devtrace.short_name(name) == "vmap_mk0_.1"
    assert not devtrace.is_pallas('%custom-call = s64[8] custom-call(u32[8] '
                                  '%x), custom_call_target="X64Combine"')


def test_reduction_of_the_recorded_trace(planes):
    red = devtrace.reduce_planes(planes, "bench.window")
    t0, t1 = devtrace.window_span(planes, "bench.window")
    ops = devtrace.device_ops(planes, t0, t1)
    assert list(ops) == ["/device:TPU:0"]
    iv = [(s, e) for s, e, _, _ in ops["/device:TPU:0"]]
    assert red["chips"] == 1
    assert red["window_s"] == pytest.approx((t1 - t0) / 1e9)
    assert red["busy_s"] == pytest.approx(brute_union(iv) / 1e9)
    assert 0 < red["busy_s"] < red["window_s"]
    # the kernel and the XLA ops around it partition the op time
    total = sum(e - s for s, e in iv) / 1e9
    assert red["pallas_s"] + red["xla_s"] == pytest.approx(total)
    top = red["breakdown"]["device_ops"]
    assert top[0][0] == "vmap_mk0_.1"
    assert top[0][1] == pytest.approx(red["pallas_s"])
    assert [n for n, _ in top] == [n for n, _ in sorted(top,
                                                        key=lambda x: -x[1])]
    # the recorded numbers, so a change to the reduction shows
    assert red["ops"] == 40
    assert red["pallas_s"] == pytest.approx(6.55e-05)
    assert red["xla_s"] == pytest.approx(3.924e-06)
    assert red["busy_s"] == pytest.approx(6.9424e-05)
    assert red["window_s"] == pytest.approx(0.054397259)


def test_idle_gaps_are_named_by_the_host(planes):
    red = devtrace.reduce_planes(planes, "bench.window")
    gaps = red["breakdown"]["idle_gaps"]
    assert 0 < len(gaps) <= devtrace.TOP
    secs = [s for _, s in gaps]
    assert secs == sorted(secs, reverse=True)
    assert sum(secs) <= red["window_s"] - red["busy_s"] + 1e-9
    # the longest gap is the host reading a batch back
    assert gaps[0][0] == "np.asarray(jax.Array)"
    assert gaps[0][1] == pytest.approx(0.019382696)


def test_missing_window_is_an_error(planes):
    with pytest.raises(ValueError):
        devtrace.reduce_planes(planes, "no.such.span")
