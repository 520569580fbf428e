"""Work counters and the peaks table against hand counts."""
import os

import pytest

import work
from run import load_module

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def ref(name):
    return load_module(os.path.join(CONFIGS, name + ".py"))


def test_flow_ops_by_hand():
    # per pixel: two 3x3 gradients (9 products + 9 summed each), the
    # temporal difference, five window sums (product, 64 widenings, 64
    # summed), five conversions, det/nu/nv (3 each), two divides
    per_px = 2 * (9 + 9) + 1 + 5 * (1 + 64 + 64) + 5 + 9 + 2
    assert per_px == 698
    assert work.ops_per_frame(ref("flow_1080p").dag(w=4, h=2)) == 8 * 698


def test_stereo_ops_by_hand():
    # per pixel and disparity: |l - r|, widening, 64 summed; arg-min reads
    # one cost per disparity
    nd = 4
    per_px = nd + nd + 64 * nd + nd
    assert work.ops_per_frame(ref("stereo_720x400").dag(w=3, h=2, nd=nd)) \
        == 6 * per_px


def test_bytes_at_declared_widths():
    # u8 pair in, two f32 planes out: 2 + 8 bytes a pixel
    assert work.bytes_per_frame(ref("flow_1080p").io_bits(w=4, h=2)) == 80
    # u8 pair in, a u6 disparity out: 2 + 1 bytes a pixel
    assert work.bytes_per_frame(
        ref("stereo_720x400").io_bits(w=3, h=2, nd=64)) == 18


def test_paper_sizes():
    flow = ref("flow_1080p")
    assert work.ops_per_frame(flow.dag(w=1920, h=1080)) == 698 * 1920 * 1080
    assert work.bytes_per_frame(flow.io_bits(w=1920, h=1080)) \
        == 10 * 1920 * 1080
    stereo = ref("stereo_720x400")
    assert work.ops_per_frame(stereo.dag(w=720, h=400)) == 4288 * 720 * 400


def test_least_time_names_its_bound():
    peak = work.peaks("TPU v5 lite")
    assert peak == {"ops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = work.least_time_s(197e12, 1, peak)
    assert (t, bound) == (1.0, "compute")
    t, bound = work.least_time_s(1, 819e9, peak)
    assert (t, bound) == (1.0, "memory")


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v4")
