"""The reduction from a trace to numbers, on a small trace recorded on the
TPU v5e during PR 26 (benchmarks/tools/record_fixture.py: three calls of
`jit_fixture_step`, two fusions each) and on made-up events. The numbers
were read off the trace by hand (tools/dump_trace.py)."""
import os

import pytest
from harness import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(HERE, "data", "small.xplane.pb")


@pytest.fixture(scope="module")
def small():
    return xplane.read(SMALL, marks=("bench_clock_sync",))


def test_planes_and_mark(small):
    chips, marks = small
    assert [c["name"] for c in chips] == ["/device:TPU:0"]
    assert len(chips[0]["ops"]) == 12 and len(chips[0]["modules"]) == 3
    assert marks == {"bench_clock_sync": 43256597.0}


def test_module_time(small):
    chips, _ = small
    assert xplane.module_durations(chips, r"fixture_step") == \
        [27346e-9, 27366e-9, 27485e-9]
    assert xplane.module_durations(chips, r"no_such_program") == []


def test_busy_is_the_union_of_the_ops(small):
    chips, _ = small
    # per call: copy-start, copy-done, convolution_tanh_fusion, fusion;
    # (14+3+14704+12614) + (13+3+14721+12616) + (14+3+14843+12615) ns
    assert xplane.busy_seconds(chips) == pytest.approx(82163e-9, rel=1e-9)
    # first start 43192958, last end 50195422 + 12615
    assert xplane.extent_seconds(chips) == pytest.approx(
        (50195422 + 12615 - 43192958) * 1e-9, rel=1e-9)


def test_per_name_sums(small):
    chips, _ = small
    top = dict(xplane.top_ops(chips, 10))
    # a fusion is labelled with its kind: kOutput fuses into a matrix product
    assert top["convolution_tanh_fusion (kOutput)"] == pytest.approx(44268e-9)
    assert top["fusion (kOutput)"] == pytest.approx(37845e-9)
    assert xplane.kernel_seconds(chips, r"^fusion") == (pytest.approx(37845e-9), 3)


def test_idle_gaps_are_named_by_the_host_span(small):
    chips, marks = small
    # the two long gaps lie between the calls: 46765193 - (43207682 + 12614)
    # and 50180560 - (46779935 + 12616) ns
    zero = marks["bench_clock_sync"]
    gap1 = (43220296 + (46765193 - 43220296) / 2 - zero) / 1e9
    spans = [("sleeping", gap1 - 1e-4, gap1 + 1e-4)]
    gaps = dict(xplane.idle_gaps(chips, spans, offset_ns=zero))
    assert gaps["sleeping"] == pytest.approx((46765193 - 43220296) * 1e-9)
    assert gaps["unattributed"] == pytest.approx(
        (50180560 - 46792551 + 6) * 1e-9, rel=1e-3)


def test_union_and_self_time_on_made_up_events():
    assert xplane.union_seconds([(0, 10), (5, 20), (30, 40)]) == 30e-9
    # a while of 100 ns encloses two bodies of 30 ns and 50 ns
    ops = [("while.1", 0.0, 100.0, ""), ("fusion.2", 10.0, 30.0, ""),
           ("flash_fwd.3", 45.0, 50.0, xplane.KERNEL_TARGET)]
    got = {n: (s, leaf) for n, s, leaf, _d in xplane.self_times(ops)}
    assert got == {"while.1": (20.0, False), "fusion.2": (30.0, True),
                   "flash_fwd.3": (50.0, True)}
    chips = [{"name": "x", "ops": ops, "modules": []}]
    assert xplane.busy_seconds(chips) == 100e-9
    assert dict(xplane.top_ops(chips))["flash_fwd (kernel)"] == 50e-9


def test_short_name():
    text = ('%jvp_flash_fwd_.18 = (bf16[48,2048,128]{2,1,0}) custom-call('
            'bf16[48,2048,128]{2,1,0} %bitcast.2934), '
            'custom_call_target="tpu_custom_call"')
    assert xplane.short_name(text) == "jvp_flash_fwd_.18"
