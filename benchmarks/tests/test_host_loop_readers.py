"""The readers of the serving engine's host loop, on a `SpanLog` filled by
hand and on the recorded fixture's chip; each returns None where the
program has no such span; and a traced rehearsal of a serving cell prints
every per-layer metric that BENCHMARK.json gives it."""
import os

import pytest
import presets
import run
from harness import host_loop, spec, xplane
from harness.spans import SpanLog

SMALL = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small.xplane.pb")
NEW = ["engine_gap_ms", "decode_dispatch_ms", "idle_attributed_share",
       "stream_lag_ms_p95", "prefill_useful_share"]
WINDOW = (100.0, 200.0)


def span(log, name, t0, t1, sid=None, parent=None, stamped=True, **fields):
    """One span as the sink sees it: stamped a little after its end."""
    f = dict(fields, name=name, seconds=round(t1 - t0, 6))
    if stamped:
        f.update(t0=t0, t1=t1)
    if sid:
        f["span_id"] = sid
    if parent:
        f["parent_id"] = parent
    log.spans.append((name, t1 + 1e-4 - (t1 - t0), t1 + 1e-4, f))


def loop_pass(log, t, step="decode_step", sid="s", wait=False, **fields):
    """One pass of the loop from `t`: admit 1 ms, then either idle_wait
    20 ms, or step_prepare 2 ms, the step of 40 ms (dispatch 5, fetch 35)
    and emit 3 ms. Returns the pass's end."""
    span(log, "admit", t, t + 0.001, sid + "a", replica=0)
    if wait:
        span(log, "idle_wait", t + 0.001, t + 0.021, sid + "w", replica=0)
        return t + 0.021
    span(log, "step_prepare", t + 0.001, t + 0.003, sid + "p", replica=0)
    span(log, "dispatch", t + 0.003, t + 0.008, sid + "d", parent=sid)
    span(log, "fetch", t + 0.008, t + 0.043, sid + "f", parent=sid)
    span(log, step, t + 0.003, t + 0.043, sid, replica=0, **fields)
    span(log, "emit", t + 0.043, t + 0.046, sid + "e", replica=0)
    return t + 0.046


def hand_made_log():
    """Four decode passes, an idle wait, a prefill pass and a decode pass."""
    log, t = SpanLog(), 110.0
    for i in range(4):
        t = loop_pass(log, t, sid=f"d{i}", n_active=2, slots=[0, 1])
    t = loop_pass(log, t, sid="w0", wait=True)
    t = loop_pass(log, t, "prefill_chunk", sid="p0", bucket=[1, 64], n_real=40)
    t = loop_pass(log, t, sid="d4", n_active=1, slots=[0])
    return log


def reader_values(log, **more):
    facts = dict({"spans": log, "window": WINDOW}, **more)
    return {m: spec.layer_reader(m)(facts) for m in NEW}


def test_readers_on_a_hand_made_loop():
    log = hand_made_log()
    for lags in ([0.001, 0.002, 0.003], [0.004] * 16 + [0.050]):
        log.events.append(("stream", 150.0, {"id": "r", "n": len(lags),
                                             "lag_s": lags, "parse_s": 1e-4}))
    log.events.append(("stream", 250.0, {"id": "late", "n": 1,
                                         "lag_s": [9.0], "parse_s": 1e-4}))
    got = reader_values(log)
    # between two steps: emit 3 + admit 1 + step_prepare 2 ms, four times;
    # the pair with the idle wait between (27 ms apart) is left out
    assert host_loop.step_gaps(log, *WINDOW) == pytest.approx([0.006] * 4)
    assert got["engine_gap_ms"] == pytest.approx(6.0)
    # the prefill's dispatch is not a decode step's
    assert len(host_loop.children_of(log, "decode_step", "dispatch",
                                     *WINDOW)) == 5
    assert got["decode_dispatch_ms"] == pytest.approx(5.0)
    # 20 lags in the window: the 95th percentile by nearest rank is the 19th
    assert got["stream_lag_ms_p95"] == pytest.approx(4.0)
    assert got["prefill_useful_share"] == pytest.approx(100.0 * 40 / 64)
    assert got["idle_attributed_share"] is None     # nothing was traced


def test_loop_readers_stop_where_the_trace_was_stopped():
    """After the profiler stops, its export slows the host for the rest of
    the window: the loop's own times are read before that."""
    log = hand_made_log()
    t = 150.0
    for i in range(8):      # the same loop, three times slower
        span(log, "dispatch", t, t + 0.015, f"x{i}d", parent=f"x{i}")
        span(log, "decode_step", t, t + 0.050, f"x{i}", replica=0)
        t += 0.070
    log.events.append(("stream", 120.0, {"id": "a", "lag_s": [0.002] * 10}))
    log.events.append(("stream", 160.0, {"id": "b", "lag_s": [0.009] * 10}))
    whole = reader_values(log)
    early = reader_values(log, traced={"t_on": 110.0, "t_off": 140.0})
    assert host_loop.quiet_window({"window": WINDOW}) == WINDOW
    assert host_loop.quiet_window(
        {"window": WINDOW, "traced": {"t_off": 140.0}}) == (100.0, 140.0)
    assert whole["decode_dispatch_ms"] == pytest.approx(15.0)   # 8 of 13
    assert early["decode_dispatch_ms"] == pytest.approx(5.0)
    assert whole["stream_lag_ms_p95"] == pytest.approx(9.0)
    assert early["stream_lag_ms_p95"] == pytest.approx(2.0)
    assert whole["engine_gap_ms"] == pytest.approx(20.0)        # 7 of 12
    assert early["engine_gap_ms"] == pytest.approx(6.0)


def test_leaves_are_the_spans_without_children():
    log = hand_made_log()
    names = {n for n, _a, _b in host_loop.leaf_spans(log)}
    assert names == {"admit", "step_prepare", "dispatch", "fetch", "emit",
                     "idle_wait"}
    # the stamps are read, not the sink's reconstruction
    assert host_loop.leaf_spans(log)[0] == ("admit", 110.0, 110.001)


def test_a_program_without_the_new_spans_reads_none_or_its_old_spans():
    """The parent commit: `decode_step` and `prefill_chunk` alone, no
    stamps, no ids to join on, no `n_real`, no `stream` events."""
    log, t = SpanLog(), 110.0
    for i in range(3):
        span(log, "decode_step", t, t + 0.040, stamped=False, n_active=2)
        t += 0.047
    span(log, "prefill_chunk", t, t + 0.020, stamped=False, bucket=[1, 64])
    got = reader_values(log)
    assert got["engine_gap_ms"] == pytest.approx(7.0)   # from the sink's times
    assert got["decode_dispatch_ms"] is None
    assert got["stream_lag_ms_p95"] is None
    assert got["prefill_useful_share"] is None
    assert reader_values(None) == dict.fromkeys(NEW)    # an untraced run


def test_idle_attributed_share_on_the_recorded_chip():
    chips, marks = xplane.read(SMALL, marks=("bench_clock_sync",))
    zero = marks["bench_clock_sync"]
    # the two long gaps lie between the fixture's three calls
    # (test_xplane.py); a leaf span of the program covers the first's middle
    gap1_ns, gap2_ns = 46765193 - 43220296, 50180560 - 46792551
    mid1 = (43220296 + gap1_ns / 2 - zero) / 1e9
    t_sync = 500.0
    traced = {"chips": chips, "t_sync": t_sync, "sync_ns": zero,
              "t_on": t_sync - 1.0, "t_off": t_sync + 1.0, "window_s": 2.0}
    log = SpanLog()
    span(log, "emit", t_sync + mid1 - 1e-3, t_sync + mid1 + 1e-3, "e1",
         parent="outer")
    span(log, "outer", t_sync - 1.0, t_sync + 1.0, "outer")   # not a leaf
    got = reader_values(log, traced=traced)["idle_attributed_share"]
    assert got == pytest.approx(100.0 * gap1_ns / (gap1_ns + gap2_ns + 6),
                                rel=1e-3)
    # the same spans read as the breakdown reads them name the whole gap
    both = dict(xplane.idle_gaps(
        chips, [(n, a - t_sync, b - t_sync) for n, a, b, _f in log.spans],
        offset_ns=zero))
    assert set(both) == {"emit", "outer"}
    assert reader_values(SpanLog(), traced=traced)[
        "idle_attributed_share"] is None                # no span, no share


def test_benchmark_json_gives_both_serving_cells_the_new_metrics():
    bench = spec.load_benchmark()
    for cell, n in (("serve_1p3b_chat", 14), ("serve_1p3b_batchgen", 11)):
        names = [m["name"] for m in spec.metrics_for(bench, "per_layer", cell)]
        assert names[-5:] == NEW and len(names) == n
    assert not set(NEW) & {m["name"] for m in spec.metrics_for(
        bench, "per_layer", "train_590m_seq2048")}


@pytest.mark.parametrize("like,mix", [
    ("serve_1p3b_chat", presets.OPEN_MIX),
    ("serve_1p3b_batchgen", presets.CLOSED_MIX)])
def test_traced_rehearsal_prints_every_metric_of_a_serving_cell(like, mix):
    bench = presets.bench_with("s", like)
    line = run.execute("s", 27, 3, 1, bench=bench, config=presets.TINY_SERVE,
                       traffic=mix, limits=presets.SERVE_LIMITS,
                       rehearsal=True)
    assert line["correct"] is True, line["checks"]
    wanted = {m["name"] for m in spec.metrics_for(bench, "per_layer", "s")}
    # shares of a peak are never printed from a CPU run
    assert wanted - set(line["metrics"]) == {"serve_mfu",
                                             "decode_step_roofline"}
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert 0 < got["prefill_useful_share"] <= 100
    assert 0 <= got["idle_attributed_share"] <= 100
    assert got["engine_gap_ms"] > 0 and got["decode_dispatch_ms"] > 0
    assert got["stream_lag_ms_p95"] > 0
    named = [who for who, _s in line["breakdown"]["idle_gaps"]]
    assert set(named) & {"dispatch", "fetch", "emit", "admit",
                         "step_prepare", "idle_wait"}
