"""Tier-1 gate for the fleet trace timeline (ISSUE 15): shard merge
ordering, correlation-field stamping, span-tree reconstruction, the
anomaly detectors (straggler/hang from an injected `pN:hang@stepK`
timeline, post-warmup retrace from a doctored late-compile shard,
input_wait/queue spikes), Perfetto export schema validity, the
tracetool CLI contract, the rolling-histogram /metrics registry, and
the artifact loader's sharded-input fallback.

Everything here is pure-host (no jax): the detectors must be provable
from the JSONL alone — that is the point of the subsystem."""

import json
import os
import subprocess
import sys
import threading

import pytest

from deeplearning4j_tpu.telemetry import Recorder
from deeplearning4j_tpu.telemetry import trace as trace_mod
from deeplearning4j_tpu.telemetry.metrics import (CONTENT_TYPE,
                                                  MetricsRegistry,
                                                  parse_exposition)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACETOOL = os.path.join(ROOT, "tools", "tracetool.py")


# ------------------------------------------------------------ fixtures

def _write_shard(path, events):
    with open(path, "w") as fh:
        for ev in events:
            fh.write(json.dumps(ev) + "\n")


def _step(run, seq, it, ts, **extra):
    return {"event": "step", "run": run, "seq": seq, "iteration": it,
            "ts": ts, "trace_id": f"step-{it}", **extra}


def _fleet_shards(tmp_path, *, hang_at=None, skew_s=0.0, steps=8):
    """Two per-process shards of a training fleet: p0 runs to `steps`;
    p1 optionally hangs at step `hang_at` (its events just STOP — the
    SIGKILL signature) or completes each step `skew_s` late."""
    base = str(tmp_path / "telemetry.jsonl")
    p0, p1 = [], []
    t0 = 1000.0
    for s in range(1, steps + 1):
        ts = t0 + s * 0.1
        p0.append(_step("runA", s, s, ts))
        if hang_at is not None and s >= hang_at:
            continue
        p1.append(_step("runB", s, s, ts + skew_s))
    _write_shard(base + ".p0", p0)
    _write_shard(base + ".p1", p1)
    return base


# ------------------------------------------------------- merge ordering

def test_two_shard_merge_is_causal_and_process_tagged(tmp_path):
    base = str(tmp_path / "t.jsonl")
    _write_shard(base + ".p0", [
        {"event": "meta", "run": "a", "seq": 0, "ts": 10.0},
        {"event": "step", "run": "a", "seq": 1, "iteration": 1,
         "ts": 12.0},
        # same ts as p1's second event: per-process seq breaks the tie
        {"event": "step", "run": "a", "seq": 2, "iteration": 2,
         "ts": 13.0},
    ])
    _write_shard(base + ".p1", [
        {"event": "meta", "run": "b", "seq": 0, "ts": 11.0},
        {"event": "step", "run": "b", "seq": 1, "iteration": 1,
         "ts": 13.0},
    ])
    tl = trace_mod.load_timeline(base)
    assert tl.processes == ["p0", "p1"]
    assert [(e["process"], e["ts"]) for e in tl.events] == [
        ("p0", 10.0), ("p1", 11.0), ("p0", 12.0), ("p0", 13.0),
        ("p1", 13.0)]
    # one process's stream never reorders, whatever the clock says
    p0_seqs = [e["seq"] for e in tl.events if e["process"] == "p0"]
    assert p0_seqs == sorted(p0_seqs)


def test_discover_shards_prefers_unsuffixed_plus_shards(tmp_path):
    base = str(tmp_path / "t.jsonl")
    _write_shard(base, [{"event": "meta", "seq": 0, "ts": 1.0}])
    _write_shard(base + ".p0", [{"event": "meta", "seq": 0, "ts": 2.0}])
    labels = [l for l, _ in trace_mod.discover_shards(base)]
    assert labels == ["main", "p0"]
    with pytest.raises(FileNotFoundError):
        trace_mod.discover_shards(str(tmp_path / "absent.jsonl"))


def test_merge_skips_garbage_and_partial_lines(tmp_path):
    base = str(tmp_path / "t.jsonl")
    with open(base, "w") as fh:
        fh.write("not json\n")
        fh.write('{"event": "meta", "seq": 0, "ts": 1.0}\n')
        fh.write('{"event": "step", "seq": 1, "ts": 2.0, "iterat')  # cut
    tl = trace_mod.load_timeline(base)
    assert len(tl.events) == 1


# ------------------------------------------- correlation + span trees

def test_recorder_stamps_span_ids_and_nesting():
    rec = Recorder(path=None)
    with rec.span("forward", bucket=[2, 8]):
        with rec.span("compile"):
            pass
        rec.event("page_pool", pages_in_use=1)
    spans = [e for e in rec.events if e["event"] == "span"]
    fwd = next(e for e in spans if e["name"] == "forward")
    comp = next(e for e in spans if e["name"] == "compile")
    pool = next(e for e in rec.events if e["event"] == "page_pool")
    assert comp["parent_id"] == fwd["span_id"]
    assert pool["parent_id"] == fwd["span_id"]
    assert "parent_id" not in fwd


def test_trace_context_crosses_threads():
    """The batch handoff idiom: a trace rooted on one thread, continued
    on another through the explicit trace() context."""
    rec = Recorder(path=None)
    root = rec.new_span_id()
    rec.event("span", name="batch_assemble", ok=True, seconds=0.001,
              trace_id="b1", span_id=root)

    def worker():
        with rec.trace("b1", parent_id=root):
            with rec.span("forward"):
                pass
            rec.request("r1", ok=True, total_s=0.01)

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    fwd = next(e for e in rec.events
               if e["event"] == "span" and e["name"] == "forward")
    req = next(e for e in rec.events if e["event"] == "request")
    assert fwd["trace_id"] == req["trace_id"] == "b1"
    assert fwd["parent_id"] == root
    tl = trace_mod.timeline_from_events(rec.events)
    roots = trace_mod.span_tree(tl, "b1")
    assert len(roots) == 1
    names = {c["event"].get("name") or c["event"]["event"]
             for c in roots[0]["children"]}
    assert names == {"forward", "request"}
    rendered = trace_mod.render_tree(roots)
    assert "batch_assemble" in rendered and "request" in rendered


def test_step_events_carry_cross_process_trace_id():
    rec = Recorder(path=None)
    rec.step(7)
    assert rec.events[-1]["trace_id"] == "step-7"


# --------------------------------------------------- straggler detection

def test_straggler_hang_detected_from_jsonl_alone(tmp_path):
    """The injected `p1:hang@step5` fault timeline: p1's events stop at
    step 4 while p0 runs to 8 — the detector names the process and the
    step it never completed, from the shards alone."""
    base = _fleet_shards(tmp_path, hang_at=5, steps=8)
    findings = trace_mod.detect_anomalies(
        trace_mod.load_timeline(base),
        trace_mod.AnomalyConfig(straggler_skew_ms=100.0))
    stalls = [f for f in findings if f["anomaly"] == "straggler"
              and f["mode"] == "stall"]
    assert len(stalls) == 1
    f = stalls[0]
    assert f["process"] == "p1" and f["step"] == 5
    assert f["last_step"] == 4 and f["fleet_step"] == 8
    assert f["skew_ms"] > 100.0


def test_straggler_skew_detected_and_thresholded(tmp_path):
    base = _fleet_shards(tmp_path, skew_s=0.5, steps=4)
    tl = trace_mod.load_timeline(base)
    tight = trace_mod.detect_stragglers(
        tl, trace_mod.AnomalyConfig(straggler_skew_ms=100.0))
    assert len(tight) == 4
    assert all(f["process"] == "p1" and f["mode"] == "skew"
               and f["skew_ms"] == pytest.approx(500.0)
               for f in tight)
    loose = trace_mod.detect_stragglers(
        tl, trace_mod.AnomalyConfig(straggler_skew_ms=2000.0))
    assert loose == []


def test_clean_fleet_timeline_yields_zero_anomalies(tmp_path):
    base = _fleet_shards(tmp_path, steps=8)
    assert trace_mod.detect_anomalies(trace_mod.load_timeline(base)) == []


def test_single_process_never_flags_stragglers(tmp_path):
    base = str(tmp_path / "t.jsonl")
    _write_shard(base, [_step("a", i, i, 100.0 + i * 60)
                        for i in range(1, 5)])
    assert trace_mod.detect_stragglers(
        trace_mod.load_timeline(base)) == []


# ----------------------------------------------------- retrace detection

def _serving_events(*, late_compile):
    evs = [
        {"event": "span", "name": "compile", "warmup": True, "run": "s",
         "seq": 0, "ts": 1.0, "seconds": 0.5, "bucket": [1, 8]},
        {"event": "span", "name": "compile", "warmup": True, "run": "s",
         "seq": 1, "ts": 2.0, "seconds": 0.4, "bucket": [2, 8]},
        {"event": "request", "id": "r0", "ok": True, "run": "s",
         "seq": 2, "ts": 3.0, "total_s": 0.01},
    ]
    if late_compile:
        evs.append({"event": "span", "name": "compile", "run": "s",
                    "seq": 3, "ts": 4.0, "seconds": 0.6,
                    "bucket": [4, 8]})
    return evs


def _mem(run, seq, ts, live, *, devices=None, **extra):
    """One ledger-annotated memory event (telemetry/memstat.py shape)."""
    return {"event": "memory", "run": run, "seq": seq, "ts": ts,
            "live_array_bytes": int(live),
            "ledger": {"params": int(live) // 2,
                       "activations": int(live) - int(live) // 2},
            "ledger_total_bytes": int(live), "source": "fit",
            "devices": devices or {}, **extra}


def test_retrace_detected_from_doctored_late_compile_shard(tmp_path):
    base = str(tmp_path / "t.jsonl")
    _write_shard(base, _serving_events(late_compile=True))
    findings = trace_mod.detect_retraces(trace_mod.load_timeline(base))
    assert len(findings) == 1
    assert findings[0]["bucket"] == [4, 8]


def test_warmup_compiles_and_training_compiles_never_flag(tmp_path):
    base = str(tmp_path / "t.jsonl")
    # a training run: compile WITHOUT warmup flags, steps after — the
    # expected first-dispatch cost, not a retrace
    _write_shard(base, [
        {"event": "span", "name": "compile", "run": "t", "seq": 0,
         "ts": 1.0, "seconds": 2.0},
        _step("t", 1, 1, 2.0),
        {"event": "span", "name": "step_scan", "run": "t", "seq": 2,
         "ts": 3.0, "seconds": 0.1},
    ] + _serving_events(late_compile=False))
    assert trace_mod.detect_retraces(trace_mod.load_timeline(base)) == []


def test_retrace_scoped_per_run_in_shared_sweep_log(tmp_path):
    """The bench sweep's shared log interleaves many runs: a warmed
    serving run must not poison a LATER training run's first compile."""
    base = str(tmp_path / "t.jsonl")
    _write_shard(base, _serving_events(late_compile=False) + [
        {"event": "span", "name": "compile", "run": "t2", "seq": 0,
         "ts": 10.0, "seconds": 2.0}])
    assert trace_mod.detect_retraces(trace_mod.load_timeline(base)) == []


# ----------------------------------------------------- spike detection

def test_input_wait_spike_detection_and_warmup_carveout(tmp_path):
    base = str(tmp_path / "t.jsonl")
    waits = [0.4, 0.3, 0.001, 0.002, 0.5, 0.001]  # first two = cold start
    _write_shard(base, [
        {"event": "span", "name": "input_wait", "pipelined": True,
         "run": "a", "seq": i, "ts": 1.0 + i, "seconds": w}
        for i, w in enumerate(waits)
    ] + [  # the synchronous fallback measures conversion, exempt
        {"event": "span", "name": "input_wait", "pipelined": False,
         "run": "a", "seq": 10, "ts": 20.0, "seconds": 5.0}])
    findings = trace_mod.detect_input_wait_spikes(
        trace_mod.load_timeline(base))
    assert len(findings) == 1
    assert findings[0]["wait_ms"] == pytest.approx(500.0)


def test_queue_spike_detection(tmp_path):
    base = str(tmp_path / "t.jsonl")
    _write_shard(base, [
        {"event": "span", "name": "queue", "run": "a", "seq": 0,
         "ts": 1.0, "seconds": 2.0},
        {"event": "span", "name": "queue", "run": "a", "seq": 1,
         "ts": 2.0, "seconds": 0.002},
        {"event": "autoscale", "run": "a", "seq": 2, "ts": 3.0,
         "queue_depth": 100, "action": 1},
        {"event": "autoscale", "run": "a", "seq": 3, "ts": 4.0,
         "queue_depth": 2, "action": 0},
    ])
    findings = trace_mod.detect_queue_spikes(trace_mod.load_timeline(base))
    assert [f["kind"] for f in findings] == ["wait", "depth"]


# ------------------------------------------------------ straggler watch

def test_straggler_watch_emits_each_anomaly_once(tmp_path):
    base = _fleet_shards(tmp_path, hang_at=5, steps=8)
    rec = Recorder(path=None)
    watch = trace_mod.StragglerWatch(
        base, recorder=rec,
        config=trace_mod.AnomalyConfig(straggler_skew_ms=100.0),
        min_interval_s=0.0)
    first = watch.poll(force=True)
    again = watch.poll(force=True)
    assert len(first) == 1 and again == []
    anomalies = [e for e in rec.events if e["event"] == "anomaly"]
    assert len(anomalies) == 1
    assert anomalies[0]["kind"] == "straggler"
    assert anomalies[0]["process"] == "p1"


def test_straggler_watch_tolerates_missing_shards(tmp_path):
    rec = Recorder(path=None)
    watch = trace_mod.StragglerWatch(str(tmp_path / "nope.jsonl"),
                                     recorder=rec, min_interval_s=0.0)
    assert watch.poll(force=True) == []


# --------------------------------------------------- memory detectors

def _leak_shard(tmp_path, *, growth_per_step=1 << 20, steps=8,
                warm_spike=True):
    """A seeded synthetic leak: live bytes climb monotonically every
    sample past the warmup window. JSONL alone — no live process."""
    base = str(tmp_path / "t.jsonl")
    evs = []
    live = 10 << 20
    for s in range(steps):
        if warm_spike and s == 0:
            # warmup allocations dwarf the leak; the warmup slice
            # must hide them
            evs.append(_mem("runL", s, 1000.0 + s, live * 3))
            continue
        evs.append(_mem("runL", s, 1000.0 + s, live))
        live += growth_per_step
    _write_shard(base, evs)
    return base


def test_seeded_leak_detected_from_jsonl_alone(tmp_path):
    base = _leak_shard(tmp_path)
    findings = trace_mod.detect_leaks(trace_mod.load_timeline(base))
    assert len(findings) == 1
    f = findings[0]
    assert f["anomaly"] == "leak"
    assert f["growth_bytes"] >= 4 << 20
    assert f["last_bytes"] > f["first_bytes"]


def test_leak_needs_monotonic_steady_state_growth(tmp_path):
    # a sawtooth (allocations that free) is NOT a leak
    base = str(tmp_path / "t.jsonl")
    vals = [10, 14, 11, 15, 12, 16, 13]
    _write_shard(base, [_mem("runS", i, 1000.0 + i, v << 20)
                        for i, v in enumerate(vals)])
    assert trace_mod.detect_leaks(trace_mod.load_timeline(base)) == []
    # flat steady state is clean too
    base2 = str(tmp_path / "t2.jsonl")
    _write_shard(base2, [_mem("runF", i, 1000.0 + i, 10 << 20)
                         for i in range(8)])
    assert trace_mod.detect_leaks(trace_mod.load_timeline(base2)) == []
    # growth under the floor (a few stray KBs) stays silent
    base3 = str(tmp_path / "t3.jsonl")
    _write_shard(base3, [_mem("runK", i, 1000.0 + i, (10 << 20) + i * 512)
                         for i in range(8)])
    assert trace_mod.detect_leaks(trace_mod.load_timeline(base3)) == []


def test_headroom_breach_detected_and_off_tpu_silent(tmp_path):
    base = str(tmp_path / "t.jsonl")
    hot = {"0": {"bytes_in_use": 95, "bytes_limit": 100,
                 "peak_bytes_in_use": 96}}
    cold = {"0": {"bytes_in_use": 10, "bytes_limit": 100,
                  "peak_bytes_in_use": 12}}
    _write_shard(base, [
        _mem("runH", 0, 1000.0, 1 << 20, devices=cold),
        _mem("runH", 1, 1001.0, 1 << 20, devices=hot),
        _mem("runH", 2, 1002.0, 1 << 20, devices=hot),  # dedup: one finding
    ])
    findings = trace_mod.detect_headroom(trace_mod.load_timeline(base))
    assert len(findings) == 1
    assert findings[0]["anomaly"] == "headroom"
    assert findings[0]["ratio"] == pytest.approx(0.95)
    # off-TPU shards carry no bytes_limit: never a breach
    base2 = str(tmp_path / "t2.jsonl")
    _write_shard(base2, [_mem("runC", 0, 1000.0, 1 << 30)])
    assert trace_mod.detect_headroom(trace_mod.load_timeline(base2)) == []


def test_cost_drift_detected_from_typed_event(tmp_path):
    base = str(tmp_path / "t.jsonl")
    _write_shard(base, [
        {"event": "cost_drift", "run": "runD", "seq": 0, "ts": 1000.0,
         "predicted_bytes": 1000, "measured_bytes": 32000,
         "ratio": 32.0, "factor": 8.0, "source": "placement"},
        {"event": "cost_drift", "run": "runD", "seq": 1, "ts": 1001.0,
         "predicted_bytes": 1000, "measured_bytes": 2000,
         "ratio": 2.0, "factor": 8.0, "source": "placement"},
    ])
    findings = trace_mod.detect_cost_drift(trace_mod.load_timeline(base))
    assert len(findings) == 1  # in-band reconciliation stays silent
    assert findings[0]["anomaly"] == "cost_drift"
    assert findings[0]["ratio"] == pytest.approx(32.0)
    # the acceptance path: the doctored drift gates the CLI from the
    # JSONL alone, and gating on other kinds leaves it informational
    out = _tracetool("check", base, "--fail-on", "cost_drift")
    assert out.returncode == 1, out.stdout
    assert _tracetool("check", base, "--fail-on",
                      "leak,headroom").returncode == 0


def test_cost_drift_join_fallback_from_placement_search(tmp_path):
    """A doctored cost-model drift with NO typed reconciliation: the
    detector joins the placement_search winner's predicted bytes
    against later measured memory events in the same (process, run)."""
    base = str(tmp_path / "t.jsonl")
    search = {"event": "placement_search", "run": "runJ", "seq": 0,
              "ts": 1000.0, "winner": "tp4", "winner_score": 1.0,
              "winner_memory_bytes": 1000.0}
    _write_shard(base, [search,
                        _mem("runJ", 1, 1001.0, 64000)])
    findings = trace_mod.detect_cost_drift(trace_mod.load_timeline(base))
    assert len(findings) == 1
    assert findings[0]["source"] == "join"
    assert findings[0]["ratio"] == pytest.approx(64.0)
    # within-band measurement: clean
    base2 = str(tmp_path / "t2.jsonl")
    _write_shard(base2, [dict(search, run="runK"),
                         _mem("runK", 1, 1001.0, 4000)])
    assert trace_mod.detect_cost_drift(
        trace_mod.load_timeline(base2)) == []


def test_clean_memory_timeline_yields_zero_anomalies(tmp_path):
    """The happy path: warmup spike settling into flat steady state,
    healthy device headroom, in-band reconciliation — zero findings
    across ALL detectors."""
    base = str(tmp_path / "t.jsonl")
    dev = {"0": {"bytes_in_use": 40, "bytes_limit": 100,
                 "peak_bytes_in_use": 45}}
    evs = [_mem("runOK", 0, 1000.0, 30 << 20, devices=dev)]
    evs += [_mem("runOK", i, 1000.0 + i, 10 << 20, devices=dev)
            for i in range(1, 7)]
    evs.append({"event": "cost_drift", "run": "runOK", "seq": 7,
                "ts": 1007.0, "predicted_bytes": 8 << 20,
                "measured_bytes": 12 << 20, "ratio": 1.5,
                "factor": 8.0, "source": "placement"})
    _write_shard(base, evs)
    assert trace_mod.detect_anomalies(trace_mod.load_timeline(base)) == []


def test_memory_watch_emits_each_finding_once(tmp_path):
    base = _leak_shard(tmp_path)
    rec = Recorder(path=None)
    watch = trace_mod.MemoryWatch(base, recorder=rec, min_interval_s=0.0)
    first = watch.poll(force=True)
    again = watch.poll(force=True)
    assert len(first) == 1 and again == []
    anomalies = [e for e in rec.events if e["event"] == "anomaly"]
    assert len(anomalies) == 1 and anomalies[0]["kind"] == "leak"


def test_memory_report_and_metric_rows(tmp_path):
    base = _leak_shard(tmp_path)
    with open(base, "a") as fh:
        fh.write(json.dumps(
            {"event": "cost", "run": "runL", "seq": 99, "ts": 2000.0,
             "entry": "forward", "shape": [4, 16], "flops": 1e9,
             "bytes_accessed": 2e6, "peak_temp_bytes": 4096,
             "alias_bytes": 512}) + "\n")
    tl = trace_mod.load_timeline(base)
    report = trace_mod.memory_report(tl)
    proc = report["processes"]["main"]
    assert proc["samples"] == 8
    assert proc["peak_bytes"] == 30 << 20  # the warmup spike
    assert proc["ledger"]["params"] > 0
    assert report["cost_book"]["forward::[4, 16]"]["flops"] == 1e9
    # what donation bought rides the summary (0 where nothing is donated)
    assert report["cost_book"]["forward::[4, 16]"]["alias_bytes"] == 512
    findings = trace_mod.detect_anomalies(tl)
    lines = trace_mod.metric_lines(tl, findings)
    by_name = {l["metric"]: l for l in lines}
    assert by_name["trace_leak_count"]["value"] == 1
    assert by_name["trace_leak_count"]["lower_is_better"]
    assert by_name["trace_cost_drift_ratio"]["value"] == 0.0
    assert by_name["trace_hbm_peak_bytes"]["value"] == 30 << 20


def test_tracetool_check_fails_on_seeded_leak(tmp_path):
    """The acceptance criterion: a seeded synthetic leak is flagged
    `leak` by `tracetool check --fail-on leak` from JSONL alone."""
    base = _leak_shard(tmp_path)
    out = _tracetool("check", base, "--fail-on", "leak", "--json")
    assert out.returncode == 1, out.stdout
    payload = json.loads(out.stdout)
    assert payload["gating"] == 1
    assert payload["findings"][0]["anomaly"] == "leak"
    # threshold flag: a floor above the seeded growth silences it
    out = _tracetool("check", base, "--fail-on", "leak",
                     "--leak-min-bytes", str(1 << 30))
    assert out.returncode == 0
    # and scoping: the same record gated on other kinds stays 0
    out = _tracetool("check", base, "--fail-on", "retrace,straggler")
    assert out.returncode == 0


def test_tracetool_mem_report_cli(tmp_path):
    base = _leak_shard(tmp_path)
    out = _tracetool("mem", base, "--json")
    assert out.returncode == 0
    report = json.loads(out.stdout)
    assert report["processes"]["main"]["samples"] == 8
    out = _tracetool("mem", base)
    assert out.returncode == 0 and "ledger" in out.stdout


def test_committed_bench_shards_memory_happy_path():
    """Clean committed fixtures stay clean through the new detectors:
    zero leak/headroom/cost_drift findings on the happy path."""
    tl = trace_mod.load_timeline(
        os.path.join(ROOT, "telemetry_bench.jsonl"))
    findings = (trace_mod.detect_leaks(tl)
                + trace_mod.detect_headroom(tl)
                + trace_mod.detect_cost_drift(tl))
    assert findings == []


# ------------------------------------------------------ perfetto export

def test_perfetto_export_schema_validity(tmp_path):
    base = _fleet_shards(tmp_path, steps=3)
    rec_events = _serving_events(late_compile=False)
    rec_events.append(_mem("s", 3, 5.0, 1 << 20))
    _write_shard(base, rec_events)  # unsuffixed joins as "main"
    doc = trace_mod.to_perfetto(trace_mod.load_timeline(base))
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    assert evs, "export must not be empty"
    # round-trips through json
    evs = json.loads(json.dumps(doc))["traceEvents"]
    pids = set()
    counters = []
    for ev in evs:
        assert {"name", "ph", "pid", "tid"} <= set(ev)
        pids.add(ev["pid"])
        if ev["ph"] == "X":
            assert ev["dur"] >= 0 and ev["ts"] >= 0
        elif ev["ph"] == "M":
            assert ev["name"] == "process_name"
        elif ev["ph"] == "C":
            # memory events render as counter tracks: live bytes +
            # the per-subsystem ledger series
            assert ev["name"] == "device_memory"
            assert ev["args"]["live_array_bytes"] == 1 << 20
            assert "ledger_params" in ev["args"]
            counters.append(ev)
        else:
            assert ev["ph"] == "i"
    assert len(counters) == 1
    assert len(pids) == 3  # main + p0 + p1
    # spans are placed at START time: a compile at ts=1.0 lasting 0.5s
    # begins 0.5s before its completion stamp
    comp = next(e for e in evs if e["name"] == "compile")
    assert comp["dur"] == pytest.approx(0.5e6)


# ------------------------------------------------------- TRACE artifacts

def test_metric_lines_and_benchdiff_directions(tmp_path):
    base = _fleet_shards(tmp_path, skew_s=0.5, steps=4)
    tl = trace_mod.load_timeline(base)
    findings = trace_mod.detect_anomalies(
        tl, trace_mod.AnomalyConfig(straggler_skew_ms=100.0))
    lines = trace_mod.metric_lines(tl, findings)
    by_name = {l["metric"]: l for l in lines}
    assert by_name["trace_anomaly_count"]["value"] == 4
    assert by_name["trace_anomaly_count"]["lower_is_better"]
    assert by_name["trace_straggler_skew_ms"]["value"] == \
        pytest.approx(500.0)


# ------------------------------------------------------------- the CLI

def _tracetool(*args):
    return subprocess.run([sys.executable, TRACETOOL, *args],
                          capture_output=True, text=True, timeout=120)


def test_tracetool_stats_merge_tree_and_check(tmp_path):
    base = _fleet_shards(tmp_path, steps=4)
    _write_shard(base, _serving_events(late_compile=False))
    out = _tracetool("stats", base)
    assert out.returncode == 0
    assert "p0" in out.stdout and "p1" in out.stdout
    merged = str(tmp_path / "merged.jsonl")
    out = _tracetool("merge", base, "-o", merged)
    assert out.returncode == 0
    with open(merged) as fh:
        lines = [json.loads(l) for l in fh]
    assert len(lines) == 11 and all("process" in l for l in lines)
    out = _tracetool("check", base)
    assert out.returncode == 0, out.stdout
    out = _tracetool("tree", base)
    assert out.returncode == 0
    out = _tracetool("check", str(tmp_path / "absent.jsonl"))
    assert out.returncode == 2


def test_tracetool_check_fails_on_injected_hang(tmp_path):
    base = _fleet_shards(tmp_path, hang_at=3, steps=6)
    out = _tracetool("check", base, "--skew-ms", "100", "--json")
    assert out.returncode == 1
    payload = json.loads(out.stdout)
    assert payload["gating"] == 1
    assert payload["findings"][0]["anomaly"] == "straggler"
    # --fail-on scoping: the same finding demoted to informational
    out = _tracetool("check", base, "--skew-ms", "100",
                     "--fail-on", "retrace")
    assert out.returncode == 0


def test_tracetool_export_perfetto(tmp_path):
    base = _fleet_shards(tmp_path, steps=3)
    out_path = str(tmp_path / "t.perfetto.json")
    out = _tracetool("export", base, "--perfetto", "-o", out_path)
    assert out.returncode == 0
    with open(out_path) as fh:
        doc = json.load(fh)
    assert doc["traceEvents"]


def test_tracetool_stats_on_committed_shards():
    """The acceptance fixture: the committed telemetry_bench.jsonl.p0/
    .p1 pair merges into per-span p50/p99 for >= 2 processes."""
    out = _tracetool("stats", os.path.join(ROOT, "telemetry_bench.jsonl"),
                     "--json")
    assert out.returncode == 0
    stats = json.loads(out.stdout)
    procs = {k.split("::")[0] for k in stats}
    assert {"p0", "p1"} <= procs
    for row in stats.values():
        assert row["p99_ms"] >= row["p50_ms"] >= 0
        assert row["count"] >= 1


# ------------------------------------------------------ metrics registry

def test_rolling_histogram_exposition_and_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("req_latency_seconds", "test", window=64)
    g = reg.gauge("queue_depth", "test")
    c = reg.counter("requests_total", "test")
    for v in (0.001, 0.002, 0.004, 0.2, 0.4):
        reg.observe(h, v)
    g.set(3)
    reg.inc(c, 1.0, outcome="ok")
    reg.inc(c, 1.0, outcome="ok")
    reg.inc(c, 1.0, outcome="error")
    text = reg.render()
    parsed = parse_exposition(text)
    assert parsed["req_latency_seconds_count"] == 5
    assert parsed["req_latency_seconds_sum"] == pytest.approx(0.607)
    assert parsed['req_latency_seconds_bucket{le="+Inf"}'] == 5
    assert parsed['req_latency_seconds_bucket{le="0.005"}'] == 3
    assert parsed['requests_total{outcome="ok"}'] == 2
    assert parsed["queue_depth"] == 3
    assert parsed["req_latency_seconds_p50"] == pytest.approx(0.004)
    assert parsed["req_latency_seconds_p99"] == pytest.approx(0.4)
    assert "# TYPE req_latency_seconds histogram" in text
    assert CONTENT_TYPE.startswith("text/plain; version=0.0.4")
    # bucket counts are cumulative-monotone
    cum = [v for k, v in parsed.items() if "_bucket{" in k]
    assert cum == sorted(cum)


def test_registry_render_is_thread_safe_under_writes():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "test")
    stop = threading.Event()
    errors = []

    def writer():
        i = 0
        while not stop.is_set():
            reg.observe(h, 0.001 * (i % 7))
            i += 1

    def reader():
        try:
            for _ in range(50):
                parse_exposition(reg.render())
        except Exception as exc:  # noqa: BLE001
            errors.append(exc)

    w = threading.Thread(target=writer)
    w.start()
    readers = [threading.Thread(target=reader) for _ in range(3)]
    for t in readers:
        t.start()
    for t in readers:
        t.join()
    stop.set()
    w.join()
    assert not errors


# ----------------------------------------- artifact sharded-input fallback

def test_artifact_load_falls_back_to_shards(tmp_path):
    from deeplearning4j_tpu.telemetry import artifact as art

    base = str(tmp_path / "t.jsonl")
    _write_shard(base + ".p0", [
        {"event": "metric", "metric": "m0", "value": 1.0, "seq": 0,
         "ts": 1.0}])
    _write_shard(base + ".p1", [
        {"event": "metric", "metric": "m1", "value": 2.0, "seq": 0,
         "ts": 2.0}])
    lines = art.load(base)  # the unsuffixed file does not exist
    assert lines["m0"]["value"] == 1.0 and lines["m1"]["value"] == 2.0
    with pytest.raises(FileNotFoundError):
        art.load(str(tmp_path / "absent.jsonl"))


def test_artifact_committed_shard_pair_parses():
    from deeplearning4j_tpu.telemetry import artifact as art

    text = art.read_artifact_text(
        os.path.join(ROOT, "telemetry_bench.jsonl") + "")
    assert text  # unsuffixed exists; now force the shard path
    shard_text = art._read_shards(
        os.path.join(ROOT, "telemetry_bench.jsonl"))
    assert shard_text.count("\n") >= 2
