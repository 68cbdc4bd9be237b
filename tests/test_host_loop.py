"""The generation engine's host loop on the record (ISSUE 27): every
pass of `_GenWorker.loop` shows as named leaf spans, one request id
joins the front door, the queue, the prefill chunks, the decode steps
and the stream, spans stand on `perf_counter`, and with telemetry off
the loop builds nothing and `/metrics` still counts requests. Since
ISSUE 36 the loop runs one program ahead: a step's span holds the
`dispatch` of its own program and the `fetch` of the one before."""

import contextlib
import json
import re
import threading
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.serving import replay
from deeplearning4j_tpu.serving.buckets import BucketLattice
from deeplearning4j_tpu.serving.engine import GenerationEngine
from deeplearning4j_tpu.serving.kvcache import PagePool
from deeplearning4j_tpu.serving.server import ServingServer
from deeplearning4j_tpu.telemetry import (NullRecorder, Recorder, get_default,
                                          recorder as recorder_mod,
                                          set_default)

pytestmark = pytest.mark.serving

# the leaf spans of the engine thread, one letter each for the grammar
LEAF = {"admit": "a", "step_prepare": "p", "dispatch": "d", "fetch": "f",
        "emit": "e", "idle_wait": "w"}
# (id, prompt length, new tokens): 13 and 16 take two chunks of 8
REQUESTS = (("req-a", 13, 8), ("req-b", 5, 6), ("req-c", 16, 3))


def _spans(events, name=None):
    return [e for e in events if e["event"] == "span"
            and (name is None or e["name"] == name)]


def _post(server, rid, prompt, max_new):
    body = json.dumps({"tokens": [int(t) for t in prompt],
                       "max_new_tokens": max_new, "id": rid}).encode()
    req = urllib.request.Request(
        f"{server.url}/generate", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        return [json.loads(ln) for ln in resp.read().splitlines() if ln]


@pytest.fixture(scope="module")
def served_run():
    """Three requests on two slots over HTTP, all at once, against a
    model wide enough that a step (milliseconds on the CPU) dwarfs the
    recorder's own emission; the whole record after the drain."""
    from deeplearning4j_tpu.models.transformer import transformer_lm

    net = transformer_lm(vocab_size=64, d_model=768, n_heads=4, n_layers=4,
                         d_ff=3072, max_length=32)
    net.init()
    rec = Recorder(path=None, keep=100_000)
    lat = BucketLattice(batch_sizes=(1,), seq_lens=(8, 16))
    engine = GenerationEngine(net, lat, slots=2, max_new_tokens=8,
                              page_size=8, prefill_chunk=8, recorder=rec)
    warm = engine.warmup()
    server = ServingServer(engine, port=0).start()
    rng = np.random.default_rng(27)
    answers = {}

    def client(rid, plen, n):
        answers[rid] = _post(server, rid, rng.integers(0, 64, plen), n)

    threads = [threading.Thread(target=client, args=r) for r in REQUESTS]
    for t in threads:
        t.start()
    for t in threads:
        t.join(180)
    assert not any(t.is_alive() for t in threads)
    server.stop()
    assert engine.trace_count == warm  # no field reached a jitted argument
    assert engine.fleet_workers()[0]._flight is None  # all retired
    return list(rec.events), answers, engine.stats()


@pytest.mark.parametrize("rid,plen,new_tokens", REQUESTS)
def test_request_timeline_joins_on_its_id(served_run, rid, plen, new_tokens):
    events, answers, _ = served_run
    assert answers[rid][-1]["done"] and answers[rid][-1]["id"] == rid
    assert len(answers[rid][-1]["tokens"]) == new_tokens
    [admit] = [e for e in events if e["event"] == "admit" and e["id"] == rid]
    [done] = [e for e in events if e["event"] == "request" and e["id"] == rid]
    [stream] = [e for e in events if e["event"] == "stream" and e["id"] == rid]
    assert admit["queue_s"] == done["queue_s"] >= 0
    # prefill: the chunks under the request's trace hold its whole prompt
    chunks = [e for e in _spans(events, "prefill_chunk")
              if e.get("trace_id") == rid]
    assert sum(e["n_real"] for e in chunks) == plen
    assert all(e["n_real"] <= e["bucket"][1] for e in chunks)
    assert [e["final"] for e in chunks] == [False] * (len(chunks) - 1) + [True]
    # decode: between admission and completion the slot is this request's
    # (a step names the rows it DISPATCHED: the step launched over the
    # request's last one no longer holds the slot, and the slot is released
    # only when that last one is retired, so no later tenant's step is here)
    steps = [e for e in _spans(events, "decode_step")
             if admit["seq"] < e["seq"] < done["seq"]
             and admit["slot"] in e["slots"]]
    assert len(steps) == new_tokens - 1   # the first token is the prefill's
    assert all(e["n_active"] == len(e["slots"]) for e in steps)
    # its tokens: the `emit` spans of exactly those programs and of the
    # prompt's final chunk, one token each for this request
    emits = {e["program"]: e for e in _spans(events, "emit")}
    mine = [chunks[-1]["program"]] + [e["program"] for e in steps]
    assert all(emits[p]["tokens"] >= 1 for p in mine)
    # and the request ends inside the emit of its last step's program
    assert done["parent_id"] == emits[mine[-1]]["span_id"]
    # stream: one record, a lag per token line, on the engine's clock
    assert stream["n"] == new_tokens == len(stream["lag_s"])
    assert all(0 <= lag < 60 for lag in stream["lag_s"])
    assert 0 <= stream["parse_s"] < 60


def test_every_pass_of_the_loop_is_on_the_record_in_order(served_run):
    events, _, _ = served_run
    leaf = sorted((e for e in _spans(events) if e["name"] in LEAF),
                  key=lambda e: e["t0"])
    passes = "".join(LEAF[e["name"]] for e in leaf)
    # a pass admits, then launches a prefill chunk and / or a decode step
    # (prepare, dispatch; where a program was in flight, its fetch inside
    # the new step's span and its emit after it), or retires the last
    # program of a busy spell alone (fetch, emit), or waits
    assert re.fullmatch(r"(a(pd(fe)?){1,2}|afe|aw)+", passes), passes
    steps = _spans(events, "prefill_chunk") + _spans(events, "decode_step")
    assert passes.count("pd") == len(steps)
    # every program is retired exactly once, in the order it was dispatched
    assert passes.count("fe") == passes.count("f") == passes.count("e") \
        == len(steps)
    programs = sorted(e["program"] for e in steps)
    assert programs == list(range(1, len(steps) + 1))
    assert [e["program"] for e in _spans(events, "emit")] == programs
    # no two leaves overlap: they are one thread's consecutive regions
    assert all(x["t1"] <= y["t0"] + 2e-6 for x, y in zip(leaf, leaf[1:]))
    # a dispatch is a child of the model step it launches; a fetch is a
    # child of the step launched over the program it brings home, or of
    # nothing (that program was the last of a busy spell)
    by_id = {e["span_id"]: e for e in _spans(events) if "span_id" in e}
    alone = 0
    for e in leaf:
        if e["name"] == "dispatch" or (e["name"] == "fetch"
                                       and "parent_id" in e):
            assert by_id[e["parent_id"]]["name"] in ("prefill_chunk",
                                                     "decode_step")
        elif e["name"] == "fetch":
            assert e["replica"] == 0 and e["fetched"] in programs
            alone += 1
    assert alone == passes.count("afe") >= 1
    # what an emit spends dropping the program's device arrays is its own
    # field
    assert all(0 <= e["release_s"] <= e["seconds"] + 2e-6
               for e in _spans(events, "emit"))
    admits = _spans(events, "admit")
    assert sum(e["admitted"] for e in admits) == len(REQUESTS)
    assert {e["blocked"] for e in admits} <= {None, "slots", "pages"}
    # three requests on two slots: the third waited for a slot
    assert any(e["blocked"] == "slots" and e["pending"] >= 1 for e in admits)


def test_a_program_is_dispatched_before_the_one_before_it_is_fetched(
        served_run):
    """The mechanism on the record: a step with `ahead` true was launched
    while its predecessor's tokens were still on the device: its
    `dispatch` ends before the `fetch` of that predecessor begins, inside
    one span, and `stats()` counts such steps. A step with `ahead` false
    found nothing in flight: it fetches nothing and is the first program
    after an `idle_wait` (or of the run)."""
    events, _, stats = served_run
    spans = _spans(events)
    steps = sorted((e for e in spans
                    if e["name"] in ("prefill_chunk", "decode_step")),
                   key=lambda e: e["program"])
    kids = {}
    for e in spans:
        if e["name"] in ("dispatch", "fetch") and "parent_id" in e:
            kids.setdefault(e["parent_id"], {})[e["name"]] = e
    ahead = [e for e in steps if e["ahead"]]
    assert len(ahead) >= len(steps) - 3 and len(ahead) < len(steps)
    for e in ahead:
        mine = kids[e["span_id"]]
        assert e["fetched"] == e["program"] - 1
        assert e["t0"] <= mine["dispatch"]["t0"]
        assert mine["dispatch"]["t1"] <= mine["fetch"]["t0"] + 2e-6
        assert mine["fetch"]["t1"] <= e["t1"]
    waits = [e["t1"] for e in spans if e["name"] == "idle_wait"]
    for e in steps:
        if not e["ahead"]:
            assert "fetched" not in e and "fetch" not in kids[e["span_id"]]
            before = [s for s in steps if s["program"] == e["program"] - 1]
            assert not before or any(
                before[0]["t1"] <= w <= e["t0"] for w in waits)
    # consecutive step spans do not overlap (the next `t0` less this `t1`
    # is the host's time between two steps)
    assert all(x["t1"] <= y["t0"] for x, y in zip(steps, steps[1:]))
    assert stats["steps_ahead"] == len(ahead) \
        == stats["fleet"][0]["steps_ahead"]
    assert stats["fleet"][0]["decode_steps_run"] == len(
        [e for e in steps if e["name"] == "decode_step"])


def test_leaf_spans_cover_the_engine_threads_time(served_run):
    events, _, _ = served_run
    leaf = [e for e in _spans(events) if e["name"] in LEAF]
    start = min(e["t0"] for e in leaf if e["name"] == "admit"
                and e["admitted"])
    end = max(e["t1"] for e in leaf if e["name"] == "emit")
    covered = sum(min(e["t1"], end) - max(e["t0"], start) for e in leaf
                  if e["t1"] > start and e["t0"] < end)
    assert covered / (end - start) >= 0.90, (covered, end - start)


def test_span_events_stand_on_the_monotonic_clock(served_run):
    events, _, _ = served_run
    spans = [e for e in _spans(events) if "t0" in e]
    assert len(spans) > 50 and len(spans) == len(
        [e for e in _spans(events) if e["name"] != "drain"])
    for e in spans:
        assert e["t0"] <= e["t1"]
        assert abs((e["t1"] - e["t0"]) - e["seconds"]) <= 2e-6, e


def test_a_decode_step_costs_six_events():
    """One request alone: every pass between two decode steps is the
    same six records (the emit of the program before the last, admit,
    step_prepare, dispatch, the fetch of the last program, decode_step),
    under the budget of eight; nothing is recorded per token, and running
    one program ahead added no record."""
    net = replay._tiny_lm(24)
    rec = Recorder(path=None, keep=100_000)
    lat = BucketLattice(batch_sizes=(1,), seq_lens=(8, 16))
    engine = GenerationEngine(net, lat, slots=2, max_new_tokens=16,
                              page_size=8, recorder=rec)
    engine.warmup()
    engine.start()
    out = engine.generate(np.arange(1, 7, dtype=np.int32), 16, timeout=120)
    engine.drain()
    assert len(out) == 16
    events = list(rec.events)
    seqs = [e["seq"] for e in _spans(events, "decode_step")]
    assert len(seqs) == 15
    per_step = [b - a for a, b in zip(seqs, seqs[1:])]
    assert max(per_step) <= 8
    assert per_step == [6] * 14
    [first, *_], last = seqs, seqs[-1]
    names = [e.get("name", e["event"]) for e in events
             if first < e["seq"] <= last]
    assert names[:6] == ["emit", "admit", "step_prepare", "dispatch",
                         "fetch", "decode_step"]
    steps = _spans(events, "decode_step")
    assert all(e["ahead"] and e["fetched"] == e["program"] - 1 for e in steps)
    # the last step's tokens come home in a pass of their own
    assert [e["fetched"] for e in _spans(events, "fetch")
            if "parent_id" not in e] == [steps[-1]["program"]]
    assert engine.stats()["steps_ahead"] == 15


@pytest.mark.parametrize("plen", [1, 7, 8, 9, 16, 21, 24])
def test_prefill_chunks_count_their_real_tokens(plen):
    net = replay._tiny_lm(24)
    rec = Recorder(path=None)
    lat = BucketLattice(batch_sizes=(1,), seq_lens=(8, 24))
    engine = GenerationEngine(net, lat, slots=1, max_new_tokens=4,
                              page_size=8, prefill_chunk=8, recorder=rec)
    engine.warmup()
    engine.start()
    engine.generate(np.ones(plen, np.int32), 2, timeout=120)
    engine.drain()
    chunks = _spans(rec.events, "prefill_chunk")
    assert sum(e["n_real"] for e in chunks) == plen
    assert [e["start"] for e in chunks] == list(range(0, plen, 8))
    assert all(e["bucket"] == [1, 8] for e in chunks)


def test_a_span_that_follows_starts_where_the_last_one_ended():
    rec = Recorder(path=None)
    with rec.span("admit", follows=True):       # nothing before it: now
        pass
    with rec.span("step_prepare", follows=True):
        pass
    with rec.span("decode_step"):               # its own start
        with rec.span("dispatch"):
            pass
        with rec.span("fetch", follows=True):
            pass
    with rec.span("emit", follows=True):
        pass
    by = {e["name"]: e for e in _spans(rec.events)}
    assert by["step_prepare"]["t0"] == by["admit"]["t1"]
    assert by["decode_step"]["t0"] > by["step_prepare"]["t1"]
    assert by["dispatch"]["t0"] >= by["decode_step"]["t0"]
    assert by["fetch"]["t0"] == by["dispatch"]["t1"]
    assert by["emit"]["t0"] == by["decode_step"]["t1"] >= by["fetch"]["t1"]
    # another thread's regions are none of this thread's
    seen = {}

    def elsewhere():
        with rec.span("idle_wait", follows=True):
            pass
        seen.update(_spans(rec.events, "idle_wait")[0])

    t = threading.Thread(target=elsewhere)
    t.start()
    t.join(30)
    assert seen["t0"] > by["emit"]["t1"]


def test_null_recorder_span_is_one_shared_object():
    null = NullRecorder()
    assert null.live is False and Recorder(path=None).live is True
    a, b = null.span("dispatch"), null.span("fetch", follows=True, n=3)
    assert a is b
    assert not isinstance(a, contextlib._GeneratorContextManager)
    with a as fields:
        fields["x"] = 1     # call sites attach result fields: still a dict
    with b as other:
        assert other == {}  # and nothing of one region reaches another
    assert null.event("page_pool", pages_in_use=1) == {}


@pytest.fixture
def telemetry_off(monkeypatch):
    monkeypatch.delenv(recorder_mod.ENV_VAR, raising=False)
    prev = set_default(None)
    yield
    set_default(prev)


def test_telemetry_off_builds_no_fields(telemetry_off, monkeypatch):
    """The default deployment: the engine's recorder is a NullRecorder of
    its own, and the loop never builds a page-pool description."""
    calls = []
    monkeypatch.setattr(PagePool, "describe",
                        lambda self: calls.append(1) or {})
    net = replay._tiny_lm(24)
    lat = BucketLattice(batch_sizes=(1,), seq_lens=(8, 16))
    engine = GenerationEngine(net, lat, slots=2, max_new_tokens=8,
                              page_size=8)
    assert isinstance(engine.recorder, NullRecorder)
    assert engine.recorder is not get_default()
    engine.warmup()
    engine.start()
    reqs = [engine.submit_generate(np.ones(n, np.int32), 4) for n in (3, 9, 5)]
    for r in reqs:
        assert r.wait(120) and r.error is None and len(r.emitted) == 4
    engine.drain()
    assert calls == []
    assert len(engine.recorder.events) == 0


def test_metrics_count_requests_with_telemetry_off(telemetry_off):
    net = replay._tiny_lm(24)
    lat = BucketLattice(batch_sizes=(1,), seq_lens=(8, 16))
    engine = GenerationEngine(net, lat, slots=2, max_new_tokens=8,
                              page_size=8)
    other = GenerationEngine(net, lat, slots=1, max_new_tokens=8, page_size=8)
    engine.warmup()
    server = ServingServer(engine, port=0).start()
    try:
        lines = _post(server, "only", [1, 2, 3], 3)
        assert lines[-1]["done"] and len(lines[-1]["tokens"]) == 3
        # a second engine of the process is not this server's to count
        other.recorder.request("elsewhere", ok=True, kind="generate",
                               total_s=1.0)
        with urllib.request.urlopen(f"{server.url}/metrics",
                                    timeout=30) as resp:
            text = resp.read().decode()
    finally:
        server.stop()
    [total] = [ln for ln in text.splitlines()
               if ln.startswith("serving_requests_total{")]
    assert 'kind="generate"' in total and 'outcome="ok"' in total
    assert total.split()[-1] in ("1", "1.0")
    for hist in ("serving_request_latency_seconds_count",
                 "serving_request_queue_seconds_count",
                 "serving_ttft_seconds_count"):
        [row] = [ln for ln in text.splitlines() if ln.startswith(hist)]
        assert float(row.split()[-1]) == 1.0
    assert "serving_mfu_live" not in text
