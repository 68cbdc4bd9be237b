"""Tier-1 gate for autoregressive generation serving (ISSUE 11):
decode-vs-full-forward parity (the incremental step IS the forward),
chunked-prefill parity, the page-pool accounting contract (exhaustion
queues or refuses, never crashes), the zero-retrace promise across a
mixed prompt/output-length replay, decode-step cost independent of
prompt length (telemetry span timings), and the generation scoreboard
reconstruction behind tools/trafficreplay.py --generate."""

import http.client
import json
import socket
import threading
import time
import types
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.serving import replay
from deeplearning4j_tpu.serving.batcher import DecodeSlots, GenRequest
from deeplearning4j_tpu.serving.buckets import BucketLattice
from deeplearning4j_tpu.serving.engine import (GenerationEngine,
                                               QueueFullError)
from deeplearning4j_tpu.serving.kvcache import (CachePlan, PagePool,
                                                pages_for, quantize)
from deeplearning4j_tpu.serving.server import ServingServer
from deeplearning4j_tpu.telemetry import Recorder
from deeplearning4j_tpu.telemetry.memstat import tree_bytes

pytestmark = pytest.mark.serving


def _greedy_full_forward(net, prompt, k):
    """Reference decode: argmax over k FULL-sequence forwards."""
    toks = [int(t) for t in prompt]
    out = []
    for _ in range(k):
        probs = np.asarray(net.output(np.asarray(toks, np.int32)[None, :]))
        nxt = int(np.argmax(probs[0, -1]))
        out.append(nxt)
        toks.append(nxt)
    return out


def _greedy_incremental(net, prompt, k, *, capacity=32, bucket=8,
                        chunk=None):
    """Incremental decode: one prefill (optionally chunked) + k-1
    single-token steps through the container's decode entries."""
    import jax

    prefill = jax.jit(net.prefill_fn())
    step = jax.jit(net.incremental_decode_fn())
    cache = net.init_kv_cache(1, capacity)
    L = len(prompt)
    starts = ([0] if chunk is None
              else list(range(0, L, chunk)))
    tok = None
    for s in starts:
        n_real = min((chunk or L), L - s)
        Tb = chunk if (chunk and n_real == chunk) else max(
            bucket, 1 << (n_real - 1).bit_length())
        tokens = np.zeros((1, Tb), np.int32)
        tokens[0, :n_real] = prompt[s:s + n_real]
        kmask = np.zeros((1, Tb), np.float32)
        kmask[0, :n_real] = 1.0
        probs, cache = prefill(net.params, net.state, cache, tokens,
                               kmask, np.zeros(1, np.int32),
                               np.asarray([s], np.int32),
                               np.asarray([n_real - 1], np.int32))
        tok = int(np.argmax(np.asarray(probs)[0]))
    out = [tok]
    pos = L
    for _ in range(k - 1):
        probs, cache = step(net.params, net.state, cache,
                            np.asarray([tok], np.int32),
                            np.asarray([pos], np.int32))
        tok = int(np.argmax(np.asarray(probs)[0]))
        out.append(tok)
        pos += 1
    return out, np.asarray(probs)[0]


# ------------------------------------------------------ decode parity

def test_incremental_decode_matches_full_forward_graph_lm():
    """THE tentpole property: greedy decode of K tokens from the
    incremental step (prefill + KV-cache decode) matches argmax over K
    full-sequence forwards — same tokens, probs at atol 1e-5."""
    net = replay._tiny_lm(32)
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 64, 6).astype(np.int32)
    k = 6
    ref = _greedy_full_forward(net, prompt, k)
    inc, last_probs = _greedy_incremental(net, prompt, k)
    assert inc == ref
    # the final step's probs match the full forward's last row
    toks = list(prompt) + ref
    full = np.asarray(net.output(np.asarray(toks[:-1], np.int32)[None]))
    np.testing.assert_allclose(last_probs, full[0, -1], atol=1e-5)


def test_chunked_prefill_matches_single_shot():
    """A long prompt prefilled in bucket-shaped chunks (the interleave
    unit) fills the cache identically to one-shot prefill: the decode
    that follows produces the same tokens."""
    net = replay._tiny_lm(32)
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 64, 13).astype(np.int32)
    one_shot, _ = _greedy_incremental(net, prompt, 5, bucket=16)
    chunked, _ = _greedy_incremental(net, prompt, 5, chunk=8)
    ref = _greedy_full_forward(net, prompt, 5)
    assert one_shot == ref
    assert chunked == ref


def test_incremental_decode_matches_full_forward_mln():
    """Both containers carry the contract: a sequential MultiLayerNetwork
    transformer stack decodes incrementally to the same greedy tokens
    as its full forward."""
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import (EmbeddingLayer,
                                                   LayerNormalization,
                                                   PositionalEncodingLayer,
                                                   RnnOutputLayer,
                                                   SelfAttentionLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder().seed(9).list()
            .layer(EmbeddingLayer(n_in=32, n_out=16,
                                  activation="identity", has_bias=False))
            .layer(PositionalEncodingLayer(max_length=32, n_features=16))
            .layer(SelfAttentionLayer(n_in=16, n_out=16, n_heads=2,
                                      causal=True, activation="identity"))
            .layer(LayerNormalization(n_in=16, n_out=16))
            .layer(RnnOutputLayer(n_in=16, n_out=32, activation="softmax",
                                  loss_function="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(5)
    prompt = rng.integers(0, 32, 5).astype(np.int32)
    ref = _greedy_full_forward(net, prompt, 4)
    inc, _ = _greedy_incremental(net, prompt, 4, capacity=16)
    assert inc == ref


def test_non_causal_attention_is_rejected():
    from deeplearning4j_tpu.nn.decode import make_decode_fn
    from deeplearning4j_tpu.models.transformer import transformer_lm

    net = transformer_lm(vocab_size=32, d_model=16, n_heads=2,
                        n_layers=1, d_ff=16, max_length=8)
    for v in net.conf.vertices.values():
        lc = getattr(v, "layer", None)
        if lc is not None and hasattr(lc, "causal"):
            lc.causal = False
    net.init()
    with pytest.raises(ValueError, match="cannot stream"):
        make_decode_fn(net)


def test_prefill_bucket_set_is_lattice_owned():
    """The prefill warmup set lives on the lattice: every seq bucket up
    to the chunk, and a chunk off the lattice is rejected (an unwarmed
    chunk shape would be a guaranteed mid-traffic retrace)."""
    lat = BucketLattice(batch_sizes=(1,), seq_lens=(8, 16, 32))
    assert lat.prefill_buckets(16) == [8, 16]
    assert lat.prefill_buckets(32) == [8, 16, 32]
    with pytest.raises(ValueError, match="lattice seq bucket"):
        lat.prefill_buckets(12)
    with pytest.raises(ValueError, match="sequence lattice"):
        BucketLattice(batch_sizes=(1, 2)).prefill_buckets(8)


# ----------------------------------------------------- page accounting

def test_page_math_quantizes_to_grid():
    assert pages_for(0, 16) == 0
    assert pages_for(1, 16) == 1
    assert pages_for(16, 16) == 1
    assert pages_for(17, 16) == 2
    assert quantize(17, 16) == 32
    plan = CachePlan(max_seq_bucket=32, max_new_tokens=16, n_slots=4,
                     page_size=16)
    assert plan.capacity == 48 and plan.pages_per_slot == 3
    assert plan.pool_pages == 12
    assert plan.request_pages(8, 4) == 1
    assert plan.request_pages(32, 16) == 3


def test_page_pool_reserve_release_occupancy():
    pool = PagePool(4, page_size=8)
    assert pool.try_reserve(3)
    assert not pool.try_reserve(2)  # all-or-nothing, no partial grant
    assert pool.try_reserve(1)
    assert pool.occupancy == 1.0 and pool.peak_occupancy == 1.0
    pool.release(3)
    assert pool.in_use == 1
    assert pool.peak_in_use == 4  # high-water mark survives release
    with pytest.raises(ValueError, match="double release"):
        pool.release(2)


def test_decode_slots_state_machine():
    slots = DecodeSlots(2)
    assert slots.free_index() == 0 and not slots.busy()
    r1 = GenRequest(tokens=np.arange(4), max_new_tokens=2, t_enqueue=0.0)
    r1.t_admitted = 1.0
    s1 = slots.admit(0, r1, pages=2)
    r2 = GenRequest(tokens=np.arange(6), max_new_tokens=2, t_enqueue=0.0)
    r2.t_admitted = 2.0
    slots.admit(1, r2, pages=2)
    assert slots.free_index() is None
    # oldest-first prefill; a slot starts decoding once its prompt is in
    assert slots.next_prefill() == 0
    s1.start = 4
    assert slots.next_prefill() == 1
    assert slots.decoding() == [0]
    s1.sent = 2  # budget dispatched: no longer decoding
    assert slots.decoding() == []
    assert slots.release(0) == 2
    assert slots.free_index() == 0


def test_pool_exhaustion_queues_then_503_never_crashes():
    """The acceptance failure mode: a saturated page pool queues
    admissions; a full queue is a graceful QueueFullError (HTTP 503) —
    and every ACCEPTED request still completes after the pool frees."""
    net = replay._tiny_lm(16)
    rec = Recorder(path=None)
    lat = BucketLattice(batch_sizes=(1,), seq_lens=(8,))
    engine = GenerationEngine(net, lat, slots=1, max_new_tokens=8,
                              page_size=8, max_queue=2, recorder=rec)
    engine.warmup()
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, 64, 5).astype(np.int32) for _ in range(6)]
    accepted, refused = [], 0
    for p in prompts:  # engine not started: the queue can only grow
        try:
            accepted.append(engine.submit_generate(p, 4))
        except QueueFullError:
            refused += 1
    # engine not started, so nothing drains: exactly max_queue admitted
    assert len(accepted) == 2 and refused == 4
    engine.start()
    for req in accepted:
        assert req.wait(60), "accepted request starved after exhaustion"
        assert req.error is None and len(req.emitted) == 4
    # a request that can NEVER fit the pool is refused outright
    big = GenerationEngine(net, lat, slots=1, max_new_tokens=8,
                           page_size=8, pool_pages=1, recorder=rec)
    with pytest.raises(ValueError, match="exceed the cache geometry"):
        big.submit_generate(prompts[0], 8)
    engine.drain()


# ---------------------------------------------------- zero-retrace gate

def test_zero_retrace_across_mixed_generation_replay():
    """Warmup compiles each (replica, prefill-bucket) and the decode
    shape ONCE; a mixed prompt-length x output-length stream adds zero
    — on both the telemetry compile-span count and the trace counter."""
    net = replay._tiny_lm(24)
    rec = Recorder(path=None)
    lat = BucketLattice(batch_sizes=(1,), seq_lens=(8, 16))
    engine = GenerationEngine(net, lat, slots=2, max_new_tokens=8,
                              page_size=8, recorder=rec)
    warmed = engine.warmup()
    assert warmed == 3  # 2 prefill buckets + 1 decode shape, 1 replica
    assert engine.trace_count == 3

    def compile_spans():
        return [e for e in rec.events
                if e.get("event") == "span" and e.get("name") == "compile"]

    assert len(compile_spans()) == 3
    assert all(e.get("warmup") for e in compile_spans())
    engine.start()
    rng = np.random.default_rng(11)
    for plen, olen in ((3, 2), (8, 5), (11, 1), (16, 8), (5, 3),
                       (1, 4), (13, 2), (16, 1), (2, 6), (7, 8)):
        out = engine.generate(rng.integers(0, 64, plen).astype(np.int32),
                              olen, timeout=60)
        assert len(out) == olen
    assert engine.trace_count == 3, "a shape escaped the page grid"
    assert len(compile_spans()) == 3
    reqs = [e for e in rec.events if e.get("event") == "request"]
    assert len(reqs) == 10
    for ev in reqs:
        assert ev["ok"] and ev["kind"] == "generate"
        assert {"ttft_s", "total_s", "queue_s", "prompt_len",
                "prompt_bucket", "new_tokens"} <= set(ev)
    # page accounting is on the record and returns to empty
    pools = [e for e in rec.events if e.get("event") == "page_pool"]
    assert pools and pools[-1]["pages_in_use"] == 0
    assert max(p["pages_in_use"] for p in pools) > 0
    engine.drain()


def test_decode_step_cost_independent_of_prompt_length():
    """Decode always attends the full (page-quantized) cache with a
    position mask, so step shape — and cost — is identical whether the
    prompt filled one page or all of them. Asserted on telemetry
    decode_step span medians across the shortest and longest prompt
    buckets (generous 3x bound: the computation is literally the same
    jit executable, only scheduler noise differs)."""
    net = replay._tiny_lm(40)
    rec = Recorder(path=None)
    lat = BucketLattice(batch_sizes=(1,), seq_lens=(8, 32))
    engine = GenerationEngine(net, lat, slots=1, max_new_tokens=16,
                              page_size=8, recorder=rec)
    engine.warmup()
    engine.start()
    rng = np.random.default_rng(13)

    def decode_medians(prompt_len):
        mark = len(rec.events)
        out = engine.generate(
            rng.integers(0, 64, prompt_len).astype(np.int32), 16,
            timeout=60)
        assert len(out) == 16
        spans = [e["seconds"] for e in list(rec.events)[mark:]
                 if e.get("event") == "span"
                 and e.get("name") == "decode_step"]
        assert len(spans) == 15  # token 1 comes from prefill
        return float(np.median(spans))

    short = decode_medians(4)    # bucket 8: one page of prompt
    long = decode_medians(30)    # bucket 32: four pages of prompt
    assert long < 3.0 * short, (
        f"decode step grew with prompt length: {short:.6f}s -> "
        f"{long:.6f}s — the step is reading prompt-dependent state")
    engine.drain()


# ------------------------------------------------- trace + scoreboard

def test_generation_trace_is_seeded_with_length_mix():
    t1 = replay.make_generation_trace(7, 30, prompt_lengths=(8, 16),
                                      output_lengths=(2, 4))
    t2 = replay.make_generation_trace(7, 30, prompt_lengths=(8, 16),
                                      output_lengths=(2, 4))
    assert t1 == t2
    t3 = replay.make_generation_trace(8, 30, prompt_lengths=(8, 16),
                                      output_lengths=(2, 4))
    assert t1 != t3
    offsets = [t for t, _, _ in t1]
    assert offsets == sorted(offsets)
    assert {p for _, p, _ in t1} <= {8, 16}
    assert {o for _, _, o in t1} <= {2, 4}


def test_reconstruct_generation_from_telemetry_alone(tmp_path):
    path = str(tmp_path / "g.jsonl")
    with open(path, "w") as fh:
        for i, (ttft, total, ntok) in enumerate(
                [(0.01, 0.05, 4), (0.02, 0.10, 8), (0.5, 1.0, 8)]):
            fh.write(json.dumps({
                "event": "request", "id": f"g{i}", "ok": True,
                "kind": "generate", "ts": 100.0 + i, "ttft_s": ttft,
                "total_s": total, "new_tokens": ntok}) + "\n")
        fh.write(json.dumps({"event": "request", "id": "bad", "ok": False,
                             "kind": "generate", "ts": 103.0,
                             "total_s": 0.2, "new_tokens": 0}) + "\n")
        fh.write(json.dumps({"event": "request", "id": "pred", "ok": True,
                             "ts": 104.0, "total_s": 0.2}) + "\n")
        fh.write(json.dumps({"event": "span", "name": "compile",
                             "warmup": True, "seconds": 1.0}) + "\n")
        fh.write(json.dumps({"event": "span", "name": "compile",
                             "seconds": 1.0}) + "\n")
        fh.write(json.dumps({"event": "span", "name": "decode_step",
                             "seconds": 0.002}) + "\n")
        fh.write(json.dumps({"event": "page_pool", "pages_in_use": 3,
                             "pages_total": 4}) + "\n")
        fh.write(json.dumps({"event": "page_pool", "pages_in_use": 0,
                             "pages_total": 4}) + "\n")
    sb = replay.reconstruct_generation(path)
    assert sb["n_ok"] == 3 and sb["n_failed"] == 1  # predict row excluded
    assert sb["total_tokens"] == 20
    assert sb["ttft_p50_ms"] == 20.0
    assert sb["ttft_p99_ms"] == 500.0
    assert sb["page_occupancy_peak"] == 0.75
    assert sb["recompiles_after_warmup"] == 1
    assert sb["decode_steps"] == 1
    first = min(100.0 + i - t for i, (_, t, _) in enumerate(
        [(0.01, 0.05, 4), (0.02, 0.10, 8), (0.5, 1.0, 8)]))
    assert sb["tokens_per_sec"] == round(20 / (102.0 - first), 2)


def test_generation_metric_lines_direction_flags():
    sb = dict(tokens_per_sec=100.0, ttft_p50_ms=1.0, ttft_p99_ms=2.0,
              page_occupancy_peak=0.5, recompiles_after_warmup=0,
              warmup_compiles=3, n_ok=5, n_failed=0, total_tokens=40)
    lines = {l["metric"]: l for l in replay.generation_metric_lines(sb)}
    assert not lines["serving_generate_tokens_per_sec"].get(
        "lower_is_better")
    for m in ("serving_generate_ttft_p50_ms",
              "serving_generate_ttft_p99_ms",
              "serving_generate_page_occupancy",
              "serving_generate_recompiles_after_warmup"):
        assert lines[m]["lower_is_better"]


def test_benchdiff_inverts_generation_rows(tmp_path):
    """TTFT/occupancy growth regresses; tokens/sec growth doesn't —
    including rows recovered from a bare summary line (no flags)."""
    import sys
    sys.path.insert(0, "tools")
    import benchdiff

    old = {"serving_generate_tokens_per_sec": {"value": 100.0},
           "serving_generate_ttft_p99_ms": {"value": 10.0},
           "serving_generate_page_occupancy": {"value": 0.5}}
    new = {"serving_generate_tokens_per_sec": {"value": 150.0},
           "serving_generate_ttft_p99_ms": {"value": 20.0},
           "serving_generate_page_occupancy": {"value": 0.9}}
    result = benchdiff.diff(old, new, threshold=0.10)
    regressed = {r["metric"] for r in result["regressions"]}
    assert regressed == {"serving_generate_ttft_p99_ms",
                         "serving_generate_page_occupancy"}


# ------------------------------------------------- the donated KV cache

_MIXED = ((3, 2), (8, 5), (11, 1), (16, 8), (5, 3), (1, 4), (13, 2),
          (16, 1), (2, 6), (7, 8))


def _prompt_dependent_lm(max_seq):
    """The tiny LM with its weights scaled up: the untrained net answers
    every prompt with one token, scaled its greedy stream depends on the
    prompt, so a wrong cache row shows in the ids."""
    net = replay._tiny_lm(max_seq)
    net.params = jax.tree.map(lambda x: x * 8.0, net.params)
    return net


def _gen_engine(net, rec, **kw):
    kw = {"slots": 2, "max_new_tokens": 8, "page_size": 8, **kw}
    return GenerationEngine(
        net, BucketLattice(batch_sizes=(1,), seq_lens=(8, 16)),
        recorder=rec, **kw)


def _consumed(tree):
    return any(leaf.is_deleted() for leaf in jax.tree.leaves(tree))


def _cost_events(rec):
    return [e for e in rec.events if e.get("event") == "cost"]


@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
def test_warmed_steps_alias_the_whole_cache(kv_dtype):
    """The counter that says donation engaged: every warmed step's
    `cost` event carries `alias_bytes` (XLA's own count of argument bytes
    the outputs reuse in place), and it reads the cache tree's bytes —
    all four leaves a layer in the int8 form."""
    rec = Recorder(path=None)
    engine = _gen_engine(replay._tiny_lm(24), rec, kv_dtype=kv_dtype)
    assert engine.warmup() == 3
    nbytes = tree_bytes(engine.fleet_workers()[0].cache)
    costs = _cost_events(rec)
    assert sorted(e["entry"] for e in costs) == ["decode", "prefill",
                                                 "prefill"]
    for e in costs:
        assert e["alias_bytes"] == nbytes > 0, e


def test_cache_is_rebound_at_dispatch_and_readers_see_it_whole():
    """The ownership rule. On the engine thread: when a step's
    `dispatch` span closes (the jit call has returned, nothing fetched
    yet) `worker.cache` is the live output and the tree that step was
    handed is consumed. From another thread, while the loop runs:
    stats() / describe() never fail, the ledger reads the whole cache's
    bytes every time, and a consumed tree is bound only inside the jit
    call — it is replaced before a reader can see it twice."""
    rec = Recorder(path=None)
    engine = _gen_engine(replay._tiny_lm(24), rec)
    engine.warmup()
    worker = engine.fleet_workers()[0]
    nbytes = tree_bytes(worker.cache)
    wrong = []  # a sink's exception is swallowed: collect, assert below
    seen = {"handed": worker.cache, "steps": 0, "over": 0}

    def at_dispatch(ev):
        if ev.get("event") != "span" or ev.get("name") != "dispatch":
            return
        if _consumed(worker.cache):
            wrong.append("a consumed cache is bound after dispatch")
        if worker.cache is seen["handed"] or not _consumed(seen["handed"]):
            wrong.append("the tree the step was handed was not consumed")
        # the program before this one is still the one in flight: its
        # tokens are on the device and the tree IT consumed is its own to
        # drop, at its retirement
        before = worker._flight
        if before is not None:
            seen["over"] += 1
            if before.tok is None or before.tok.is_deleted() \
                    or not _consumed(before.handed):
                wrong.append("the program in flight was already retired")
        seen["handed"] = worker.cache
        seen["steps"] += 1

    rec.add_sink(at_dispatch)
    stop = threading.Event()
    polls = []

    def reader():
        while not stop.is_set():
            tree = worker.cache
            engine.stats()
            worker.describe()
            polls.append(engine.memsampler.ledger.attributed()["kv_pages"])
            if _consumed(tree):
                deadline = time.monotonic() + 10.0
                while worker.cache is tree and time.monotonic() < deadline:
                    time.sleep(0)
                if worker.cache is tree:
                    wrong.append("a consumed cache stayed bound")

    thread = threading.Thread(target=reader, daemon=True)
    engine.start()
    thread.start()
    rng = np.random.default_rng(11)
    tokens = 0
    for plen, olen in _MIXED:
        out = engine.generate(rng.integers(0, 64, plen).astype(np.int32),
                              olen, timeout=60)
        tokens += len(out)
    stop.set()
    thread.join(30)
    assert not thread.is_alive()
    engine.drain()
    assert wrong == []
    # one prefill chunk a request, then one decode step a further token
    assert seen["steps"] == tokens
    # each request alone: every program but its first was dispatched over
    # the one before it
    assert seen["over"] == tokens - len(_MIXED) \
        == engine.stats()["steps_ahead"]
    assert worker._flight is None
    assert polls and set(polls) == {nbytes}
    assert not _consumed(worker.cache)


def test_donated_replay_is_bit_identical_to_undonated_jits():
    """Donation changes where the scatter writes, not what: the mixed
    replay through the donating worker emits the ids of the same replay
    through un-donated jits of the very same step functions, and neither
    retraces after warmup."""
    net = _prompt_dependent_lm(24)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 64, plen).astype(np.int32)
               for plen, _ in _MIXED]

    def replay_through(donate):
        rec = Recorder(path=None)
        engine = _gen_engine(net, rec)
        worker = engine.fleet_workers()[0]
        if not donate:
            worker._prefill_jit = jax.jit(worker._prefill_jit.__wrapped__)
            worker._decode_jit = jax.jit(worker._decode_jit.__wrapped__)
        assert engine.warmup() == 3
        # all queued before the loop starts: slots refill while others
        # decode, so prefill chunks and decode steps interleave
        reqs = [engine.submit_generate(p, olen)
                for p, (_, olen) in zip(prompts, _MIXED)]
        engine.start()
        for req in reqs:
            assert req.wait(60) and req.error is None
        assert engine.trace_count == 3, "a step retraced after warmup"
        engine.drain()
        return ([list(r.emitted) for r in reqs],
                [e["alias_bytes"] for e in _cost_events(rec)])

    donated, aliased = replay_through(True)
    plain, unaliased = replay_through(False)
    assert donated == plain
    assert len({tuple(out) for out in donated}) > 3, \
        "the replay's streams do not depend on the prompt"
    assert [len(out) for out in donated] == [olen for _, olen in _MIXED]
    assert min(aliased) > 0 and unaliased == [0, 0, 0]


def _family_net(family):
    """(net, vocabulary, kv_dtype) of a tiny net of each served family."""
    if family in ("gpt2", "gpt2_int8"):
        return (_prompt_dependent_lm(24), 64,
                "int8" if family == "gpt2_int8" else "f32")
    if family == "latent_moe":
        from tests import test_latent_moe as tiny
    else:
        from tests import test_power_retention as tiny
    return tiny.tiny_net(tiny.seeded_weights(31)), tiny.DIMS["V"], "f32"


# (prompt length, new tokens): 9-16 take two chunks of 8; on three slots
# the slots end mid-batch, get later tenants, and chunks fall between steps
_TENANTS = ((3, 2), (16, 8), (5, 3), (11, 1), (8, 5), (13, 6), (1, 4),
            (9, 8))


@pytest.mark.parametrize("family", ["gpt2", "gpt2_int8", "latent_moe",
                                    "retention"])
def test_replay_one_program_ahead_serves_what_each_request_gets_alone(
        family):
    """The same work: with every program dispatched before the one
    before it is fetched, with positions and the live set advanced at
    dispatch and slots released at retirement, a replay of mixed lengths
    on fewer slots than requests serves each request, token for token,
    what a fresh engine serves it alone. One decode program, compiled at
    warmup, whatever the source of a row's token."""
    net, vocab, kv_dtype = _family_net(family)
    rng = np.random.default_rng(36)
    prompts = [rng.integers(0, vocab, plen).astype(np.int32)
               for plen, _ in _TENANTS]

    def engine_of():
        engine = GenerationEngine(
            net, BucketLattice(batch_sizes=(1,), seq_lens=(8, 16)), slots=3,
            max_new_tokens=8, page_size=8, prefill_chunk=8,
            kv_dtype=kv_dtype)
        return engine, engine.warmup()

    engine, warm = engine_of()
    reqs = [engine.submit_generate(p, olen)
            for p, (_, olen) in zip(prompts, _TENANTS)]
    engine.start()
    for req in reqs:
        assert req.wait(120) and req.error is None
    stats = engine.stats()
    engine.drain()
    assert engine.trace_count == warm, "a step retraced after warmup"
    assert (engine.failed, engine.served) == (0, len(_TENANTS))
    programs = stats["fleet"][0]["decode_steps_run"] + sum(
        -(-plen // 8) for plen, _ in _TENANTS)
    # busy from the first admission to the last token: every program but
    # the first was dispatched over an un-retired one
    assert stats["steps_ahead"] == programs - 1
    served = [list(r.emitted) for r in reqs]
    assert [len(out) for out in served] == [olen for _, olen in _TENANTS]
    assert len({tuple(out[:1]) for out in served}) > 2, \
        "the streams do not depend on the prompt"
    for prompt, (_, olen), out in zip(prompts, _TENANTS, served):
        alone, _ = engine_of()
        alone.start()
        assert alone.generate(prompt, olen, timeout=120) == out
        alone.drain()


def test_drain_returns_after_the_last_program_in_flight_is_emitted():
    """drain() with work queued and a program in flight: it returns only
    when every request holds all its tokens, the last program was retired
    in a pass of its own (nothing was left to dispatch over it) and its
    `emit` is on the record before the `drain`."""
    rec = Recorder(path=None, keep=100_000)
    engine = _gen_engine(_prompt_dependent_lm(24), rec)
    engine.warmup()
    worker = engine.fleet_workers()[0]
    rng = np.random.default_rng(3)
    reqs = [engine.submit_generate(rng.integers(0, 64, plen), olen)
            for plen, olen in ((5, 8), (12, 6), (3, 7))]
    engine.start()
    engine.drain()  # no wait on any request first
    assert all(r.done.is_set() and r.error is None for r in reqs)
    assert [len(r.emitted) for r in reqs] == [8, 6, 7]
    assert worker._flight is None
    assert worker.pool.describe()["pages_in_use"] == 0
    spans = [e for e in rec.events if e["event"] == "span"]
    steps = [e for e in spans if e["name"] in ("prefill_chunk", "decode_step")]
    emits = [e for e in spans if e["name"] == "emit"]
    assert [e["program"] for e in emits] == sorted(
        e["program"] for e in steps)
    [drain] = [e for e in spans if e["name"] == "drain"]
    assert emits[-1]["seq"] < drain["seq"]
    assert (drain["served"], drain["failed"]) == (3, 0)


def _two_chunk_engine(net, rec, **kw):
    """slots=2 over 8-token prefill chunks: a 16-token prompt holds its
    slot through two chunks, with decode steps between them."""
    engine = _gen_engine(net, rec, prefill_chunk=8, **kw)
    engine.warmup()
    worker = engine.fleet_workers()[0]
    allocs = []
    alloc = worker._alloc_cache
    worker._alloc_cache = lambda: allocs.append(1) or alloc()
    return engine, worker, allocs


@pytest.mark.parametrize("speculative_k", [0, 2])
def test_step_that_consumed_the_cache_fails_every_slot_and_serves_on(
        speculative_k):
    """A decode (or verify) step whose execution raises after the cache
    was donated has lost every slot's rows: the decoding slot AND the
    slot still in prefill fail with their pages released, one `error`
    names the loss, a fresh cache of the same shapes takes over without
    a retrace, and the request that was waiting in the queue is served
    correctly."""
    net = _prompt_dependent_lm(24)
    rec = Recorder(path=None)
    engine, worker, allocs = _two_chunk_engine(
        net, rec, speculative_k=speculative_k)
    shapes = jax.tree.map(lambda x: (x.shape, x.dtype), worker.cache)
    warm = engine.trace_count
    step = "_verify_jit" if speculative_k else "_decode_jit"
    real, calls = getattr(worker, step), []

    def consume_then_raise(params, state, cache, *inputs):
        calls.append(1)
        out = real(params, state, cache, *inputs)
        if len(calls) == 1:
            raise RuntimeError("device fault after the cache was donated")
        return out

    setattr(worker, step, consume_then_raise)
    rng = np.random.default_rng(5)
    decoding = engine.submit_generate(rng.integers(0, 64, 5), 6)
    prefilling = engine.submit_generate(rng.integers(0, 64, 16), 4)
    prompt = rng.integers(0, 64, 7).astype(np.int32)
    queued = engine.submit_generate(prompt, 5)  # no slot left: it waits
    engine.start()
    for req in (decoding, prefilling):
        assert req.wait(60)
        assert req.error is not None and "device fault" in req.error
    assert len(decoding.emitted) == 1 and prefilling.emitted == []
    assert queued.wait(60) and queued.error is None
    assert list(queued.emitted) == _greedy_full_forward(net, prompt, 5)
    engine.drain()
    assert allocs == [1]
    assert not _consumed(worker.cache)
    assert jax.tree.map(lambda x: (x.shape, x.dtype), worker.cache) == shapes
    assert engine.trace_count == warm, "the fresh cache retraced a step"
    assert worker.pool.describe()["pages_in_use"] == 0
    assert (engine.failed, engine.served) == (2, 1)
    assert worker.describe()["cache_losses"] == 1
    lost = [e for e in rec.events if e.get("event") == "error"
            and e.get("lost") == "kv_cache"]
    assert len(lost) == 1 and "device fault" in lost[0]["error"]


def test_fault_before_the_call_fails_only_its_slots_and_keeps_the_cache():
    """An injected fault fires before the jit call: nothing was donated,
    so containment stays narrow. The decoding slot fails; the slot whose
    first prompt chunk was already in the cache finishes with the right
    tokens (its rows survived), and no cache is allocated."""
    net = _prompt_dependent_lm(24)
    rec = Recorder(path=None)
    engine, worker, allocs = _two_chunk_engine(net, rec)
    handed = []

    def check(index, unit, count):
        if unit == "decode" and count == 2:
            handed.append(worker.cache)
            raise RuntimeError("injected before the call")

    worker.faults = types.SimpleNamespace(check=check)
    rng = np.random.default_rng(5)
    decoding = engine.submit_generate(rng.integers(0, 64, 5), 6)
    prompt = rng.integers(0, 64, 16).astype(np.int32)
    prefilling = engine.submit_generate(prompt, 4)
    engine.start()
    assert decoding.wait(60) and "injected before" in decoding.error
    assert len(decoding.emitted) == 2  # its prefill's and step 1's
    assert prefilling.wait(60) and prefilling.error is None
    assert list(prefilling.emitted) == _greedy_full_forward(net, prompt, 4)
    engine.drain()
    assert allocs == [] and len(handed) == 1
    assert (engine.failed, engine.served) == (1, 1)
    assert worker.pool.describe()["pages_in_use"] == 0
    assert worker.describe()["cache_losses"] == 0
    assert not [e for e in rec.events if e.get("lost") == "kv_cache"]


def test_consumed_cache_with_a_program_in_flight_fails_every_slot_once():
    """The third decode step consumes the cache and raises while the
    second prompt's final chunk is in flight. That chunk ran before the
    fault on a sound cache: it is retired and its first token reaches
    its request; then every occupied slot fails ONCE, one cache is
    allocated, `cache_losses` is 1, every page is back, and the request
    that waited is served right, without a retrace."""
    net = _prompt_dependent_lm(24)
    rec = Recorder(path=None)
    engine, worker, allocs = _two_chunk_engine(net, rec)
    warm = engine.trace_count
    real, calls, in_flight = worker._decode_jit, [], []

    def consume_then_raise(params, state, cache, *inputs):
        calls.append(1)
        out = real(params, state, cache, *inputs)
        if len(calls) == 3:
            in_flight.append(worker._flight)
            raise RuntimeError("device fault after the cache was donated")
        return out

    worker._decode_jit = consume_then_raise
    rng = np.random.default_rng(5)
    decoding = engine.submit_generate(rng.integers(0, 64, 5), 6)
    prefilling = engine.submit_generate(rng.integers(0, 64, 16), 4)
    prompt = rng.integers(0, 64, 7).astype(np.int32)
    queued = engine.submit_generate(prompt, 5)  # no slot left: it waits
    engine.start()
    for req in (decoding, prefilling):
        assert req.wait(60) and "device fault" in req.error
    # chunk + steps 1 and 2; the final chunk that was in flight
    assert (len(decoding.emitted), len(prefilling.emitted)) == (3, 1)
    [flight] = in_flight
    assert flight is not None and [i for i, _ in flight.rows] == [1]
    assert queued.wait(60) and queued.error is None
    assert list(queued.emitted) == _greedy_full_forward(net, prompt, 5)
    engine.drain()
    assert allocs == [1] and worker._flight is None
    assert not _consumed(worker.cache)
    assert engine.trace_count == warm, "the fresh cache retraced a step"
    assert worker.pool.describe()["pages_in_use"] == 0
    assert (engine.failed, engine.served) == (2, 1)
    assert worker.describe()["cache_losses"] == 1
    assert len([e for e in rec.events if e.get("event") == "request"
                and not e["ok"]]) == 2     # once each
    assert len([e for e in rec.events if e.get("lost") == "kv_cache"]) == 1


def test_a_fetch_that_raises_loses_the_cache_and_what_flew_on_it():
    """A program that fails on the device shows when its tokens are
    fetched, a pass late: what was dispatched on its output is poisoned
    with it. Every occupied slot fails once, the in-flight record and the
    poisoned token vector go, one cache is allocated, and the worker
    serves the next request right."""
    net = _prompt_dependent_lm(24)
    rec = Recorder(path=None)
    engine, worker, allocs = _two_chunk_engine(net, rec)
    real, calls = worker._decode_jit, []

    class Poisoned:
        """The second decode step's tokens: the next program can be
        enqueued on them, the host cannot have them."""

        def __init__(self, tok):
            self.tok = tok

        def copy_to_host_async(self):
            pass

        def __array__(self, *a, **kw):
            raise RuntimeError("the program failed on the device")

    def second_step_fails_late(params, state, cache, last, *inputs):
        calls.append(1)
        tok, out = real(params, state, cache, getattr(last, "tok", last),
                        *inputs)
        return (Poisoned(tok) if len(calls) == 2 else tok), out

    worker._decode_jit = second_step_fails_late
    rng = np.random.default_rng(5)
    first = engine.submit_generate(rng.integers(0, 64, 5), 6)
    second = engine.submit_generate(rng.integers(0, 64, 4), 6)
    prompt = rng.integers(0, 64, 7).astype(np.int32)
    queued = engine.submit_generate(prompt, 5)
    engine.start()
    for req in (first, second):
        assert req.wait(60) and "failed on the device" in req.error
    assert queued.wait(60) and queued.error is None
    assert list(queued.emitted) == _greedy_full_forward(net, prompt, 5)
    engine.drain()
    assert allocs == [1] and worker._flight is None
    assert not _consumed(worker.cache)
    assert len(calls) >= 3  # the third step was dispatched on the lost one
    assert (engine.failed, engine.served) == (2, 1)
    assert worker.describe()["cache_losses"] == 1
    assert worker.pool.describe()["pages_in_use"] == 0
    assert len([e for e in rec.events if e.get("lost") == "kv_cache"]) == 1


def test_fault_before_the_call_emits_the_tokens_of_the_program_in_flight():
    """The third decode step's fault fires before its jit call, with the
    second step in flight: that step is retired first, so both requests
    hold its token when they fail (the chunk's, step 1's and step 2's:
    three), the cache is kept, and the next request is served right."""
    net = _prompt_dependent_lm(24)
    rec = Recorder(path=None)
    engine, worker, allocs = _two_chunk_engine(net, rec)
    in_flight = []

    def check(index, unit, count):
        if unit == "decode" and count == 4:
            in_flight.append(worker._flight)
            raise RuntimeError("injected before the call")

    worker.faults = types.SimpleNamespace(check=check)
    rng = np.random.default_rng(5)
    # one chunk each: chunk a, chunk b + step 1 (a alone), steps 2, 3, [4]
    a = engine.submit_generate(rng.integers(0, 64, 5), 6)
    b = engine.submit_generate(rng.integers(0, 64, 4), 6)
    prompt = rng.integers(0, 64, 7).astype(np.int32)
    queued = engine.submit_generate(prompt, 5)
    engine.start()
    for req in (a, b):
        assert req.wait(60) and "injected before" in req.error
    [flight] = in_flight
    assert sorted(i for i, _ in flight.rows) == [0, 1]
    assert (len(a.emitted), len(b.emitted)) == (4, 3)
    assert queued.wait(60) and queued.error is None
    assert list(queued.emitted) == _greedy_full_forward(net, prompt, 5)
    engine.drain()
    assert allocs == [] and worker._flight is None
    assert (engine.failed, engine.served) == (2, 1)
    assert worker.describe()["cache_losses"] == 0
    assert worker.pool.describe()["pages_in_use"] == 0


@pytest.mark.parametrize("consumed", [False, True])
def test_failed_slot_says_why_on_stderr_with_telemetry_off(consumed, capfd):
    """An untraced server keeps no `error` event (NullRecorder) and the
    client's copy of the error dies with the client: the failed slot's
    one line on the server's own stderr is all that is left. It names
    the request, the slot, the replica, the exception and whether the
    cache went with it."""
    engine = _gen_engine(replay._tiny_lm(24), None)
    assert not engine.recorder.live
    engine.warmup()
    worker = engine.fleet_workers()[0]
    real = worker._decode_jit

    def fail(params, state, cache, *inputs):
        if consumed:
            real(params, state, cache, *inputs)
        raise RuntimeError("no such device")

    worker._decode_jit = fail
    req = engine.submit_generate(np.arange(5, dtype=np.int32), 4,
                                 request_id="r7.1")
    engine.start()
    assert req.wait(60) and "no such device" in req.error
    engine.drain()
    lines = [l for l in capfd.readouterr().err.splitlines()
             if l.startswith("[gen-replica 0]")]
    assert lines == [
        "[gen-replica 0] request r7.1 failed in slot 0 after 1 tokens "
        f"(kv cache lost: {consumed}): RuntimeError: no such device"]
    assert worker.describe()["cache_losses"] == int(consumed)


# ------------------------------------------------------- HTTP round trip

@pytest.fixture(scope="module")
def gen_stack():
    net = replay._tiny_lm(24)
    rec = Recorder(path=None)
    lat = BucketLattice(batch_sizes=(1,), seq_lens=(8, 16))
    engine = GenerationEngine(net, lat, slots=2, max_new_tokens=8,
                              page_size=8, recorder=rec)
    engine.warmup()
    server = ServingServer(engine, port=0).start()
    yield net, engine, server, rec
    server.stop()


def test_generate_http_streams_tokens_and_summary(gen_stack):
    net, engine, server, _ = gen_stack
    rng = np.random.default_rng(17)
    prompt = rng.integers(0, 64, 6).astype(np.int32)
    body = json.dumps({"tokens": prompt.tolist(),
                       "max_new_tokens": 5}).encode()
    req = urllib.request.Request(
        f"{server.url}/generate", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as resp:
        lines = [json.loads(l) for l in resp.read().splitlines() if l]
    assert [l["token"] for l in lines[:-1]] == lines[-1]["tokens"]
    summary = lines[-1]
    assert summary["done"] and len(summary["tokens"]) == 5
    assert summary["timing"]["total_s"] >= summary["timing"]["ttft_s"] > 0
    # HTTP tokens match the engine's own greedy decode
    assert summary["tokens"] == _greedy_full_forward(net, prompt, 5)


def test_generate_http_rejects_oversized_and_post_drain(gen_stack):
    _, _, server, _ = gen_stack
    too_long = {"tokens": list(range(17))}  # lattice max seq is 16
    req = urllib.request.Request(
        f"{server.url}/generate", data=json.dumps(too_long).encode(),
        headers={"Content-Type": "application/json"})
    with pytest.raises(urllib.error.HTTPError) as e:
        urllib.request.urlopen(req, timeout=30)
    assert e.value.code == 400


def test_listener_holds_a_burst_of_callers_before_anyone_accepts():
    """The accept queue: 32 callers that connect at the same instant (a
    closed loop's first moment) all complete their handshake while the
    accept thread has not run at all. At socketserver's default of 5 the
    kernel drops the seventh SYN (that connect times out here) and
    resets some: the front door failed requests the engine never saw."""
    engine = _gen_engine(replay._tiny_lm(24), None)
    server = ServingServer(engine, port=0)  # bound and listening, not started
    host, port = server._httpd.server_address[:2]
    conns = []
    try:
        for _ in range(32):
            conns.append(socket.create_connection((host, port), timeout=0.5))
    finally:
        for c in conns:
            c.close()
        server._httpd.server_close()
    assert len(conns) == 32


def test_closed_loop_soak_over_http_fails_no_request():
    """Twice as many callers as slots over real HTTP, all starting at
    the same instant, each sending its next request the moment the last
    completes (a failed one too, as the benchmark's load generator
    does), for three seconds: slots turn over under a full queue, every
    step donates the cache, and no request fails, at the front door or
    in the engine. Each stream is the greedy decode of its prompt."""
    net = _prompt_dependent_lm(24)
    engine = _gen_engine(net, None, slots=4)
    engine.warmup()
    server = ServingServer(engine, port=0).start()
    host, port = server._httpd.server_address[:2]
    rng = np.random.default_rng(29)
    work = [(rng.integers(0, 64, int(rng.integers(2, 17))).tolist(),
             int(rng.integers(2, 9))) for _ in range(8)]
    want = [_greedy_full_forward(net, p, n) for p, n in work]
    n_callers, seconds = 8, 3.0
    gate = threading.Barrier(n_callers)
    lock = threading.Lock()
    sent, failures = [0], []

    def caller(k):
        gate.wait(30)
        t_end = time.monotonic() + seconds
        while time.monotonic() < t_end:
            prompt, n = work[k % len(work)]
            status = got = None
            try:
                conn = http.client.HTTPConnection(host, port, timeout=30)
                conn.request("POST", "/generate", body=json.dumps(
                    {"tokens": prompt, "max_new_tokens": n}),
                    headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                status = resp.status
                got = [json.loads(l) for l in resp.read().splitlines()][-1]
                conn.close()
            except Exception as exc:  # a reset connection is a failure
                got = f"{type(exc).__name__}: {exc}"
            with lock:
                sent[0] += 1
                if not (isinstance(got, dict) and status == 200
                        and got.get("error") is None
                        and got.get("tokens") == want[k % len(work)]):
                    failures.append((k, status, got))
            k += 1

    threads = [threading.Thread(target=caller, args=(k,), daemon=True)
               for k in range(n_callers)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(seconds + 60)
        assert not any(t.is_alive() for t in threads)
    finally:
        server.stop()
    assert failures == []
    assert sent[0] >= 4 * n_callers
    worker = engine.fleet_workers()[0]
    assert (engine.failed, worker.describe()["cache_losses"]) == (0, 0)
    assert engine.served == sent[0]
    assert engine.trace_count == 3 and not _consumed(worker.cache)


def test_end_to_end_generation_replay_artifact(tmp_path):
    """The full rc=0 path at small scale: generation replay over real
    HTTP with streaming reads, scoreboard from telemetry alone, SERVE
    artifact written, truncation-proof via the summary line."""
    from deeplearning4j_tpu.telemetry import artifact as art

    tpath = str(tmp_path / "telemetry.jsonl")
    apath = str(tmp_path / "SERVE_gen.json")
    sb = replay.run_generation_replay(
        seed=0, n_requests=10, prompt_lengths=(8, 16),
        output_lengths=(2, 4), slots=2, page_size=8,
        telemetry_path=tpath, artifact_path=apath)
    assert sb["n_ok"] == 10
    assert sb["recompiles_after_warmup"] == 0
    assert sb["tokens_per_sec"] > 0
    assert sb["ttft_p99_ms"] >= sb["ttft_p50_ms"] > 0
    assert 0 < sb["page_occupancy_peak"] <= 1
    full = art.load(apath)
    assert full["serving_generate_tokens_per_sec"]["value"] == \
        sb["tokens_per_sec"]
    with open(apath) as fh:
        last = fh.read().splitlines()[-1]
    cut = str(tmp_path / "cut.json")
    with open(cut, "w") as fh:
        fh.write(last + "\n")
    recovered = art.load(cut)
    for metric in ("serving_generate_tokens_per_sec",
                   "serving_generate_ttft_p50_ms",
                   "serving_generate_ttft_p99_ms",
                   "serving_generate_page_occupancy",
                   "serving_generate_recompiles_after_warmup"):
        assert recovered[metric]["value"] == full[metric]["value"]
