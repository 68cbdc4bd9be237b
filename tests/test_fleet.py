"""Tier-1 gate for zero-downtime fleet operations (ISSUE 13,
serving/fleet.py).

The acceptance properties are asserted FROM THE TELEMETRY JSONL ALONE:
a mid-traffic hot-swap with zero failed requests and the weight
generation flip visible in `request` events; a replica-kill chaos
replay where only the in-flight batch fails, the respawned replica
serves again, and the trace counter stays frozen (0 retraces). The
swap/supervisor state machines are additionally proven as pure
functions on fake clocks — hysteresis, respawn backoff jitter caps,
double-buffer flip ordering, failed-restore rollback — with no sleeps.

Every test that spawns a supervisor/engine thread runs under a hard
wall-clock deadline: each blocking wait carries an explicit timeout
(DEADLINE_S) and asserts it was not hit, so a wedged fleet fails the
test instead of hanging the suite.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.serving import fleet
from deeplearning4j_tpu.serving.batcher import Batcher, PendingRequest
from deeplearning4j_tpu.serving.buckets import BucketLattice
from deeplearning4j_tpu.serving.engine import InferenceEngine
from deeplearning4j_tpu.serving.fleet import (AutoscalePolicy,
                                              AutoscaleState,
                                              CheckpointWatcher,
                                              FleetSupervisor,
                                              ReplicaFaultInjector,
                                              ReplicaKilled, RespawnBackoff,
                                              WeightStore, WeightSwapError,
                                              autoscale_decision)
from deeplearning4j_tpu.serving.server import ServingServer
from deeplearning4j_tpu.serving import replay
from deeplearning4j_tpu.telemetry import Recorder

pytestmark = [pytest.mark.serving, pytest.mark.fleet]

# the hard deadline every spawned-supervisor wait runs under
DEADLINE_S = 30.0


def _mlp():
    return replay._tiny_mlp()


def _benchdiff():
    """tools/benchdiff.py as a module (the test_benchdiff.py idiom —
    tools/ is not a package)."""
    import importlib.util as ilu
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = ilu.spec_from_file_location(
        "benchdiff_fleet_test", os.path.join(root, "tools",
                                             "benchdiff.py"))
    mod = ilu.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _events(path, kind):
    out = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line.startswith("{"):
                continue
            ev = json.loads(line)
            if ev.get("event") == kind:
                out.append(ev)
    return out


def _save_publish_checkpoint(net, step, tmp_path, *, bump=0.5):
    """The 'training fleet publishes a step' half: the net's params
    shifted by `bump`, saved as an Orbax host checkpoint at `step`."""
    import jax

    from deeplearning4j_tpu.util.orbax_checkpoint import ShardedCheckpointer

    pub = net.clone()
    pub.params = jax.tree.map(lambda a: a + bump, pub.params)
    pub.iteration_count = step
    ckdir = str(tmp_path / f"publish_{step}")
    ShardedCheckpointer(ckdir).save(pub, step, host=True)
    return ckdir


# ------------------------------------------------------ pure: weight store

def test_weight_store_flip_ordering_and_immutability():
    store = WeightStore({"w": 1}, {"s": 1}, step=3)
    before = store.current
    assert (before.generation, before.step) == (0, 3)
    new = store.publish({"w": 2}, {"s": 2}, step=9)
    # the flip is a single reference swap to a FULLY-built set
    assert store.current is new
    assert (new.generation, new.step) == (1, 9)
    # the old set stays intact for in-flight readers
    assert before.params == {"w": 1} and before.generation == 0
    assert store.last_swap_ts is not None
    # frozen: a reader can never mutate a published set
    with pytest.raises(Exception):
        new.params = {}


def test_weight_store_concurrent_readers_see_whole_generations():
    """Readers racing a publisher observe only complete (gen, step)
    pairs — never generation N with generation N+1's step."""
    store = WeightStore({"w": 0}, None, step=0)
    seen = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            ws = store.current
            seen.append((ws.generation, ws.step, ws.params["w"]))

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    for g in range(1, 50):
        store.publish({"w": g}, None, step=g * 10)
    stop.set()
    t.join(timeout=DEADLINE_S)
    assert not t.is_alive(), "reader missed its deadline"
    for gen, step, w in seen:
        assert step == gen * 10 and w == gen, "torn read across the flip"


# -------------------------------------------------- pure: respawn backoff

def test_respawn_backoff_growth_cap_and_jitter_cap():
    b = RespawnBackoff(base_s=0.1, factor=2.0, cap_s=0.8, jitter_frac=0.25,
                       seed=7)
    delays = [b.next() for _ in range(8)]
    bases = [0.1, 0.2, 0.4, 0.8, 0.8, 0.8, 0.8, 0.8]
    for d, base in zip(delays, bases):
        assert base <= d <= base * 1.25 + 1e-12, (d, base)
    # the TOTAL is capped: never more than cap * (1 + jitter_frac)
    assert max(delays) <= 0.8 * 1.25 + 1e-12
    # deterministic: same seed, same ladder
    b2 = RespawnBackoff(base_s=0.1, factor=2.0, cap_s=0.8,
                        jitter_frac=0.25, seed=7)
    assert [b2.next() for _ in range(8)] == delays
    b2.reset()
    assert b2.next() <= 0.1 * 1.25


def test_respawn_backoff_rejects_bad_jitter():
    with pytest.raises(ValueError, match="jitter_frac"):
        RespawnBackoff(jitter_frac=1.5)


# ---------------------------------------------- pure: autoscale hysteresis

def test_autoscale_scale_up_on_queue_depth_with_cooldown():
    p = AutoscalePolicy(min_replicas=1, max_replicas=3, up_queue_depth=8,
                        down_queue_depth=1, cooldown_up_s=1.0,
                        cooldown_down_s=5.0)
    s = AutoscaleState()
    assert autoscale_decision(p, s, queue_depth=10, p99_ms=0.0,
                              n_replicas=1, now=0.0) == 1
    # cooldown: an immediate second burst sample does NOT double-grow
    assert autoscale_decision(p, s, queue_depth=50, p99_ms=0.0,
                              n_replicas=2, now=0.5) == 0
    assert autoscale_decision(p, s, queue_depth=50, p99_ms=0.0,
                              n_replicas=2, now=1.1) == 1
    # ceiling: never above max_replicas
    assert autoscale_decision(p, s, queue_depth=50, p99_ms=0.0,
                              n_replicas=3, now=9.0) == 0


def test_autoscale_scale_down_hysteresis_and_floor():
    p = AutoscalePolicy(min_replicas=1, max_replicas=3, up_queue_depth=8,
                        down_queue_depth=1, cooldown_up_s=0.5,
                        cooldown_down_s=4.0)
    s = AutoscaleState()
    assert autoscale_decision(p, s, queue_depth=10, p99_ms=0.0,
                              n_replicas=1, now=0.0) == 1
    # idle right after the burst: the up-flip armed the down cooldown
    assert autoscale_decision(p, s, queue_depth=0, p99_ms=0.0,
                              n_replicas=2, now=1.0) == 0
    # between the low and high water marks: hold (hysteresis band)
    assert autoscale_decision(p, s, queue_depth=4, p99_ms=0.0,
                              n_replicas=2, now=10.0) == 0
    assert autoscale_decision(p, s, queue_depth=0, p99_ms=0.0,
                              n_replicas=2, now=10.0) == -1
    # down cooldown: one drain per window, and never below the floor
    assert autoscale_decision(p, s, queue_depth=0, p99_ms=0.0,
                              n_replicas=2, now=11.0) == 0
    assert autoscale_decision(p, s, queue_depth=0, p99_ms=0.0,
                              n_replicas=1, now=99.0) == 0


def test_autoscale_p99_trigger():
    p = AutoscalePolicy(max_replicas=2, up_queue_depth=10 ** 9,
                        up_p99_ms=50.0, cooldown_up_s=0.0)
    s = AutoscaleState()
    assert autoscale_decision(p, s, queue_depth=0, p99_ms=80.0,
                              n_replicas=1, now=0.0) == 1


# ------------------------------------------------- pure: fault injection

def test_replica_fault_injector_fires_once_and_records():
    rec = Recorder(path=None)
    inj = ReplicaFaultInjector("r1:kill@batch3", recorder=rec)
    inj.check(0, "batch", 3)      # wrong replica: silent
    inj.check(1, "batch", 2)      # wrong count: silent
    inj.check(1, "decode", 3)     # wrong unit: silent
    with pytest.raises(ReplicaKilled):
        inj.check(1, "batch", 3)
    # one-shot: a respawned replica reaching batch 3 again is NOT re-killed
    inj.check(1, "batch", 3)
    faults = [e for e in rec.events if e.get("event") == "fault"]
    assert len(faults) == 1
    assert faults[0]["kind"] == "replica-kill"
    assert faults[0]["spec"] == "r1:kill@batch3"


def test_latest_step_sees_only_committed_steps(tmp_path):
    d = tmp_path / "ck"
    assert fleet.latest_step(str(d)) is None
    (d / "step_3").mkdir(parents=True)
    (d / "step_7").mkdir()
    (d / "step_3" / "meta.json").write_text("{}")
    # step_7 has no meta.json: mid-write, invisible
    assert fleet.latest_step(str(d)) == 3
    (d / "step_7" / "meta.json").write_text("{}")
    assert fleet.latest_step(str(d)) == 7


# --------------------------------------------- batcher requeue (no sleeps)

def test_batcher_requeue_puts_requests_back_at_fifo_head():
    now = {"t": 0.0}
    b = Batcher(BucketLattice(batch_sizes=(1, 2, 4)), max_wait_ms=5.0,
                clock=lambda: now["t"])
    first = b.submit(np.zeros(3, np.float32))
    second = b.submit(np.ones(3, np.float32))
    now["t"] = 0.006
    batch = b.next_batch(timeout=0.5)
    assert batch.n_real == 2 and b.depth == 0
    # a reaped replica hands its batch's requests back: FIFO order kept
    b.requeue(batch.requests)
    assert b.depth == 2
    again = b.next_batch(timeout=0.5)
    assert again.requests[0] is first and again.requests[1] is second
    # requeue works even while draining (they were already admitted)
    b.close()
    b.requeue([first])
    assert b.next_batch(timeout=0.0).requests == [first]


# ----------------------------------------- acceptance: live hot-swap

def test_hot_swap_mid_traffic_zero_failed_from_telemetry(tmp_path):
    """THE swap acceptance, from the JSONL alone: traffic before,
    during, and after a live hot-swap; zero failed requests; the typed
    weight_swap event (step, restore_ms, generation); and the
    generation flip visible in the request events' weight_gen."""
    tpath = str(tmp_path / "telemetry.jsonl")
    rec = Recorder(tpath)
    net = _mlp()
    engine = InferenceEngine(net, BucketLattice(batch_sizes=(1, 2)),
                             max_wait_ms=1.0, recorder=rec)
    engine.warmup(np.zeros(8, np.float32))
    engine.start()
    ckdir = _save_publish_checkpoint(net, 5, tmp_path)

    x = np.ones(8, np.float32)
    outs = []
    done_half = threading.Event()
    swap_done = threading.Event()
    finished = threading.Event()

    def traffic():
        for i in range(20):
            outs.append(np.asarray(engine.predict(x, timeout=DEADLINE_S)))
            if i == 9:
                done_half.set()
                # the second half of the traffic overlaps and follows
                # the swap — without this gate a fast forward path can
                # finish all 20 requests before the restore completes
                swap_done.wait(DEADLINE_S)
        finished.set()

    t = threading.Thread(target=traffic, daemon=True)
    t.start()
    assert done_half.wait(DEADLINE_S), "traffic missed its deadline"
    swap = fleet.hot_swap(engine, ckdir)   # mid-traffic, off the req path
    swap_done.set()
    assert swap["step"] == 5 and swap["generation"] == 1
    assert finished.wait(DEADLINE_S), "traffic missed its deadline"
    t.join(DEADLINE_S)
    engine.drain(DEADLINE_S)
    rec.close()

    reqs = _events(tpath, "request")
    assert len(reqs) == 20
    assert all(e["ok"] for e in reqs), "a request failed across the swap"
    gens = [e["weight_gen"] for e in reqs]
    assert set(gens) == {0, 1}, "the flip never became visible"
    # generations are monotonic in completion order: old, then new
    assert gens == sorted(gens)
    swaps = _events(tpath, "weight_swap")
    assert len(swaps) == 1 and swaps[0]["ok"]
    assert swaps[0]["step"] == 5 and swaps[0]["generation"] == 1
    assert swaps[0]["restore_ms"] > 0
    # the new weights actually serve: outputs changed across the flip
    assert not np.allclose(outs[0], outs[-1])


def test_hot_swap_rejects_mismatched_and_truncated_checkpoints(tmp_path):
    """Failed-restore rollback: a checkpoint from a different
    architecture and a truncated step directory are both rejected with
    the OLD weights still serving (same outputs, same generation), and
    the rejection is on the telemetry record."""
    tpath = str(tmp_path / "telemetry.jsonl")
    rec = Recorder(tpath)
    engine = InferenceEngine(_mlp(), BucketLattice(batch_sizes=(1, 2)),
                             max_wait_ms=1.0, recorder=rec)
    engine.warmup(np.zeros(8, np.float32))
    engine.start()
    x = np.ones(8, np.float32)
    before = np.asarray(engine.predict(x, timeout=DEADLINE_S))

    # (a) wrong architecture: different OUTPUT width
    from deeplearning4j_tpu.util.orbax_checkpoint import ShardedCheckpointer

    other = replay._tiny_mlp(n_in=8, n_out=7)
    bad_dir = str(tmp_path / "wrong_arch")
    ShardedCheckpointer(bad_dir).save(other, 3, host=True)
    with pytest.raises(WeightSwapError):
        fleet.hot_swap(engine, bad_dir)

    # (a') wrong HIDDEN width — the insidious case: the reshard-aware
    # restore reads only the slices a target template asks for, so
    # without the PRE-restore metadata gate this partially loads into
    # correctly-shaped garbage that a post-restore check cannot see
    from deeplearning4j_tpu.nn.conf import (DenseLayer,
                                            NeuralNetConfiguration,
                                            OutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    narrow_conf = (NeuralNetConfiguration.builder().seed(1).list()
                   .layer(DenseLayer(n_in=8, n_out=5, activation="relu"))
                   .layer(OutputLayer(n_in=5, n_out=4,
                                      activation="softmax",
                                      loss_function="mcxent"))
                   .build())
    narrow = MultiLayerNetwork(narrow_conf).init()
    narrow.iteration_count = 3
    narrow_dir = str(tmp_path / "wrong_hidden")
    ShardedCheckpointer(narrow_dir).save(narrow, 3, host=True)
    with pytest.raises(WeightSwapError, match="mismatch"):
        fleet.hot_swap(engine, narrow_dir)

    # (b) truncated checkpoint: a committed-looking step with its
    # array data gutted
    import os
    import shutil

    ckdir = _save_publish_checkpoint(engine.net, 4, tmp_path)
    step_dir = os.path.join(ckdir, "step_4")
    shutil.rmtree(os.path.join(step_dir, "model"))
    with pytest.raises(WeightSwapError):
        fleet.hot_swap(engine, ckdir)

    # old weights still serving, generation unmoved
    after = np.asarray(engine.predict(x, timeout=DEADLINE_S))
    np.testing.assert_array_equal(before, after)
    assert engine.weights.generation == 0
    engine.drain(DEADLINE_S)
    rec.close()
    swaps = _events(tpath, "weight_swap")
    assert len(swaps) == 3 and not any(s["ok"] for s in swaps)
    assert all(s["generation"] == 0 for s in swaps)
    assert all(e["ok"] for e in _events(tpath, "request"))


def test_checkpoint_watcher_follows_publishes_and_skips_rejects(tmp_path):
    """The train-fleet-publishes loop: poll_once swaps each newly
    committed step exactly once, ignores already-seen steps, and never
    hot-loops on a rejected one."""
    engine = InferenceEngine(_mlp(), BucketLattice(batch_sizes=(1,)),
                             max_wait_ms=1.0, recorder=Recorder(path=None))
    engine.warmup(np.zeros(8, np.float32))
    ckdir = _save_publish_checkpoint(engine.net, 2, tmp_path)
    watcher = CheckpointWatcher(engine, ckdir, interval_s=0.01)
    out = watcher.poll_once()
    assert out["ok"] and out["step"] == 2
    assert engine.weights.generation == 1
    assert watcher.poll_once() is None  # nothing new
    # publish step 6 with GUTTED data -> rejected once, then quiet
    import os
    import shutil

    from deeplearning4j_tpu.util.orbax_checkpoint import ShardedCheckpointer

    pub = engine.net.clone()
    pub.iteration_count = 6
    ShardedCheckpointer(ckdir).save(pub, 6, host=True)
    shutil.rmtree(os.path.join(ckdir, "step_6", "model"))
    out = watcher.poll_once()
    assert out is not None and not out["ok"] and out["step"] == 6
    assert engine.weights.generation == 1  # old weights still serving
    assert watcher.poll_once() is None     # rejected step not retried


def test_hot_swap_refuses_generation_engines():
    from deeplearning4j_tpu.serving.engine import GenerationEngine

    net = replay._tiny_lm(16)
    engine = GenerationEngine(
        net, BucketLattice(batch_sizes=(1,), seq_lens=(8, 16)),
        slots=2, max_new_tokens=4, recorder=Recorder(path=None))
    with pytest.raises(WeightSwapError, match="KV cache"):
        fleet.hot_swap(engine, "/nonexistent")


# ------------------------------------- acceptance: replica chaos healing

def test_replica_kill_chaos_only_inflight_batch_fails_zero_retraces(
        tmp_path):
    """THE self-healing acceptance, from the JSONL alone: an injected
    replica kill fails ONLY the in-flight batch, the supervisor reaps
    and respawns (respawn_ms on the record), the respawned replica
    serves again, and the trace counter stays frozen — 0 non-warmup
    compiles."""
    tpath = str(tmp_path / "telemetry.jsonl")
    rec = Recorder(tpath)
    engine = InferenceEngine(_mlp(), BucketLattice(batch_sizes=(1, 2)),
                             max_wait_ms=1.0, recorder=rec,
                             faults="r0:kill@batch2")
    engine.warmup(np.zeros(8, np.float32))
    trace_frozen_at = engine.trace_count
    engine.start()
    supervisor = FleetSupervisor(
        engine, death_after_s=1.0,
        backoff=RespawnBackoff(base_s=0.0, jitter_frac=0.0), recorder=rec)
    x = np.ones(8, np.float32)
    ok_before = np.asarray(engine.predict(x, timeout=DEADLINE_S))  # batch 1
    with pytest.raises(RuntimeError, match="ReplicaKilled"):
        engine.predict(x, timeout=DEADLINE_S)                      # batch 2
    actions = supervisor.poll()
    assert actions["reaped"] == [0] and actions["respawned"] == [0]
    ok_after = np.asarray(engine.predict(x, timeout=DEADLINE_S))
    np.testing.assert_array_equal(ok_before, ok_after)
    assert engine.trace_count == trace_frozen_at, "respawn retraced"
    engine.drain(DEADLINE_S)
    rec.close()

    reqs = _events(tpath, "request")
    failed = [e for e in reqs if not e["ok"]]
    assert len(failed) == 1, "more than the in-flight batch failed"
    assert "ReplicaKilled" in failed[0]["error"]
    assert [e["ok"] for e in reqs].count(True) == 2
    kinds = [e["kind"] for e in _events(tpath, "fault")]
    assert kinds == ["replica-kill", "replica-dead", "replica-respawn"]
    respawn = _events(tpath, "fault")[-1]
    assert respawn["respawn_ms"] >= 0
    compiles = [e for e in _events(tpath, "span")
                if e.get("name") == "compile"]
    assert compiles and all(e.get("warmup") for e in compiles), \
        "a non-warmup compile leaked into the chaos replay"


def test_replica_hang_reaped_by_heartbeat_and_queue_drains_back(tmp_path):
    """The hang half: a wedged replica is detected by heartbeat
    staleness (fake `now`), its in-flight batch fails loudly, its
    QUEUED batch drains back to the batcher and completes on the
    respawned replica."""
    rec = Recorder(path=None)
    engine = InferenceEngine(_mlp(), BucketLattice(batch_sizes=(1,)),
                             max_wait_ms=0.5, recorder=rec,
                             faults="r0:hang@batch1")
    engine.warmup(np.zeros(8, np.float32))
    engine.start()
    supervisor = FleetSupervisor(
        engine, death_after_s=2.0,
        backoff=RespawnBackoff(base_s=0.0, jitter_frac=0.0), recorder=rec)
    x = np.ones(8, np.float32)
    hung = engine.submit(x)      # batch 1: the replica wedges on it
    queued = engine.submit(x)    # lands in the wedged replica's queue
    replica = engine.fleet_workers()[0]
    deadline = threading.Event()
    for _ in range(int(DEADLINE_S / 0.01)):
        if replica.current_batch is not None:
            break
        deadline.wait(0.01)
    assert replica.current_batch is not None, "hang never engaged"
    # heartbeat staleness via a FAKE now — no real waiting; the zero
    # backoff lets the same poll reap AND respawn
    actions = supervisor.poll(now=engine._clock() + 10.0)
    assert actions["reaped"] == [0] and actions["respawned"] == [0]
    assert hung.wait(DEADLINE_S) and hung.error is not None
    assert "reaped" in hung.error
    assert queued.wait(DEADLINE_S), "requeued batch missed its deadline"
    assert queued.error is None and queued.result is not None
    engine.drain(2.0)


def test_gen_worker_kill_mid_decode_releases_pages_and_respawns(tmp_path):
    """The generation twin: a mid-decode kill fails the active slots
    (pages released — the pool returns to empty), the supervisor
    respawns the worker with ZERO new compiles, and queued work
    completes."""
    tpath = str(tmp_path / "telemetry.jsonl")
    rec = Recorder(tpath)
    from deeplearning4j_tpu.serving.engine import GenerationEngine

    net = replay._tiny_lm(24)
    engine = GenerationEngine(
        net, BucketLattice(batch_sizes=(1,), seq_lens=(8,)),
        slots=2, max_new_tokens=8, page_size=4, recorder=rec,
        faults="r0:kill@decode2")
    engine.warmup()
    trace_frozen_at = engine.trace_count
    engine.start()
    supervisor = FleetSupervisor(
        engine, death_after_s=1.0,
        backoff=RespawnBackoff(base_s=0.0, jitter_frac=0.0), recorder=rec)
    prompt = np.arange(8, dtype=np.int32)
    req = engine.submit_generate(prompt, max_new_tokens=6)
    assert req.wait(DEADLINE_S), "killed generation missed its deadline"
    assert req.error is not None and "ReplicaKilled" in req.error
    # step 1 was in flight when the kill fired before step 2's call: it
    # was retired first, so the request holds the chunk's token and its
    worker = engine.fleet_workers()[0]
    assert len(req.emitted) == 2 and worker._flight is None
    assert worker.lifecycle == "dead"
    assert worker.pool.describe()["pages_in_use"] == 0, \
        "a dead slot leaked its pages"
    actions = supervisor.poll()
    assert actions["respawned"] == [0]
    toks = engine.generate(prompt, max_new_tokens=6, timeout=DEADLINE_S)
    assert len(toks) == 6
    assert engine.trace_count == trace_frozen_at, "respawn retraced"
    engine.drain(DEADLINE_S)
    rec.close()
    kinds = [e["kind"] for e in _events(tpath, "fault")]
    assert kinds == ["replica-kill", "replica-dead", "replica-respawn"]


def test_gen_worker_kill_retires_the_program_in_flight_first():
    """A kill before decode step 3's call, with step 2 in flight over
    two slots: step 2 spent the short request's budget, so that request
    is not among step 3's rows. It must not be left holding its slot on
    a dead worker: step 2 is retired (the short request completes, its
    pages come back), then the rows of the step that died fail."""
    from deeplearning4j_tpu.serving.engine import GenerationEngine

    engine = GenerationEngine(
        replay._tiny_lm(24), BucketLattice(batch_sizes=(1,), seq_lens=(8,)),
        slots=2, max_new_tokens=8, page_size=4, faults="r0:kill@decode3")
    engine.warmup()
    # short's chunk, step 1 (short); long's chunk, step 2 (both); [step 3]
    short = engine.submit_generate(np.arange(5, dtype=np.int32), 3)
    long = engine.submit_generate(np.arange(8, dtype=np.int32), 6)
    engine.start()
    assert long.wait(DEADLINE_S) and short.wait(DEADLINE_S)
    worker = engine.fleet_workers()[0]
    assert worker.lifecycle == "dead" and worker._flight is None
    assert "ReplicaKilled" in long.error and len(long.emitted) == 2
    assert short.error is None and len(short.emitted) == 3
    assert (engine.failed, engine.served) == (1, 1)
    assert not worker.slots.busy()
    assert worker.pool.describe()["pages_in_use"] == 0


# --------------------------------------------- scale up / drain down

def test_add_replica_serves_and_keeps_retrace_accounting(tmp_path):
    tpath = str(tmp_path / "telemetry.jsonl")
    rec = Recorder(tpath)
    engine = InferenceEngine(_mlp(), BucketLattice(batch_sizes=(1, 2)),
                             max_wait_ms=0.5, recorder=rec)
    engine.warmup(np.zeros(8, np.float32))
    engine.start()
    assert engine.fleet_snapshot()["n_serving"] == 1
    engine.add_replica()
    assert engine.fleet_snapshot()["n_serving"] == 2
    x = np.ones(8, np.float32)
    for _ in range(6):
        engine.predict(x, timeout=DEADLINE_S)
    engine.drain(DEADLINE_S)
    rec.close()
    # the new replica's compiles are warmup-flagged: the zero-retrace
    # accounting survives scale-up
    compiles = [e for e in _events(tpath, "span")
                if e.get("name") == "compile"]
    assert len(compiles) == 4 and all(e.get("warmup") for e in compiles)
    assert all(e["ok"] for e in _events(tpath, "request"))


def test_retire_replica_drains_queued_work_and_keeps_last():
    engine = InferenceEngine(_mlp(), BucketLattice(batch_sizes=(1, 2)),
                             max_wait_ms=0.5,
                             recorder=Recorder(path=None))
    engine.warmup(np.zeros(8, np.float32))
    engine.start()
    second = engine.add_replica()
    # park a batch directly on the replica being retired: scale-down
    # with queued work must finish it, not drop it
    from deeplearning4j_tpu.serving.batcher import assemble

    req = PendingRequest(features=np.ones(8, np.float32),
                         t_enqueue=engine._clock())
    batch = assemble([req], engine.lattice)
    batch.t_cut = engine._clock()
    req.t_assembled = batch.t_cut
    second.queue.put(batch)
    retired = engine.retire_replica()
    assert retired is second
    assert req.wait(DEADLINE_S), "queued work dropped on scale-down"
    assert req.error is None
    # the drained replica left dispatch; the survivor still serves
    assert engine.fleet_snapshot()["n_serving"] == 1
    out = engine.predict(np.ones(8, np.float32), timeout=DEADLINE_S)
    assert np.asarray(out).shape == (4,)
    # the LAST live replica is never retired
    assert engine.retire_replica() is None
    engine.drain(DEADLINE_S)


def test_supervisor_autoscales_live_engine_up_and_down():
    """The supervisor's live loop against a real engine, with manual
    polls and fake clocks: deep queue grows the fleet, sustained idle
    drains it back to the floor."""
    rec = Recorder(path=None)
    engine = InferenceEngine(_mlp(), BucketLattice(batch_sizes=(1, 2)),
                             max_wait_ms=0.5, recorder=rec)
    engine.warmup(np.zeros(8, np.float32))
    supervisor = FleetSupervisor(
        engine, policy=AutoscalePolicy(min_replicas=1, max_replicas=2,
                                       up_queue_depth=4,
                                       down_queue_depth=0,
                                       cooldown_up_s=0.0,
                                       cooldown_down_s=1.0),
        recorder=rec)
    # park a deep queue BEFORE the dispatcher starts (requeue admits
    # without the submit() drain race), sample it, then serve
    reqs = [PendingRequest(features=np.ones(8, np.float32),
                           t_enqueue=engine._clock()) for _ in range(8)]
    engine.batcher.requeue(reqs)
    actions = supervisor.poll(now=100.0)
    assert actions["scale"] == 1
    assert engine.fleet_snapshot()["n_replicas"] == 2
    # start serving: the grown fleet flushes the queue
    engine.start()
    for r in reqs:
        assert r.wait(DEADLINE_S), "parked request missed its deadline"
    assert engine.batcher.depth == 0
    actions = supervisor.poll(now=200.0)
    assert actions["scale"] == -1
    assert engine.fleet_snapshot()["n_serving"] == 1
    auto = [e for e in rec.events if e.get("event") == "autoscale"]
    assert len(auto) == 2
    assert auto[0]["action"] == 1 and auto[1]["action"] == -1
    assert all(e["max_replicas"] == 2 for e in auto)
    engine.drain(DEADLINE_S)


# --------------------------------------------------- server fleet state

def test_healthz_reports_fleet_state_and_drain_retry_after(tmp_path):
    engine = InferenceEngine(_mlp(), BucketLattice(batch_sizes=(1,)),
                             max_wait_ms=1.0, recorder=Recorder(path=None))
    engine.warmup(np.zeros(8, np.float32))
    ckdir = _save_publish_checkpoint(engine.net, 11, tmp_path)
    server = ServingServer(engine, port=0).start()
    try:
        fleet.hot_swap(engine, ckdir)
        with urllib.request.urlopen(f"{server.url}/healthz",
                                    timeout=10) as r:
            health = json.loads(r.read())
        assert health["status"] == "serving"
        assert health["weights"]["generation"] == 1
        assert health["weights"]["step"] == 11
        assert health["weights"]["last_swap_ts"] is not None
        rows = health["fleet"]
        assert rows[0]["state"] == "serving" and rows[0]["alive"]
        assert "last_beat_age_s" in rows[0]
        # drain: /predict 503s WITH a Retry-After header
        urllib.request.urlopen(
            urllib.request.Request(f"{server.url}/drain", data=b""),
            timeout=10).read()
        req = urllib.request.Request(
            f"{server.url}/predict",
            data=json.dumps({"features": [0.0] * 8}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(req, timeout=10)
        assert e.value.code == 503
        assert e.value.headers["Retry-After"] == "5"
    finally:
        server.stop()


# ------------------------------------------------ bench + artifact gates

def test_fleet_replay_artifact_and_benchdiff_gate(tmp_path):
    """A small end-to-end fleet replay: both arms complete, the chaos
    kill's failures stay bounded, zero retraces, the swap and respawn
    are on the record — and the artifact self-diffs clean while a
    doctored regression (failed_requests growing) trips benchdiff."""
    tpath = str(tmp_path / "t.jsonl")
    apath = str(tmp_path / "SERVE_fleet.json")
    out = replay.run_fleet_replay(
        seed=3, n_requests=24, burst=4, mean_gap_s=0.01,
        autoscale_max=2, chaos="r0:kill@batch3", hot_swap_after=6,
        telemetry_path=tpath, artifact_path=apath)
    fixed, auto = out["fixed"], out["autoscale"]
    assert fixed["n_failed"] == 0 and fixed["n_ok"] == 24
    assert auto["n_ok"] >= 20
    assert 1 <= auto["n_failed"] <= 4, "chaos failures not bounded"
    assert auto["n_respawns"] >= 1 and auto["respawn_ms"] >= 0
    assert auto["n_swaps"] == 1 and auto["swap_ms"] > 0
    # the flip's deterministic visibility proof lives in
    # test_hot_swap_mid_traffic...; here the replay just must not
    # invent generations or lose the starting one
    assert auto["weight_generations"][0] == 0
    assert set(auto["weight_generations"]) <= {0, 1}
    assert auto["recompiles_after_warmup"] == 0
    assert fixed["recompiles_after_warmup"] == 0
    assert 0 < auto["autoscale_occupancy"] <= 1.0

    bd = _benchdiff()
    assert bd.main([apath, apath]) == 0
    # doctor failed_requests upward: lower-is-better must trip
    doctored = str(tmp_path / "doctored.json")
    with open(apath) as fh, open(doctored, "w") as out_fh:
        for line in fh:
            row = json.loads(line)
            if row.get("metric") == "fleet_failed_requests":
                row["value"] = row["value"] + 50
            if row.get("metric") == "summary" and \
                    "fleet_failed_requests" in row:
                row["fleet_failed_requests"] += 50
            out_fh.write(json.dumps(row) + "\n")
    assert bd.main([apath, doctored]) == 1


def test_committed_serve_r03_artifact_parses_and_gates():
    """The committed SERVE_r03.json: every fleet row present with the
    right direction flags, zero retraces on the record, and a self-diff
    through benchdiff is clean."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    apath = os.path.join(root, "SERVE_r03.json")
    assert os.path.exists(apath), "SERVE_r03.json missing"
    from deeplearning4j_tpu.telemetry import artifact as art

    lines = art.load(apath)
    for metric in ("fleet_fixed_qps", "fleet_autoscale_qps",
                   "fleet_autoscale_occupancy", "fleet_swap_ms",
                   "fleet_respawn_ms", "fleet_failed_requests",
                   "fleet_recompiles_after_warmup"):
        assert metric in lines, f"{metric} missing from SERVE_r03"
    assert lines["fleet_recompiles_after_warmup"]["value"] == 0
    assert lines["fleet_swap_ms"]["lower_is_better"]
    assert lines["fleet_failed_requests"]["lower_is_better"]
    assert lines["fleet_fixed_qps"]["value"] > 0
    assert lines["fleet_autoscale_qps"]["value"] > 0