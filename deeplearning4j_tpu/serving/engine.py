"""Replica dispatch: jitted forward workers consuming bucket batches.

One `_Replica` = one worker thread owning its own jit wrapper of the
net's pure inference function (`net.inference_fn()` — nn/multilayer.py
and nn/graph.py). The dispatcher pulls assembled batches from the
Batcher and deals them round-robin over the replicas, so host-side
padding/assembly of the next batch overlaps the current forward (XLA
releases the GIL during execution). On the distributed runtime each
process runs its own engine behind its own port (the CLI `serve
--multiprocess` plan); the per-process telemetry suffix from
distributed/bootstrap keeps the logs attributable.

Zero-retrace accounting: every bucket shape is compiled ONCE during
`warmup` under a telemetry span named "compile"; the traced function
also bumps a host-side trace counter at trace time, so tier-1 can
assert the compile-span count AND the trace count stay frozen across a
replayed mixed-length trace (the lattice contract in
serving/buckets.py).

Failure containment (ARCHITECTURE §Serving failure modes): a worker
dying mid-batch fails THAT batch's requests (each future carries the
error, the HTTP layer returns 500, a telemetry `error` event keeps the
full traceback) and the replica keeps serving the next batch — one
poisoned input cannot take the replica down with it.

Fleet operations (ISSUE 13, serving/fleet.py): every replica reads its
params through the engine's double-buffered `WeightStore` exactly ONCE
per batch — the live hot-swap flips that reference between batches, so
each request event records the single coherent `weight_gen` it served
against. Replicas carry a lifecycle (warming → serving → draining /
dead → retired), a heartbeat, and an optional chaos injector
(replica-scoped `distributed/faults.py` specs); the `FleetSupervisor`
reaps dead/hung replicas (queued batches drain back to the batcher),
respawns them through the SAME jit wrappers (zero new traces), and the
autoscale loop grows/drains the replica set through `add_replica` /
`retire_replica` (a retiring replica finishes its queued work first).

jax imports stay inside methods: the module is importable under the
graftlint AST stubs and costs tools nothing.
"""

from __future__ import annotations

import contextlib
import queue
import sys
import threading
import time
import traceback
from collections import deque

import numpy as np

from deeplearning4j_tpu.serving.batcher import (Batch, Batcher, DecodeSlots,
                                                GenRequest)
from deeplearning4j_tpu.serving.buckets import Bucket, BucketLattice
from deeplearning4j_tpu.serving.fleet import (ReplicaFaultInjector,
                                              ReplicaKilled, WeightStore,
                                              restore_for_serving)
from deeplearning4j_tpu.serving.kvcache import CachePlan
from deeplearning4j_tpu.serving.speculative import (NgramProposer,
                                                    accept_greedy)
from deeplearning4j_tpu.telemetry.costbook import CostBook, peak_flops
from deeplearning4j_tpu.telemetry.memstat import (MemoryLedger,
                                                  MemorySampler, tree_bytes)


def _peak_fields(peak) -> dict:
    """The device's published peak as an event/stats field — absent
    off-TPU (costbook.peak_flops gives None there), never invented."""
    return {} if peak is None else {"peak_flops": peak}


def _weights_facts(stored, served) -> dict:
    """What a generation engine's store holds, against the net's own
    tree `stored`: the floating dtype(s) of the served leaves, their
    bytes on the device, and how many leaves nn/decode.serving_params
    cast (0 where the net computes in the dtype it stores)."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree.leaves(served)
    return {"weights_dtype": "+".join(sorted(
                {x.dtype.name for x in leaves
                 if jnp.issubdtype(x.dtype, jnp.floating)})),
            "weights_bytes": tree_bytes(served),
            "weights_cast_leaves": sum(
                a.dtype != b.dtype
                for a, b in zip(jax.tree.leaves(stored), leaves))}


def _engine_recorder(recorder):
    """The recorder an engine emits through: the one it was given, else
    the process default — and where that is the shared NullRecorder, a
    NullRecorder of the engine's own, so that the sinks on it (its
    server's /metrics registry) hear this engine's `request` events and
    no other engine's."""
    if recorder is not None:
        return recorder
    from deeplearning4j_tpu.telemetry import NullRecorder, get_default

    recorder = get_default()
    return recorder if recorder.live else NullRecorder()


class QueueFullError(RuntimeError):
    """Generation admission refused: the page pool and the pending queue
    are both full — the front door's graceful 503, never a crash."""


class _Replica:
    """One forward worker: its own jit wrapper (own compile cache), its
    own batch queue, its own trace counter. Params come from the
    engine's double-buffered `WeightStore` — read ONCE per batch, so a
    hot-swap flip lands between batches, never inside one. Lifecycle
    (`warming`/`serving`/`draining`/`dead`/`retired`), heartbeat, and
    the chaos injector are what `serving/fleet.FleetSupervisor`
    supervises."""

    def __init__(self, index: int, net, recorder, weights: WeightStore,
                 faults: ReplicaFaultInjector | None = None):
        import jax

        self.index = index
        self.net = net
        self.recorder = recorder
        self.weights = weights
        self.faults = faults
        self.queue: queue.Queue = queue.Queue()
        # guards the stats counters below: they are `+=`-mutated on the
        # worker thread and read by describe()/stats() on the control
        # plane — bare read-modify-write loses updates (G025)
        self._mu = threading.Lock()
        self.trace_count = 0
        self.served = 0
        self.failed = 0
        self.batches_run = 0
        self.alive = True
        self.lifecycle = "warming"
        self.last_beat = 0.0
        self.current_batch: Batch | None = None
        self._seen_shapes: set = set()
        fwd = net.inference_fn()

        def counted(params, state, x, mask=None):
            # runs at TRACE time only: the retrace tell the zero-retrace
            # gate asserts on (one bump per compiled bucket shape)
            with self._mu:
                self.trace_count += 1
            return fwd(params, state, x, mask)

        self._jit = jax.jit(counted)
        self._thread: threading.Thread | None = None

    # ----------------------------------------------------------- forward
    def _shape_key(self, feats: np.ndarray, mask) -> tuple:
        return (feats.shape, str(feats.dtype), mask is not None)

    def fail_batch(self, batch: Batch, exc_or_msg, *, clock,
                   weight_gen: int | None = None) -> None:
        """Fail every request of one batch loudly (worker death, reaped
        hang, drain with no live replica) — each future carries the
        error, telemetry keeps the record."""
        with self._mu:
            self.failed += batch.n_real
        if isinstance(exc_or_msg, BaseException):
            self.recorder.error(f"replica:{self.index}", exc=exc_or_msg)
            err = "".join(traceback.format_exception_only(
                type(exc_or_msg), exc_or_msg)).strip()
        else:
            err = str(exc_or_msg)
            self.recorder.error(f"replica:{self.index}", error=err)
        t_done = clock()
        for r in batch.requests:
            r.error = err
            r.t_done = t_done
            self._request_event(r, batch, None, ok=False, error=err,
                               weight_gen=weight_gen)
            r.done.set()

    def run_batch(self, batch: Batch, *, clock, sequence: bool) -> None:
        # the cross-thread correlation handoff: the batcher rooted this
        # batch's trace (queue -> batch_assemble on the dispatcher
        # thread); everything this replica thread emits for it —
        # forward, nested compile, the per-request events — joins that
        # tree (telemetry/recorder.py; warmup batches carry no trace
        # and the context is a no-op)
        with self.recorder.trace(batch.trace_id,
                                 parent_id=batch.parent_span):
            self._run_batch(batch, clock=clock, sequence=sequence)

    def _run_batch(self, batch: Batch, *, clock, sequence: bool) -> None:
        rec = self.recorder
        self.current_batch = batch
        self.last_beat = clock()
        with self._mu:
            self.batches_run += 1
        # the ONE read of the published weight set this batch serves
        # against — the hot-swap flip is atomic relative to it
        ws = self.weights.current
        key = self._shape_key(batch.features, batch.mask)
        first = key not in self._seen_shapes
        t0 = time.perf_counter()
        try:
            with rec.span("forward", bucket=list(batch.bucket.key()),
                          replica=self.index, n_real=batch.n_real):
                if self.faults is not None:
                    self.faults.check(self.index, "batch",
                                      self.batches_run)
                if first:
                    # the first execution of a bucket shape includes its
                    # compile — span-named so the warmed compile count is
                    # reconstructable from telemetry alone
                    with rec.span("compile",
                                  bucket=list(batch.bucket.key()),
                                  replica=self.index):
                        y = self._jit(ws.params, ws.state,
                                      batch.features, batch.mask)
                        rows = np.asarray(y)  # batch-boundary fetch
                    self._seen_shapes.add(key)
                else:
                    y = self._jit(ws.params, ws.state,
                                  batch.features, batch.mask)
                    rows = np.asarray(y)  # batch-boundary fetch
        except ReplicaKilled as exc:
            # injected replica death: the in-flight batch fails (the
            # BOUNDED failure set), the thread dies; the supervisor
            # requeues this replica's queue and respawns it. Death is
            # marked BEFORE the futures complete so a waiter that saw
            # the failure also sees the dead replica.
            self.current_batch = None
            self.alive = False
            self.lifecycle = "dead"
            self.fail_batch(batch, exc, clock=clock,
                            weight_gen=ws.generation)
            raise
        except Exception as exc:  # worker dying mid-batch: contain it
            self.fail_batch(batch, exc, clock=clock,
                            weight_gen=ws.generation)
            self.current_batch = None
            return
        forward_s = time.perf_counter() - t0
        t_done = clock()
        for i, r in enumerate(batch.requests):
            out = rows[i]
            if sequence:
                out = out[:r.length]  # drop time padding
            r.result = out
            r.t_done = t_done
            with self._mu:
                self.served += 1
            self._request_event(r, batch, forward_s, ok=True,
                               weight_gen=ws.generation)
            r.done.set()
        self.current_batch = None
        self.last_beat = clock()

    def _request_event(self, r, batch: Batch, forward_s, *, ok,
                       error: str | None = None,
                       weight_gen: int | None = None) -> None:
        """The per-request telemetry record — the ONLY source the
        traffic-replay bench reads latency from (serving/replay.py
        reconstructs p50/p99/QPS from these events alone). `weight_gen`
        names the published weight generation the batch served against
        — the hot-swap flip's visibility in the request stream."""
        fields = dict(
            ok=ok, bucket=list(batch.bucket.key()),
            replica=self.index, n_real=batch.n_real,
            queue_s=round(r.t_assembled - r.t_enqueue, 6),
            batch_assemble_s=round(batch.assemble_seconds, 6),
            total_s=round(r.t_done - r.t_enqueue, 6))
        if weight_gen is None:
            weight_gen = self.weights.generation
        fields["weight_gen"] = weight_gen
        if forward_s is not None:
            fields["forward_s"] = round(forward_s, 6)
        if batch.bucket.seq is not None:
            fields["seq_len"] = r.length
            fields["padded_seq"] = batch.bucket.seq
        if error:
            fields["error"] = error
        self.recorder.request(r.request_id, **fields)

    # ---------------------------------------------------------- lifecycle
    def start(self, clock, sequence: bool) -> None:
        self.last_beat = clock()

        def loop():
            while True:
                batch = self.queue.get()
                if batch is None:
                    if self.lifecycle != "dead":
                        self.lifecycle = "retired"
                    return
                try:
                    self.run_batch(batch, clock=clock, sequence=sequence)
                except ReplicaKilled:
                    return  # dead: the supervisor requeues + respawns

        self.lifecycle = "serving"
        self._thread = threading.Thread(target=loop, daemon=True,
                                        name=f"serve-replica-{self.index}")
        self._thread.start()

    def join(self, timeout: float | None = None) -> None:
        if self._thread is not None:
            self._thread.join(timeout)

    def describe(self, now: float | None = None) -> dict:
        """One /healthz row: lifecycle, counters, heartbeat age."""
        with self._mu:
            out = {"index": self.index, "state": self.lifecycle,
                   "alive": self.alive, "served": self.served,
                   "failed": self.failed,
                   "batches_run": self.batches_run}
        if now is not None:
            out["last_beat_age_s"] = round(max(0.0, now - self.last_beat),
                                           3)
        return out


class InferenceEngine:
    """The serving core: Batcher in front, round-robin replicas behind.

    `net` is shared by every replica (params are immutable device
    arrays; each replica jits its own wrapper). `checkpoint` resumes the
    net from an Orbax host-checkpoint directory before any compile —
    the PR 6 portable-restore seed: a checkpoint saved by a training
    fleet restores into this single serving process."""

    def __init__(self, net, lattice: BucketLattice | None = None, *,
                 replicas: int = 1, max_wait_ms: float = 5.0,
                 sequence: bool = False, checkpoint: str | None = None,
                 faults=None, recorder=None):
        self.recorder = recorder = _engine_recorder(recorder)
        self.sequence = sequence
        if net.params is None:
            net.init()
        self.restored_step = 0
        if checkpoint is not None:
            # any-mesh checkpoint restore through the blessed fleet
            # path: the checkpoint may have been written by a 2x4
            # training fleet; the portable resharding engine (reshard/)
            # plans its placement onto this serving process's own
            # one-device mesh and orbax reads only the slices it needs
            self.restored_step = restore_for_serving(net, checkpoint)
        self.net = net
        # the double-buffered published weight set every replica reads
        # from — live hot-swap (serving/fleet.hot_swap) flips it
        self.weights = WeightStore(net.params, net.state,
                                   step=self.restored_step)
        # the memory-observability spine: the ledger attributes live
        # bytes (the weight-store read tracks hot-swaps), the sampler
        # emits `memory` events at warmup and on the stats tick, the
        # costbook harvests XLA cost/memory analyses at warmup
        ledger = MemoryLedger()
        ledger.register("params", lambda: self.weights.current.params)
        self.memsampler = MemorySampler(recorder, ledger)
        self.costbook = CostBook(recorder)
        self.peak_flops = None  # set at warmup; stays None off-TPU
        self.lattice = lattice or BucketLattice()
        self.batcher = Batcher(self.lattice, max_wait_ms,
                               sequence=sequence, recorder=recorder)
        self._clock = self.batcher._clock
        self._faults = None
        if faults is not None:
            self._faults = (faults if isinstance(faults,
                                                 ReplicaFaultInjector)
                            else ReplicaFaultInjector(faults, recorder))
        self._rcv = threading.Condition()
        self._next_index = 0
        self._replicas = [self._new_replica()
                          for _ in range(max(1, int(replicas)))]
        self._rr = 0
        self._dispatcher: threading.Thread | None = None
        self._started = False
        self._draining = False
        self._feature_template: np.ndarray | None = None
        recorder.meta(role="serving-engine", replicas=len(self._replicas),
                      sequence=sequence, lattice=self.lattice.describe(),
                      restored_step=self.restored_step)

    def _new_replica(self) -> _Replica:
        r = _Replica(self._next_index, self.net, self.recorder,
                     self.weights, faults=self._faults)
        self._next_index += 1
        return r

    # ------------------------------------------------------------- warmup
    def warmup(self, example_features) -> int:
        """Compile every lattice bucket on every replica once, BEFORE
        traffic. `example_features` is one request-shaped array (its
        trailing dims + dtype define the bucket shapes). Returns the
        number of (replica, bucket) compiles performed; after this the
        compile-span count and trace count are frozen — a mixed-length
        replay must add zero."""
        ex = np.asarray(example_features)
        self._feature_template = ex
        compiles = sum(self._warm_replica(r) for r in self._replicas)
        if compiles:
            # one post-warmup snapshot: every serving run's telemetry
            # carries at least one `memory` event, and the MFU gauge
            # gets its device-peak denominator
            import jax

            self.peak_flops = peak_flops(jax.devices()[0])
            self.memsampler.sample("warmup",
                                   **_peak_fields(self.peak_flops))
        return compiles

    def _warm_replica(self, replica: _Replica) -> int:
        """Compile every lattice bucket this replica has not yet seen
        (warmup, add_replica, and the supervisor's respawn-re-warm all
        route here; a respawn compiles NOTHING — the jit executables
        survive a thread death in-process)."""
        ex = self._feature_template
        if ex is None:
            return 0
        replica.lifecycle = ("warming" if replica.lifecycle != "serving"
                             else replica.lifecycle)
        tail = ex.shape[1:] if self.sequence else ex.shape
        ws = self.weights.current
        compiles = 0
        for bucket in self.lattice.shapes():
            feats, mask = self._zeros_for(bucket, tail, ex.dtype)
            batch = Batch(bucket, feats, mask, [])
            key = replica._shape_key(feats, mask)
            if key in replica._seen_shapes:
                continue
            with self.recorder.span("compile",
                                    bucket=list(bucket.key()),
                                    replica=replica.index,
                                    warmup=True):
                y = replica._jit(ws.params, ws.state,
                                 batch.features, batch.mask)
                np.asarray(y)  # batch-boundary fetch
            replica._seen_shapes.add(key)
            compiles += 1
            # cost-book harvest rides the warmup compile: lower() after
            # the warm call is a jaxpr-cache hit (no re-trace — the
            # frozen trace counters stay frozen), and the analyses come
            # from the AOT executable XLA already built
            self.costbook.record("forward", list(bucket.key()),
                                 replica._jit,
                                 (ws.params, ws.state, batch.features,
                                  batch.mask))
        return compiles

    def _zeros_for(self, bucket: Bucket, tail: tuple, dtype):
        if self.sequence:
            feats = np.zeros((bucket.batch, bucket.seq) + tail, dtype)
            mask = np.ones((bucket.batch, bucket.seq), np.float32)
            return feats, mask
        return np.zeros((bucket.batch,) + tail, dtype), None

    # ------------------------------------------------------------ serving
    def start(self) -> "InferenceEngine":
        if self._started:
            return self
        self._started = True
        for r in self._replicas:
            r.start(self._clock, self.sequence)

        def dispatch():
            while True:
                batch = self.batcher.next_batch()
                if batch is None:
                    break  # draining and empty
                if not self._dispatch_batch(batch):
                    # draining with zero live replicas left
                    self._replicas[0].fail_batch(
                        batch, "no live replica during drain",
                        clock=self._clock)
            with self._rcv:
                targets = list(self._replicas)
            for r in targets:
                r.queue.put(None)

        self._dispatcher = threading.Thread(target=dispatch, daemon=True,
                                            name="serve-dispatch")
        self._dispatcher.start()
        return self

    def _dispatch_batch(self, batch: Batch) -> bool:
        """Round-robin one batch over LIVE replicas only — dead,
        draining, and retired workers never receive new batches. The
        pick AND the queue put happen under the replica lock, so a
        concurrent retire's drain sentinel can never slip between them
        and strand the batch behind it. Blocks (condition-notified by
        respawn/add) while no replica is servable; returns False only
        when the engine is draining and no replica will come back."""
        with self._rcv:
            while True:
                serving = [r for r in self._replicas
                           if r.alive and r.lifecycle == "serving"]
                if serving:
                    replica = serving[self._rr % len(serving)]
                    self._rr += 1
                    replica.queue.put(batch)
                    return True
                if self._draining:
                    return False
                self._rcv.wait(timeout=0.05)

    def submit(self, features, mask=None, request_id=None):
        features = np.asarray(features)
        if self._feature_template is not None:
            # the lattice freezes dtype as much as shape: a JSON round
            # trip arrives float64/int64 and would miss every warmed
            # cache entry (one silent retrace per bucket) — cast to the
            # warmup template's dtype at the door
            features = features.astype(self._feature_template.dtype,
                                       copy=False)
        return self.batcher.submit(features, mask=mask,
                                   request_id=request_id)

    def predict(self, features, mask=None, timeout: float = 30.0):
        """Synchronous convenience: submit + wait. Raises on a failed
        batch (the worker-death path) or timeout."""
        req = self.submit(features, mask=mask)
        if not req.wait(timeout):
            raise TimeoutError(f"request {req.request_id} timed out "
                               f"after {timeout}s")
        if req.error is not None:
            raise RuntimeError(f"request {req.request_id} failed: "
                               f"{req.error}")
        return req.result

    # ---------------------------------------------------- fleet lifecycle
    # The FleetSupervisor's contract surface (serving/fleet.py): reap a
    # dead/hung worker, respawn it, grow/drain the replica set.

    def fleet_workers(self) -> list:
        with self._rcv:
            return list(self._replicas)

    def fleet_snapshot(self) -> dict:
        """The autoscale loop's engine-side signals."""
        with self._rcv:
            n_serving = sum(1 for r in self._replicas
                            if r.alive and r.lifecycle == "serving")
            n_replicas = sum(1 for r in self._replicas
                             if r.alive and r.lifecycle
                             in ("warming", "serving"))
        return {"queue_depth": self.batcher.depth,
                "n_serving": n_serving, "n_replicas": n_replicas}

    def fleet_reap(self, replica: _Replica, reason: str = "died") -> int:
        """Take a dead/hung replica out of dispatch: fail its in-flight
        batch (the hang case — a wedged thread can never complete it;
        the kill path already failed its own), then drain its QUEUED
        batches back to the batcher FIFO head, where live replicas pick
        them up. Returns the requeued request count."""
        with self._rcv:
            replica.alive = False
            replica.lifecycle = "dead"
        inflight = replica.current_batch
        if inflight is not None:
            replica.current_batch = None
            replica.fail_batch(inflight, f"replica {replica.index} "
                                         f"reaped ({reason})",
                               clock=self._clock)
        requeued = []
        while True:
            try:
                b = replica.queue.get_nowait()
            except queue.Empty:
                break
            if b is not None:
                requeued.extend(b.requests)
        if requeued:
            self.batcher.requeue(requeued)
        return len(requeued)

    def fleet_respawn(self, replica: _Replica) -> _Replica:
        """Bring a reaped replica back: fresh queue + thread over the
        SAME jit wrappers (compiled executables survive a thread death
        in-process), warmup re-run before re-admission — it compiles
        nothing, so the trace counter stays frozen — then re-admit to
        dispatch."""
        replica.queue = queue.Queue()
        replica.batches_run = 0
        replica.current_batch = None
        replica.alive = True
        replica.lifecycle = "warming"
        self._warm_replica(replica)
        replica.start(self._clock, self.sequence)
        with self._rcv:
            self._rcv.notify_all()
        return replica

    def add_replica(self) -> _Replica:
        """Scale UP one replica: build, warm every lattice bucket
        (warmup-flagged compiles — the zero-retrace accounting is
        unchanged), start, admit to dispatch."""
        with self._rcv:
            replica = self._new_replica()
            self._replicas.append(replica)
        self._warm_replica(replica)
        if self._started:
            replica.start(self._clock, self.sequence)
        with self._rcv:
            self._rcv.notify_all()
        return replica

    def retire_replica(self) -> _Replica | None:
        """Scale DOWN one replica, gracefully: the newest serving
        replica stops receiving batches (lifecycle `draining`),
        finishes everything already in its queue, and its thread exits
        — queued work is never dropped. The last live replica is never
        retired."""
        with self._rcv:
            serving = [r for r in self._replicas
                       if r.alive and r.lifecycle == "serving"]
            if len(serving) <= 1:
                return None
            replica = serving[-1]
            replica.lifecycle = "draining"
            # the sentinel lands under the same lock the dispatcher
            # picks+puts under: no batch can follow it into the queue
            replica.queue.put(None)
        return replica

    # -------------------------------------------------------------- drain
    def drain(self, timeout: float = 30.0) -> None:
        """Graceful shutdown: refuse new requests, flush every pending
        batch through the replicas, join the threads. Every admitted
        request completes (or fails loudly) before this returns."""
        self._draining = True
        with self._rcv:
            self._rcv.notify_all()
        self.batcher.close()
        if self._dispatcher is not None:
            self._dispatcher.join(timeout)
        for r in self.fleet_workers():
            if r.lifecycle == "dead":
                continue  # a wedged thread never joins; it is a daemon
            r.join(timeout)
        self.recorder.event("span", name="drain", ok=True, seconds=0.0,
                            served=self.served, failed=self.failed)

    # -------------------------------------------------------------- stats
    @property
    def trace_count(self) -> int:
        return sum(r.trace_count for r in self._replicas)

    @property
    def served(self) -> int:
        return sum(r.served for r in self._replicas)

    @property
    def failed(self) -> int:
        return sum(r.failed for r in self._replicas)

    def stats(self) -> dict:
        now = self._clock()
        with self._rcv:
            fleet = [r.describe(now) for r in self._replicas]
        # the stats tick is a blessed batch boundary: rate-limited, so
        # a tight scrape loop cannot turn /stats into a live-array walk
        self.memsampler.maybe_sample("stats_tick")
        return {
            "replicas": len(fleet),
            "served": self.served,
            "failed": self.failed,
            "queue_depth": self.batcher.depth,
            "trace_count": self.trace_count,
            "restored_step": self.restored_step,
            "lattice": self.lattice.describe(),
            "sequence": self.sequence,
            "fleet": fleet,
            "weights": self.weights.describe(),
            "memory": self.memsampler.last,
            **_peak_fields(self.peak_flops),
        }


# --------------------------------------------------------------- generation

class _Flight:
    """A program the engine thread has dispatched and not yet retired:
    its sequence number, its fetched array still on the device (`tok`),
    the consumed cache tree it was handed (array handles only, dropped
    with `tok` when it is retired) and the (index, slot) rows its tokens
    belong to: the active rows of a decode step, the one row of a
    prompt's final chunk, none for a chunk that is not the last."""

    __slots__ = ("program", "tok", "handed", "rows")

    def __init__(self, program: int, tok, handed, rows: list):
        self.program, self.tok = program, tok
        self.handed, self.rows = handed, rows


class _GenWorker:
    """One generation replica: its own KV-cache allocation, page pool,
    decode-slot state machine, and jit wrappers (own compile cache, own
    trace counter) for the prefill and decode steps.

    The loop interleaves chunked prefills into the running decode batch:
    each iteration admits what the pool allows, runs at most ONE prompt
    chunk (so a long prefill never starves decoding slots), then one
    decode step over all slots. The decode step's shape is FIXED —
    [n_slots] tokens and positions against the [n_slots, capacity]
    cache — so it compiles exactly once; inactive rows decode a stale
    token whose K/V write is routed to the scratch position
    (capacity - 1), which any real tenant overwrites before it can ever
    be attended (a token's own K/V lands at its position in the same
    step that reads it). The step is TOLD which rows are occupied
    (`_live`): an idle row attends no key, so the walk over the cache's
    key blocks (ops/decode_attention.py) ends at the last block an
    occupied row can see, not at the scratch position's; a counting
    layer computes nothing for it.

    THE LOOP RUNS ONE PROGRAM AHEAD. A program (a prompt chunk or a
    decode step) is RETIRED (its tokens fetched and emitted, its slots
    completed, its device outputs released) only after the NEXT program
    has been dispatched; at most one un-retired program (`_flight`)
    stands behind the one just dispatched. Nothing a step is handed
    waits for the host: completion is by count alone (no stop token), so
    positions and the live set come from the host's own
    count, which advances at DISPATCH (`_Slot.start`, `.pos`, `.sent`),
    and the slots' last tokens never leave the device: every plain
    step's fetched array is the [n_slots] token vector (a chunk writes
    its row into the vector it was handed, a decode step every row) and
    is the next step's argument as it lies there (`_tokens`; `warmup()`
    hands it on the same way). A row's entry means something from its
    prompt's final chunk until its budget is spent, exactly the steps
    that are told the row is live. `last_token`, `req.emit`,
    `tokens_out`, `_maybe_complete` and the release of the arrays happen
    at retirement, from the retired program's OWN array (`_Flight.tok`;
    its copy home starts at dispatch, `copy_to_host_async`). A slot
    whose budget step n spends is not live in step n+1 and is RELEASED
    when step n is retired, which is before the next `_admit`: "release,
    then admit, then the new tenant's chunk at position 0" holds as it
    did, which is what keeps a state layer sound. The served streams
    are the serial loop's token for token. `drain()` and the loop's exit
    come only after the last program is retired (a pass that dispatches
    nothing retires it alone: `_land`). Every step's span says whether
    it was launched over an un-retired program (`ahead`: in a busy
    server every step but the first after an `idle_wait`), and
    `describe()` / stats() count them (`steps_ahead`).

    SPECULATIVE MODE (speculative_k >= 2): the decode step is replaced
    by a fixed-shape VERIFY step over [n_slots, k] token windows
    (nn/decode.make_verify_fn). Each active slot's window is its true
    last token followed by k-1 host-side n-gram drafts
    (serving/speculative.NgramProposer); the greedy acceptance mask
    (`accept_greedy`) turns the k verify rows into 1..k emitted tokens
    — each one a model argmax given exactly its prefix, so the emitted
    stream is bit-identical to non-speculative greedy. The zero-retrace
    contract is untouched: ONE verify shape compiles at warmup (instead
    of the decode shape — only the step actually used is warmed), the
    DecodeSlots machine is unchanged, and a rejected draft's cache
    pages stay reserved by the up-front admission reservation (released
    on the same completion/failure path as ever; its stale K/V is
    invisible under key_limit until the next window overwrites it).
    This path does NOT run ahead and shares none of that logic: its next
    window is drafted on the host from the tokens just emitted, so each
    verify step, and each chunk before one, is dispatched, fetched and
    emitted in one pass as ever (such a chunk's span reads `ahead` false
    and `fetched` its own number). The two needs conflict (drafts need
    the tokens on the host; the plain step does not), so they are two
    paths chosen by how the worker was built, not a switch on one.

    kv_dtype="int8" swaps every cache entry for the quantized paged
    form ({"k","k_scale","v","v_scale"}) through the same three step
    fns — shapes still lattice/page-grid points, ~4x less HBM/slot.

    A NET WHOSE CACHE ENTRY IS A STATE (a running sum a slot, not a row
    a token: nn/layers/power_retention.py, nn/layers/gated_deltanet.py,
    beside full-attention rows in one net) is served by the same loop
    and the worker names no layer: the layer itself zeroes a row's state
    in the chunk that starts at position 0 (a slot's new tenant: stale
    rows hide behind a key limit, a stale sum would not), adds nothing
    for a bucket's pad, and leaves the rows `_live` calls idle bit for
    bit; its steps hand home `state_resets`, which `_split_fetch` puts
    on the step's span. speculative_k >= 2 is refused for such a net
    when the worker is built (nn/decode.make_verify_fn raises: a
    rejected draft cannot be taken out of a sum).

    A NET WHOSE CACHE ENTRY IS A RING (grouped attention with a window:
    nn/layers/grouped_attention.py keeps the newest `window` rows a
    slot, position p at row p % window) is served by the same loop and
    the worker names no layer either: the layer writes nothing for the
    rows `_live` calls idle (their scratch position capacity - 1 would
    land on a row some tenant needs), lets a chunk attend the ring as it
    found it and write after, and needs no reset for a slot's new
    tenant (a row's position is arithmetic on the step's own, and one
    that comes out negative is masked); its steps hand home
    `attn_rows_seen`, `attn_wrapped` and `attn_write_wraps`.
    speculative_k >= 2 and kv_dtype="int8" are refused for such a net
    when the worker is built, with the layer named. `plan.describe(net)` (the `meta` event,
    /stats) says which kinds of row are rings (`windows`) and what a
    slot is allocated (`bytes_per_slot`); the page pool still counts one
    kind of page, `prompt + max_new` of them a request.

    THE CACHE IS DONATED to every step (`donate_argnums` on the cache
    argument of the three jits): the scatter of nn/decode._cache_write
    (what a layer's `apply_cached` reaches through `CacheStep.write`)
    writes in place into the buffers the step was handed, so a step
    neither copies the cache nor holds it twice. The worker owns the
    buffers' lifetime. It rebinds `self.cache` to the step's output as
    soon as the step is dispatched (the jit call has returned; nothing
    has been waited for), in warmup() too, so `self.cache` names a
    consumed tree only inside that call. Another thread (the memory
    ledger behind /stats) may read the tree's metadata (`nbytes`,
    shapes) and nothing else; reap(), on the supervisor's thread, fails
    slots and never touches the tree. Failure cases (`_fail_step`), with
    a program in flight: a step that failed before it ran (an injected
    fault, a trace-time error) left the cache intact; the program in
    flight, older than the fault, is retired and its tokens emitted,
    then only the failed step's own slots fail (`ReplicaKilled` the
    same, and the thread dies). A step that consumed the cache and then
    failed costs EVERY occupied slot its rows: the program in flight is
    still retired first, then all of them fail once with their pages
    released. A program that fails on the device shows a pass late,
    when its tokens are fetched: the program dispatched on its output
    is poisoned with it, the in-flight record and the token vector are
    dropped, every occupied slot fails once. In both a fresh cache is
    allocated, `cache_losses` counts ONE, and the queue is served on.

    THE LOOP IS NAMED WHOLE (telemetry/recorder.py): each pass is an
    `admit` span, then per program `step_prepare`, the step's own span
    (`prefill_chunk` / `decode_step` / `verify_step`) with its children
    `dispatch`, of the program the span launches, and `fetch`, of the
    program BEFORE it (the span says which: `program`, `fetched`), then
    `emit` of that earlier program; the last program of a busy spell
    is retired by a `fetch` and an `emit` with no step around them; or
    `idle_wait` when there is nothing to run. These six are leaves, and
    all but `dispatch` start where the region before them ended
    (`follows=True`), so the recorder's own emission lies inside them
    and a device idle gap laid over them says what the host was doing.
    Consecutive step spans do not overlap, so the next one's `t0` less
    this one's `t1` is still the host's time between two steps. A plain
    decode pass emits six events; a request costs one `admit` event, a
    `page_pool` event at each end and its `request` event on top —
    nothing per token. With telemetry off every span is one shared
    no-op object."""

    def __init__(self, index: int, net, lattice: BucketLattice,
                 plan: CachePlan, prefill_chunk: int, max_queue: int,
                 recorder, weights: WeightStore | None = None,
                 faults: ReplicaFaultInjector | None = None,
                 speculative_k: int = 0, costbook: CostBook | None = None):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn.decode import serving_params

        self.index = index
        self.net = net
        self.lattice = lattice
        self.plan = plan
        self.prefill_chunk = prefill_chunk
        self.max_queue = max_queue
        self.recorder = recorder
        self.costbook = costbook or CostBook(recorder)
        # a worker built alone serves as the engine's do: from the net's
        # parameters in its COMPUTE dtype (GenerationEngine's docstring)
        self.weights = weights or WeightStore(serving_params(net),
                                              net.state)
        self.weights_facts = _weights_facts(net.params,
                                            self.weights.current.params)
        self.faults = faults
        self.pool = plan.make_pool()
        self.slots = DecodeSlots(plan.n_slots)
        self.kv_dtype = plan.kv_dtype
        self.speculative_k = int(speculative_k)
        self.cache = self._alloc_cache()
        # guards the stats counters below (worker-thread `+=` vs
        # describe()/stats() reads on the control plane — G025); never
        # held across a jit call or a queue wait, so it orders freely
        # against `_cv`
        self._mu = threading.Lock()
        self.trace_count = 0
        self.served = 0
        self.failed = 0
        self.cache_losses = 0  # consumed caches rebuilt (_fail_step)
        self.tokens_out = 0
        self.decode_steps_run = 0
        self.steps_ahead = 0  # programs dispatched over an un-retired one
        self.verify_steps_run = 0
        self.slot_steps = 0  # (active slot, verify step) pairs
        self.accepted_tokens = 0
        self.drafted_tokens = 0
        self.draft_overhead_s = 0.0
        self.proposer = NgramProposer()
        self.alive = True
        self.lifecycle = "warming"
        self.last_beat = 0.0
        self.current_batch = None  # the active row set mid-step
        self._seen_shapes: set = set()
        self.pending: deque[GenRequest] = deque()
        self._cv = threading.Condition()
        self._closed = False
        self._thread: threading.Thread | None = None

        prefill_raw = net.prefill_fn(plan.kv_dtype, plan.page_size)
        step_raw = net.incremental_decode_fn(plan.kv_dtype,
                                             plan.page_size)

        # a net with counting layers (the dropless expert layer) makes
        # its steps return a third value, the counters of `.counters`:
        # they ride home behind the tokens, in the one array the host
        # fetches anyway (`_split_fetch` takes them off again)
        self.step_counters = tuple(step_raw.counters)
        B = plan.n_slots

        def fetched(tok, out):
            if self.step_counters:
                tok = jnp.concatenate([tok.reshape(-1), out[2]])
            return tok, out[1]

        # the next token's choice is the head's work
        def argmax(out):
            with jax.named_scope("head"):
                return jnp.argmax(out[0], axis=-1).astype(jnp.int32)

        # THE SLOTS' LAST TOKENS STAY ON THE DEVICE (`self._tokens`): a
        # plain step's fetched array is a [n_slots] vector (counters
        # behind it), every prefill chunk and decode step takes the one
        # of the program before it (`last`) and hands on its own. A
        # chunk writes its row, a decode step every row; a row's entry
        # means something from its prompt's final chunk until its budget
        # is spent, which is exactly when a decode step is told the row
        # is live and reads it. So no step waits for the host to hand it
        # a token (class docstring, THE LOOP RUNS ONE PROGRAM AHEAD).
        def counted_prefill(params, state, cache, last, padded_tokens,
                            bucket_kmask, rows, start, last_idx):
            with self._mu:  # trace-time bump: the retrace tell
                self.trace_count += 1
            out = prefill_raw(params, state, cache, padded_tokens,
                              bucket_kmask, rows, start, last_idx)
            return fetched(jax.lax.dynamic_update_slice(
                last[:B], argmax(out), (rows[0],)), out)

        # `live`: the occupied rows (`_live`)
        def counted_step(params, state, cache, last, pos, live):
            with self._mu:
                self.trace_count += 1
            out = step_raw(params, state, cache, last[:B], pos, live)
            return fetched(argmax(out), out)

        # argument 2 is the cache: donated, so the steps' scatters write
        # in place into the buffers they were handed (class docstring)
        self._prefill_jit = jax.jit(counted_prefill, donate_argnums=2)
        self._decode_jit = jax.jit(counted_step, donate_argnums=2)
        self._verify_jit = None
        if self.speculative_k >= 2:
            verify_raw = net.verify_decode_fn(plan.kv_dtype,
                                              plan.page_size)

            def counted_verify(params, state, cache, padded_windows,
                               pos, live):
                with self._mu:
                    self.trace_count += 1
                # [B, k] argmax rows: the acceptance mask's input —
                # k verification verdicts for one batch-boundary fetch
                out = verify_raw(params, state, cache, padded_windows,
                                 pos, live)
                return fetched(argmax(out), out)

            self._verify_jit = jax.jit(counted_verify, donate_argnums=2)
        self._tokens = self._alloc_tokens()
        self._flight: _Flight | None = None  # dispatched, not yet retired
        self._programs = 0  # sequence number of the last one dispatched

    def _live(self, active=()) -> np.ndarray:
        """The decode or verify step's last argument: [n_slots] True for
        the occupied rows. An idle row is fed the scratch position; told
        that it is idle, the step lets it attend no key (it would see
        the whole capacity, and the walk over the cache's key blocks
        would never end early) and a counting layer selects no expert
        and counts nothing for it."""
        live = np.zeros(self.plan.n_slots, bool)
        live[list(active)] = True
        return live

    def _split_fetch(self, fetched, shape: tuple, span) -> np.ndarray:
        """The step's tokens, in `shape`, out of the fetched array; the
        counters behind them (a net with counting layers) become fields
        of the step's span: `moe_pairs`, `moe_rows`, `moe_max_load` of
        an expert layer, `state_resets` of a layer that keeps a state,
        `attn_rows_seen`, `attn_wrapped` and `attn_write_wraps` of a
        grouped-attention layer."""
        n = len(self.step_counters)
        if not n:
            return fetched
        span.update(**{k: int(v) for k, v in
                       zip(self.step_counters, fetched[-n:])})
        return fetched[:-n].reshape(shape)

    # ---------------------------------------------------------- planning
    def chunk_buckets(self) -> list:
        """The prefill shapes this worker ever compiles (the lattice
        owns the set — buckets.prefill_buckets)."""
        return self.lattice.prefill_buckets(self.prefill_chunk)

    def _next_chunk_len(self, remaining: int) -> int:
        """Bucket-shaped length of the next prompt chunk: full chunks
        while more than a chunk remains, the bucketed remainder last."""
        if remaining >= self.prefill_chunk:
            return self.prefill_chunk
        return self.lattice.seq_bucket(remaining)

    # ------------------------------------------------------------ warmup
    def warmup(self, clock) -> int:
        """Compile every (prefill-bucket) shape plus the decode step
        once, before traffic. After this the trace counter is frozen —
        a mixed prompt/output-length replay must add zero."""
        compiles = 0
        ws = self.weights.current
        rows = np.zeros(1, np.int32)
        start = np.zeros(1, np.int32)
        for Tb in self.chunk_buckets():
            key = ("prefill", Tb)
            if key in self._seen_shapes:
                continue
            with self.recorder.span("compile", kind="prefill",
                                    bucket=[1, Tb], replica=self.index,
                                    warmup=True):
                # the tokens as the loop hands them on: the device
                # array the program before this one returned
                self._tokens, self.cache = self._prefill_jit(
                    ws.params, ws.state, self.cache, self._tokens,
                    np.zeros((1, Tb), np.int32),
                    np.zeros((1, Tb), np.float32), rows, start,
                    np.asarray([Tb - 1], np.int32))
                np.asarray(self._tokens)  # batch-boundary fetch
            self._seen_shapes.add(key)
            compiles += 1
            # warmup-time cost harvest: lower() is a jaxpr-cache hit
            # (no trace-counter bump), the analyses are XLA's own
            self.costbook.record("prefill", [1, Tb], self._prefill_jit,
                                 (ws.params, ws.state, self.cache,
                                  self._tokens,
                                  np.zeros((1, Tb), np.int32),
                                  np.zeros((1, Tb), np.float32), rows,
                                  start, np.asarray([Tb - 1], np.int32)))
        # only the step this worker actually runs is warmed: the decode
        # shape in plain mode, the [B, k] verify shape in speculative
        # mode — either way ONE step compile, and the trace counter is
        # frozen after it
        if self._verify_jit is not None:
            if "verify" not in self._seen_shapes:
                B, K = self.plan.n_slots, self.speculative_k
                scratch = np.full(B, self.plan.capacity - 1, np.int32)
                with self.recorder.span("compile", kind="verify",
                                        shape=[B, K, self.plan.capacity],
                                        replica=self.index, warmup=True):
                    tok, self.cache = self._verify_jit(
                        ws.params, ws.state, self.cache,
                        np.zeros((B, K), np.int32), scratch, self._live())
                    np.asarray(tok)  # batch-boundary fetch
                self._seen_shapes.add("verify")
                compiles += 1
                self.costbook.record(
                    "verify", [B, K, self.plan.capacity],
                    self._verify_jit,
                    (ws.params, ws.state, self.cache,
                     np.zeros((B, K), np.int32), scratch, self._live()))
        elif "decode" not in self._seen_shapes:
            B = self.plan.n_slots
            scratch = np.full(B, self.plan.capacity - 1, np.int32)
            with self.recorder.span("compile", kind="decode",
                                    shape=[B, self.plan.capacity],
                                    replica=self.index, warmup=True):
                self._tokens, self.cache = self._decode_jit(
                    ws.params, ws.state, self.cache, self._tokens,
                    scratch, self._live())
                np.asarray(self._tokens)  # batch-boundary fetch
            self._seen_shapes.add("decode")
            compiles += 1
            self.costbook.record("decode", [B, self.plan.capacity],
                                 self._decode_jit,
                                 (ws.params, ws.state, self.cache,
                                  self._tokens, scratch, self._live()))
        return compiles

    # --------------------------------------------------------- admission
    def submit(self, req: GenRequest) -> None:
        pages = self.plan.request_pages(
            self.lattice.seq_bucket(req.prompt_len), req.max_new_tokens)
        if pages > self.pool.n_pages:
            raise ValueError(
                f"request needs {pages} cache pages but the replica "
                f"pool holds {self.pool.n_pages} — prompt + "
                "max_new_tokens exceed the cache geometry")
        with self._cv:
            if self._closed:
                raise RuntimeError("engine is draining; request refused")
            if len(self.pending) >= self.max_queue:
                raise QueueFullError(
                    "generation queue full (page pool saturated and "
                    f"{self.max_queue} requests already waiting) — "
                    "retry later")
            self.pending.append(req)
            self._cv.notify_all()

    def _admit(self, clock) -> None:
        """One admission pass, under an `admit` span: bind queued
        requests to free slots while the pool has their pages. The span
        says why the head of the queue stayed (`blocked`: "slots" /
        "pages" / None), each admission leaves an `admit` event that
        ties the request's id to its slot — the join key for the
        `decode_step` spans' `slots` — and the records are emitted once
        `_cv` is released, so a submitter never waits on a sink."""
        rec = self.recorder
        admitted: list = []
        blocked = None
        with rec.span("admit", follows=True, replica=self.index) as sp:
            with self._cv:
                while self.pending:
                    idx = self.slots.free_index()
                    if idx is None:
                        blocked = "slots"
                        break
                    req = self.pending[0]
                    pages = self.plan.request_pages(
                        self.lattice.seq_bucket(req.prompt_len),
                        req.max_new_tokens)
                    if not self.pool.try_reserve(pages):
                        blocked = "pages"  # stays queued, not dropped
                        break
                    self.pending.popleft()
                    req.t_admitted = clock()
                    self.slots.admit(idx, req, pages)
                    if rec.live:
                        admitted.append((req, idx, self.pool.describe()))
                pending = len(self.pending)
            for req, idx, pool in admitted:
                rec.event("admit", id=req.request_id,
                          trace_id=req.request_id, slot=idx,
                          replica=self.index,
                          queue_s=round(req.t_admitted - req.t_enqueue, 6))
                rec.event("page_pool", replica=self.index, **pool)
            sp.update(admitted=len(admitted), pending=pending,
                      blocked=blocked)

    # ----------------------------------------------------------- compute
    def _run_prefill_chunk_bucketed(self, slot_idx: int, clock) -> None:
        """One bucket-shaped prompt chunk for one slot: dispatched, and
        under its span the program before it retired (`_land`'s twin
        inside a step). The jit sees only padded bucket arrays and the
        device's token vector (G017/G019); the only host fetch is the
        one batch-boundary np.asarray of the retired program's tokens.
        `step_prepare` and the chunk's span run under the request's
        trace context, so a generation's prefill tree reconstructs from
        the JSONL alone; the `emit` after them is another program's."""
        rec = self.recorder
        prev = self._flight
        slot = self.slots.slots[slot_idx]
        req = slot.request
        flight = None
        with rec.trace(req.request_id):
            with rec.span("step_prepare", follows=True, replica=self.index,
                          kind="prefill"):
                L = req.prompt_len
                Tc = self._next_chunk_len(L - slot.start)
                n_real = min(Tc, L - slot.start)
                padded_tokens = np.zeros((1, Tc), np.int32)
                padded_tokens[0, :n_real] = req.tokens[slot.start:slot.start
                                                       + n_real]
                bucket_kmask = np.zeros((1, Tc), np.float32)
                bucket_kmask[0, :n_real] = 1.0
                final = slot.start + n_real >= L
                key = ("prefill", Tc)
                first = key not in self._seen_shapes
                ws = self.weights.current
                handed = self.cache
                inputs = (padded_tokens, bucket_kmask,
                          np.asarray([slot_idx], np.int32),
                          np.asarray([slot.start], np.int32),
                          np.asarray([n_real - 1], np.int32))
            compiling = (rec.span("compile", kind="prefill", bucket=[1, Tc],
                                  replica=self.index)
                         if first else contextlib.nullcontext())
            try:
                with rec.span("prefill_chunk", bucket=[1, Tc],
                              start=slot.start, replica=self.index,
                              final=final, n_real=n_real,
                              ahead=prev is not None) as step, \
                        compiling:
                    with rec.span("dispatch"):
                        tok, self.cache = self._prefill_jit(
                            ws.params, ws.state, handed, self._tokens,
                            *inputs)
                    # the prompt's last forward row IS the first
                    # generated token: only a final chunk has one
                    flight = self._took_off(
                        tok, handed, [(slot_idx, slot)] if final else [],
                        step)
                    self._seen_shapes.add(key)
                    slot.start += n_real
                    if final:
                        slot.pos, slot.sent = L, 1
                    if self._verify_jit is not None:
                        # the verify step drafts on the host from the
                        # tokens emitted: nothing runs ahead of it, and
                        # the chunk is retired under its own span
                        prev, self._flight = flight, None
                    toks = self._fetch(prev, step)
            except Exception as exc:
                self._fail_step(handed, [slot_idx], exc, clock,
                                lost=flight is not None)
                return
        self._emit(prev, toks, clock)

    def _took_off(self, tok, handed, rows: list, step: dict) -> "_Flight":
        """The jit call has returned: the program is on the device's
        queue. Its record becomes the one in flight (the caller holds
        the one before, to retire it), its token vector the next
        program's, and the copy home starts now, so that `fetch` finds
        the tokens on the host when the device is done."""
        tok.copy_to_host_async()
        with self._mu:
            self._programs += 1
            if self._flight is not None:
                self.steps_ahead += 1
        step["program"] = self._programs
        self._tokens = tok
        self._flight = _Flight(self._programs, tok, handed, rows)
        return self._flight

    def _fetch(self, flight: "_Flight | None", step: dict | None = None):
        """The one batch-boundary np.asarray of a dispatched program's
        tokens, under a `fetch` span: inside `step`, the span of the
        program dispatched after it, or alone in a pass that dispatches
        nothing. The counters behind the tokens land where they came
        home, and `fetched` says whose they are. None for no program
        (the first dispatch after an `idle_wait` retires nothing)."""
        if flight is None:
            return None
        alone = {"replica": self.index} if step is None else {}
        with self.recorder.span("fetch", follows=True, **alone) as sp:
            toks = np.asarray(flight.tok)  # batch-boundary fetch
            home = sp if step is None else step
            home["fetched"] = flight.program
            return self._split_fetch(toks, (self.plan.n_slots,), home)

    def _emit(self, flight: "_Flight | None", toks, clock) -> None:
        """Retire a fetched program: hand each of its rows its token
        (the stream put, the completion and its `request` event), then
        drop the program's device outputs."""
        self.current_batch = None
        if flight is None:
            return
        with self.recorder.span("emit", follows=True, replica=self.index,
                                tokens=len(flight.rows),
                                program=flight.program) as sp:
            now = clock()
            for i, slot in flight.rows:
                if self.slots.slots[i] is not slot:
                    continue  # reaped from the supervisor's thread
                slot.last_token = int(toks[i])
                slot.request.emit(slot.last_token, now)
                self._maybe_complete(i, clock)
            with self._mu:
                self.tokens_out += len(flight.rows)
            # dropping a program's device outputs is a call into the
            # runtime, and the first place after the stream puts where
            # the engine thread lets go of the interpreter (PERF.md
            # section 7 (d)), so it happens here, inside a named span
            # and with a field of its own, not in a frame's teardown;
            # the consumed tree's 2 x layers array handles go with it
            t_release = time.perf_counter()
            flight.tok = flight.handed = None
            sp["release_s"] = round(time.perf_counter() - t_release, 6)

    def _land(self, clock) -> None:
        """A pass that dispatches nothing: the program in flight is
        retired alone, its `fetch` the child of no step."""
        flight = self._flight
        toks = self._fetch(flight)
        self._flight = None
        self._emit(flight, toks, clock)

    def _decode_batch_step(self, active: list, clock) -> None:
        """One fixed-shape decode step over every slot row; `active`
        names the rows whose outputs are real. Its tokens come from the
        device (`self._tokens`: the vector the program before it left
        there), its positions and its live set from the host's own
        count, so it is dispatched BEFORE that program's tokens are
        fetched: under this step's span the host then makes the one
        np.asarray of the program before (the batch-boundary fetch) and,
        after the span, distributes those tokens to their slots.
        `decode_step.slots` names this step's rows: with the `admit`
        events (id -> slot) a request's decode steps join to its id."""
        rec = self.recorder
        prev = self._flight
        flight = None
        with rec.span("step_prepare", follows=True, replica=self.index,
                      kind="decode"):
            B = self.plan.n_slots
            pos = np.full(B, self.plan.capacity - 1, np.int32)  # scratch
            rows = [(i, self.slots.slots[i]) for i in active]
            for i, slot in rows:
                pos[i] = slot.pos
            ws = self.weights.current
            handed = self.cache
            with self._mu:
                self.decode_steps_run += 1
            self.current_batch = list(active)
        try:
            with rec.span("decode_step", replica=self.index,
                          n_active=len(active), slots=self.current_batch,
                          ahead=prev is not None) as step:
                if self.faults is not None:
                    self.faults.check(self.index, "decode",
                                      self.decode_steps_run)
                with rec.span("dispatch"):
                    tok, self.cache = self._decode_jit(
                        ws.params, ws.state, handed, self._tokens, pos,
                        self._live(active))
                flight = self._took_off(tok, handed, rows, step)
                for _i, slot in rows:
                    slot.pos += 1
                    slot.sent += 1
                toks = self._fetch(prev, step)
        except ReplicaKilled as exc:
            # injected mid-decode death, before the call: the program in
            # flight is retired, then every active slot fails (pages
            # released by _fail_slot) and the thread dies; the supervisor
            # respawns — pending requests stay queued with the worker.
            # Death is marked BEFORE the futures complete so a waiter
            # that saw the failure also sees the dead worker.
            self.current_batch = None
            self.alive = False
            self.lifecycle = "dead"
            self._fail_step(handed, active, exc, clock)
            raise
        except Exception as exc:
            self._fail_step(handed, active, exc, clock,
                            lost=flight is not None)
            self.current_batch = None
            return
        self._emit(prev, toks, clock)

    def _speculative_batch_step(self, active: list, clock) -> None:
        """One fixed-shape VERIFY step over every slot row: each active
        row's window is [last_token, d_1..d_{k-1}] (host-side n-gram
        drafts), inactive rows ride the scratch position like the plain
        decode step. ONE np.asarray fetches the whole [n_slots, k]
        argmax matrix; the greedy acceptance mask then emits 1..k
        tokens per slot — every accepted draft is a decode step that
        never ran. Draft proposal cost is metered host-side
        (`draft_overhead_us`) and the per-step `draft` telemetry event
        is what the replay's accepted_tokens_per_step headline
        reconstructs from. Serial by nature: the next window is drafted
        from the tokens this step emits, so the step is dispatched,
        fetched and emitted here, in one pass, and nothing of the plain
        path's running ahead (`_flight`, `_took_off`, `_land`) is used."""
        rec = self.recorder
        with rec.span("step_prepare", follows=True, replica=self.index,
                      kind="verify"):
            B, K = self.plan.n_slots, self.speculative_k
            padded_windows = np.zeros((B, K), np.int32)
            pos = np.full(B, self.plan.capacity - 1, np.int32)  # scratch
            t_draft = time.perf_counter()
            drafts: dict = {}
            for i in active:
                slot = self.slots.slots[i]
                req = slot.request
                d = self.proposer.propose(
                    list(req.tokens) + list(req.emitted), K - 1)
                drafts[i] = d
                padded_windows[i, 0] = slot.last_token
                padded_windows[i, 1:] = d
                pos[i] = slot.pos
            draft_s = time.perf_counter() - t_draft
            ws = self.weights.current
            handed = self.cache
            with self._mu:
                self.decode_steps_run += 1
                self.verify_steps_run += 1
            self.current_batch = list(active)
        try:
            with rec.span("verify_step", replica=self.index,
                          n_active=len(active), k=K,
                          slots=self.current_batch) as step:
                if self.faults is not None:
                    self.faults.check(self.index, "decode",
                                      self.decode_steps_run)
                with rec.span("dispatch"):
                    tok, self.cache = self._verify_jit(
                        ws.params, ws.state, handed,
                        padded_windows, pos, self._live(active))
                with rec.span("fetch", follows=True):
                    toks = np.asarray(tok)  # [B, k] batch-boundary fetch
                toks = self._split_fetch(toks, (B, K), step)
        except ReplicaKilled as exc:
            # same containment contract as the plain decode step
            self.current_batch = None
            self.alive = False
            self.lifecycle = "dead"
            for i in active:
                self._fail_slot(i, exc, clock)
            raise
        except Exception as exc:
            self._fail_step(handed, active, exc, clock)
            self.current_batch = None
            return
        with rec.span("emit", follows=True, replica=self.index) as sp:
            self.current_batch = None
            now = clock()
            step_emitted = 0
            step_accepted = 0
            for i in active:
                slot = self.slots.slots[i]
                req = slot.request
                budget = req.max_new_tokens - len(req.emitted)
                _n_acc, emitted = accept_greedy(drafts[i], toks[i])
                take = min(len(emitted), budget)
                for t in emitted[:take]:
                    req.emit(int(t), now)
                    with self._mu:
                        self.tokens_out += 1
                slot.pos += take
                slot.sent += take
                slot.last_token = int(emitted[take - 1])
                step_emitted += take
                step_accepted += take - 1  # drafts accepted (bonus aside)
                self._maybe_complete(i, clock)
            with self._mu:
                self.accepted_tokens += step_emitted
                self.drafted_tokens += (K - 1) * len(active)
                self.slot_steps += len(active)
                self.draft_overhead_s += draft_s
            rec.event("draft", replica=self.index, k=K,
                      n_active=len(active), emitted=step_emitted,
                      accepted=step_accepted,
                      drafted=(K - 1) * len(active),
                      overhead_us=round(draft_s * 1e6, 2))
            sp["tokens"] = step_emitted
            t_release = time.perf_counter()
            del tok, handed  # as in the plain decode step
            sp["release_s"] = round(time.perf_counter() - t_release, 6)

    # -------------------------------------------------------- lifecycle
    def _maybe_complete(self, slot_idx: int, clock) -> None:
        slot = self.slots.slots[slot_idx]
        req = slot.request
        if len(req.emitted) < req.max_new_tokens:
            return
        self.pool.release(self.slots.release(slot_idx))
        self._page_pool_event()
        req.finish(clock())
        with self._mu:
            self.served += 1
        self._request_event(req, ok=True)

    def _page_pool_event(self) -> None:
        if self.recorder.live:  # else describe() builds what nobody keeps
            self.recorder.event("page_pool", replica=self.index,
                                **self.pool.describe())

    def _fail_slot(self, slot_idx: int, exc: Exception, clock,
                   cache_lost: bool = False) -> None:
        """Mid-decode death containment: the slot's request fails
        loudly, its PAGES ARE RELEASED, and the worker keeps serving —
        mirror of the predict replica's worker-death contract. Loudly
        means on the server's own stderr too, whatever the recorder is:
        with telemetry off `recorder.error` keeps nothing, and the
        client's copy of the error dies with the client."""
        slot = self.slots.slots[slot_idx]
        req = slot.request
        self.pool.release(self.slots.release(slot_idx))
        self._page_pool_event()
        self.recorder.error(f"gen-replica:{self.index}", exc=exc)
        err = "".join(traceback.format_exception_only(type(exc),
                                                      exc)).strip()
        print(f"[gen-replica {self.index}] request {req.request_id} "
              f"failed in slot {slot_idx} after {len(req.emitted)} tokens "
              f"(kv cache lost: {cache_lost}): {err}",
              file=sys.stderr, flush=True)
        req.finish(clock(), error=err)
        with self._mu:
            self.failed += 1
        self._request_event(req, ok=False, error=err)

    def _fail_occupied(self, exc: Exception, clock,
                       cache_lost: bool = False) -> None:
        for i, s in enumerate(self.slots.slots):
            if s is not None:
                self._fail_slot(i, exc, clock, cache_lost)

    def _alloc_cache(self):
        return self.net.init_kv_cache(self.plan.n_slots, self.plan.capacity,
                                      self.plan.kv_dtype,
                                      self.plan.page_size)

    def _alloc_tokens(self):
        """The slots' last tokens on the device, before any program has
        written one: [n_slots] ids and the step's counters behind them,
        the shape of every plain step's fetched array."""
        import jax.numpy as jnp

        return jnp.zeros(self.plan.n_slots + len(self.step_counters),
                         jnp.int32)

    def _fail_step(self, handed, own: list, exc: Exception, clock,
                   lost: bool = False) -> None:
        """Containment of a failed step, by what became of the cache it
        was handed. A step that raised before it ran left `handed` live:
        the program in flight, which ran before it on a sound cache, is
        retired first (its tokens are real and reach their requests),
        then only the step's `own` slots fail. One that ran has consumed
        it: the donated buffers are deleted and the outputs poisoned, so
        the rows of every occupied slot are gone, those of prefilling
        slots too (the program in flight is still retired first: it is
        older than the fault). `lost` says the caller knows worse: the
        FETCH of the program in flight raised, so that program failed on
        the device and whatever was dispatched on its output is poisoned
        with it. Either way every occupied slot fails once (pages
        released), the in-flight record is dropped, a fresh cache and
        token vector are allocated as in __init__, one `error` names the
        loss, `cache_losses` counts it once, and the worker serves on."""
        import jax

        if not lost:
            lost = any(leaf.is_deleted() for leaf in jax.tree.leaves(handed))
            try:
                if self._flight is not None:
                    self._land(clock)
            except Exception:
                lost = True  # the program before had failed too
        if not lost:
            for i in own:
                self._fail_slot(i, exc, clock)
            return
        # drop the failed step's outputs first: beside a fresh cache
        # they would hold the cache's bytes twice
        self._flight = self.cache = None
        self._fail_occupied(exc, clock, cache_lost=True)
        self.cache = self._alloc_cache()
        self._tokens = self._alloc_tokens()
        with self._mu:
            self.cache_losses += 1
        self.recorder.error(f"gen-replica:{self.index}", exc=exc,
                            lost="kv_cache")

    def _request_event(self, req: GenRequest, *, ok,
                       error: str | None = None) -> None:
        fields = dict(
            ok=ok, kind="generate", replica=self.index,
            # the generation trace key: the prefill_chunk spans carry
            # the same id, so the request's tree joins by trace_id even
            # though completion happens on the decode path
            trace_id=req.request_id,
            prompt_len=req.prompt_len,
            prompt_bucket=self.lattice.seq_bucket(req.prompt_len),
            new_tokens=len(req.emitted),
            queue_s=round(req.t_admitted - req.t_enqueue, 6),
            total_s=round(req.t_done - req.t_enqueue, 6))
        if req.t_first_token:
            fields["ttft_s"] = round(req.t_first_token - req.t_enqueue, 6)
        if error:
            fields["error"] = error
        self.recorder.request(req.request_id, **fields)

    def start(self, clock) -> None:
        self.last_beat = clock()

        def loop():
            while True:
                self.last_beat = clock()
                self._admit(clock)
                progressed = False
                try:
                    pi = self.slots.next_prefill()
                    if pi is not None:
                        self._run_prefill_chunk_bucketed(pi, clock)
                        progressed = True
                    active = self.slots.decoding()
                    if active:
                        if self._verify_jit is not None:
                            self._speculative_batch_step(active, clock)
                        else:
                            self._decode_batch_step(active, clock)
                        progressed = True
                    if not progressed and self._flight is not None:
                        # the last program of a busy spell: nothing to
                        # dispatch over it, so it is retired alone, and
                        # the slots it frees are admitted to next pass
                        try:
                            self._land(clock)
                        except Exception as exc:
                            self._fail_step(None, [], exc, clock, lost=True)
                        progressed = True
                except ReplicaKilled:
                    return  # dead: the fleet supervisor respawns
                if progressed:
                    continue
                # nothing to run: an idle device under this span has an
                # idle engine, not a slow one (opened outside `_cv`: no
                # recorder call runs under the queue lock)
                with self.recorder.span("idle_wait", follows=True,
                                        replica=self.index):
                    with self._cv:
                        if self._closed and not self.pending \
                                and not self.slots.busy():
                            if self.lifecycle != "dead":
                                self.lifecycle = "retired"
                            return
                        if not self.pending \
                                or self.slots.free_index() is None:
                            self._cv.wait(timeout=0.05)

        self.lifecycle = "serving"
        self._thread = threading.Thread(target=loop, daemon=True,
                                        name=f"gen-replica-{self.index}")
        self._thread.start()

    def respawn(self, clock) -> None:
        """Fleet-supervisor respawn: fresh thread over the SAME jit
        wrappers and KV cache (warmup re-runs and compiles nothing —
        every shape is already seen), pending requests continue from
        the worker's own queue."""
        self.alive = True
        self.lifecycle = "warming"
        self.current_batch = None
        self._flight = None  # a wedged thread's: its slots were reaped
        with self._mu:
            self.decode_steps_run = 0
        self.warmup(clock)
        self.start(clock)
        with self._cv:
            self._cv.notify_all()

    def reap(self, reason: str, clock) -> int:
        """Fail every occupied slot (pages released) — the hang case,
        where the wedged thread can never finish them. Pending requests
        stay queued for the respawned thread. Returns 0 (nothing is
        re-dispatched elsewhere: the queue IS this worker's)."""
        self.alive = False
        self.lifecycle = "dead"
        self.current_batch = None
        self._fail_occupied(
            RuntimeError(f"gen replica {self.index} reaped ({reason})"),
            clock)
        return 0

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    def join(self, timeout: float | None = None) -> None:
        if self._thread is not None:
            self._thread.join(timeout)

    @property
    def depth(self) -> int:
        with self._cv:
            return len(self.pending)

    def describe(self, now: float | None = None) -> dict:
        with self._mu:
            out = {"index": self.index, "state": self.lifecycle,
                   "alive": self.alive, "served": self.served,
                   "failed": self.failed,
                   "cache_losses": self.cache_losses,
                   "decode_steps_run": self.decode_steps_run,
                   "steps_ahead": self.steps_ahead,
                   **self.weights_facts}
            if self.speculative_k >= 2:
                out["verify_steps_run"] = self.verify_steps_run
                out["accepted_tokens"] = self.accepted_tokens
                out["drafted_tokens"] = self.drafted_tokens
        if now is not None:
            out["last_beat_age_s"] = round(max(0.0, now - self.last_beat),
                                           3)
        return out


class GenerationEngine:
    """Autoregressive generation serving: prefill/decode split over a
    paged KV cache, continuous batching across decode slots.

    Where `InferenceEngine` answers one forward per request, this
    engine holds each admitted request in a decode SLOT: its prompt
    prefills the slot's cache rows chunk-by-chunk (interleaved into the
    running decode batch so long prompts don't stall everyone else's
    tokens), then every decode step extends all active slots by one
    token — N generated tokens cost prefill + N single-token steps, not
    N full-sequence forwards. Shapes are lattice/page-grid points only:
    warmup compiles each (replica, prefill-bucket) and the (replica,
    decode-shape) once, and the trace counters stay frozen under mixed
    traffic (tier-1 asserts it). Page accounting and the
    exhaustion-queues-not-crashes contract live in serving/kvcache.py.

    TWO COPIES OF THE WEIGHTS, TWO OWNERS. `net.params` are the
    caller's, in the net's `param_dtype`; the engine reads them once,
    here (after the optional checkpoint restore), and never again. What
    every step of every replica is handed is the engine's own
    `WeightStore`: the same parameters in the net's `compute_dtype`
    (nn/decode.serving_params: cast once, as the walk would cast them
    in every step; the very arrays of `net.params`, no copy, where the
    two dtypes are equal). The `meta` event and stats() say which it is
    (`weights_dtype`, `weights_bytes`, `weights_cast_leaves`). A serving
    process that wants the stored tree's bytes back drops `net.params`
    once the engine is built."""

    def __init__(self, net, lattice: BucketLattice, *, slots: int = 4,
                 max_new_tokens: int = 16, page_size: int = 16,
                 pool_pages: int | None = None,
                 prefill_chunk: int | None = None, max_queue: int = 64,
                 replicas: int = 1, checkpoint: str | None = None,
                 speculative_k: int = 0, kv_dtype: str = "f32",
                 faults=None, recorder=None):
        self.recorder = recorder = _engine_recorder(recorder)
        if lattice.seq_lens is None:
            raise ValueError("generation needs a sequence lattice "
                             "(BucketLattice with seq_lens)")
        if net.params is None:
            net.init()
        self.restored_step = 0
        if checkpoint is not None:
            # the blessed fleet restore path (any-mesh checkpoint onto
            # this process's own one-device mesh)
            self.restored_step = restore_for_serving(net, checkpoint)
        from deeplearning4j_tpu.nn.decode import serving_params

        self.net = net
        self.weights = WeightStore(serving_params(net), net.state,
                                   step=self.restored_step)
        self._faults = None
        if faults is not None:
            self._faults = (faults if isinstance(faults,
                                                 ReplicaFaultInjector)
                            else ReplicaFaultInjector(faults, recorder))
        self.lattice = lattice
        chunk = (lattice.max_seq if prefill_chunk is None
                 else int(prefill_chunk))
        lattice.prefill_buckets(chunk)  # raises on a non-lattice chunk
        self.speculative_k = int(speculative_k)
        if self.speculative_k == 1 or self.speculative_k < 0:
            raise ValueError(
                "speculative_k is 0 (off) or >= 2 (a window of the true "
                f"last token plus k-1 drafts); got {speculative_k}")
        if self.speculative_k > int(max_new_tokens):
            raise ValueError(
                f"speculative_k {speculative_k} exceeds max_new_tokens "
                f"{max_new_tokens} — a window can never be used whole")
        self.plan = CachePlan(lattice.max_seq, max_new_tokens,
                              max(1, int(slots)), page_size,
                              pool_pages=pool_pages, kv_dtype=kv_dtype)
        # every timing mark of a request (t_enqueue .. t_done, the stamp
        # beside each streamed token) is read on this clock; the front
        # door reads it too, for the stream's lag
        self.clock = self._clock = time.monotonic
        self.costbook = CostBook(recorder)
        self._workers = [
            _GenWorker(i, net, lattice, self.plan, chunk, max_queue,
                       recorder, weights=self.weights,
                       faults=self._faults,
                       speculative_k=self.speculative_k,
                       costbook=self.costbook)
            for i in range(max(1, int(replicas)))]
        # ledger: published weights + every worker's paged KV cache
        ledger = MemoryLedger()
        ledger.register("params", lambda: self.weights.current.params)
        ledger.register("kv_pages",
                        lambda: [w.cache for w in self._workers])
        self.memsampler = MemorySampler(recorder, ledger)
        self.peak_flops = None  # set at warmup; stays None off-TPU
        self._rr = 0
        self._started = False
        recorder.meta(role="generation-engine",
                      replicas=len(self._workers),
                      lattice=lattice.describe(),
                      cache=self.plan.describe(net),
                      prefill_chunk=chunk,
                      speculative_k=self.speculative_k,
                      restored_step=self.restored_step,
                      **self._workers[0].weights_facts)

    # ------------------------------------------------------------- warmup
    def warmup(self) -> int:
        """Compile every (replica, prefill-bucket) and (replica,
        decode-shape) once. Returns the compile count; after this the
        trace counters are frozen."""
        compiles = sum(w.warmup(self._clock) for w in self._workers)
        if compiles:
            import jax

            self.peak_flops = peak_flops(jax.devices()[0])
            self.memsampler.sample("warmup",
                                   **_peak_fields(self.peak_flops))
        return compiles

    # ------------------------------------------------------------ serving
    def start(self) -> "GenerationEngine":
        if self._started:
            return self
        self._started = True
        for w in self._workers:
            w.start(self._clock)
        return self

    def submit_generate(self, tokens, max_new_tokens: int | None = None,
                        request_id: str | None = None) -> GenRequest:
        """Admit one generation request. Validates the prompt against
        the lattice (a too-long prompt is the client's 400) and the
        output budget against the cache geometry; a saturated pool +
        full queue raises QueueFullError (HTTP 503), never a crash."""
        toks = np.asarray(tokens)
        if toks.ndim != 1:
            raise ValueError(
                f"generation takes a [T] token prompt; got {toks.shape}")
        self.lattice.seq_bucket(int(toks.shape[0]))  # raises if too long
        max_new = (self.plan.max_new_tokens if max_new_tokens is None
                   else int(max_new_tokens))
        if not 1 <= max_new <= self.plan.max_new_tokens:
            raise ValueError(
                f"max_new_tokens must be in [1, "
                f"{self.plan.max_new_tokens}]; got {max_new}")
        from deeplearning4j_tpu.serving.batcher import _req_counter

        req = GenRequest(tokens=toks.astype(np.int32),
                         max_new_tokens=max_new,
                         request_id=request_id
                         or f"g{next(_req_counter)}",
                         t_enqueue=self._clock())
        worker = self._workers[self._rr % len(self._workers)]
        self._rr += 1
        worker.submit(req)
        return req

    def generate(self, tokens, max_new_tokens: int | None = None,
                 timeout: float = 60.0) -> list:
        """Synchronous convenience: submit + wait; returns the emitted
        token list. Raises on failure or timeout."""
        req = self.submit_generate(tokens, max_new_tokens)
        if not req.wait(timeout):
            raise TimeoutError(f"request {req.request_id} timed out "
                               f"after {timeout}s")
        if req.error is not None:
            raise RuntimeError(f"request {req.request_id} failed: "
                               f"{req.error}")
        return list(req.emitted)

    # ---------------------------------------------------- fleet lifecycle
    def fleet_workers(self) -> list:
        return list(self._workers)

    def fleet_snapshot(self) -> dict:
        n_serving = sum(1 for w in self._workers
                        if w.alive and w.lifecycle == "serving")
        return {"queue_depth": sum(w.depth for w in self._workers),
                "n_serving": n_serving, "n_replicas": n_serving}

    def fleet_reap(self, worker, reason: str = "died") -> int:
        return worker.reap(reason, self._clock)

    def fleet_respawn(self, worker) -> None:
        worker.respawn(self._clock)

    # -------------------------------------------------------------- drain
    def drain(self, timeout: float = 30.0) -> None:
        for w in self._workers:
            w.close()
        for w in self._workers:
            if w.lifecycle == "dead":
                continue  # a wedged daemon thread never joins
            w.join(timeout)
        self.recorder.event("span", name="drain", ok=True, seconds=0.0,
                            served=self.served, failed=self.failed)

    # -------------------------------------------------------------- stats
    @property
    def trace_count(self) -> int:
        return sum(w.trace_count for w in self._workers)

    @property
    def served(self) -> int:
        return sum(w.served for w in self._workers)

    @property
    def failed(self) -> int:
        return sum(w.failed for w in self._workers)

    def stats(self) -> dict:
        now = self._clock()
        pools = [w.pool.describe() for w in self._workers]
        # rate-limited memory tick — the stats path is a batch boundary
        self.memsampler.maybe_sample("stats_tick")
        return {
            "replicas": len(self._workers),
            "served": self.served,
            "failed": self.failed,
            "tokens_out": sum(w.tokens_out for w in self._workers),
            "steps_ahead": sum(w.steps_ahead for w in self._workers),
            "queue_depth": sum(w.depth for w in self._workers),
            "trace_count": self.trace_count,
            "restored_step": self.restored_step,
            "lattice": self.lattice.describe(),
            "cache": self.plan.describe(self.net),
            **self._workers[0].weights_facts,
            "page_pools": pools,
            "fleet": [w.describe(now) for w in self._workers],
            "weights": self.weights.describe(),
            "generate": True,
            "speculative": self._speculative_stats(),
            "memory": self.memsampler.last,
            **_peak_fields(self.peak_flops),
        }

    def _speculative_stats(self) -> dict:
        """The /stats + /metrics acceptance surface: emitted tokens per
        verify step (the headline), draft acceptance rate, and the
        host-side proposer overhead — all zero/off when speculative
        decoding is disabled."""
        if self.speculative_k < 2:
            return {"enabled": False, "k": 0}
        steps = sum(w.verify_steps_run for w in self._workers)
        slot_steps = sum(w.slot_steps for w in self._workers)
        accepted = sum(w.accepted_tokens for w in self._workers)
        drafted = sum(w.drafted_tokens for w in self._workers)
        # tokens beyond the 1-per-slot-step a plain decode would emit
        bonus = accepted - slot_steps
        overhead = sum(w.draft_overhead_s for w in self._workers)
        return {
            "enabled": True, "k": self.speculative_k,
            "verify_steps": steps,
            "accepted_tokens_per_step": (round(accepted / slot_steps, 4)
                                         if slot_steps else 0.0),
            "draft_acceptance_rate": (round(bonus / drafted, 4)
                                      if drafted else 0.0),
            "draft_overhead_us_total": round(overhead * 1e6, 1),
        }
