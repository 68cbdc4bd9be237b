"""Zero-downtime fleet operations: live weight hot-swap, replica
self-healing, and telemetry-driven autoscaling (ISSUE 13).

Every pillar this composes already exists — any-mesh checkpoint restore
(reshard/), the continuous-batching engine (serving/engine.py), elastic
fault machinery (distributed/faults.py), and request-level telemetry —
but until this module a serving fleet could not pick up a new
checkpoint, replace a dead replica, or change size without dropping
traffic. Three operations, all off the request path (the SparkNet
train-to-serve story, arXiv:1511.06051, done with the reshard planner
of arXiv:2112.01075):

* **Live weight hot-swap** — `hot_swap` restores a checkpoint step
  through the reshard-aware `restore_for_serving` into a SHADOW net (a
  double-buffered param slot the replicas never read), validates it
  against the currently-served set (tree structure, per-leaf
  shape/dtype, device placement), and publishes it through the
  `WeightStore`: one atomic reference flip. A replica reads
  `store.current` exactly ONCE per batch, so every in-flight and queued
  request completes against a coherent param set — generation N or
  N+1, never a mix — and telemetry `request` events carry the
  generation each batch served (`weight_gen`), making the flip visible
  and the zero-failed-requests property assertable from the JSONL
  alone. A restore that fails validation (shape mismatch, truncated
  checkpoint, wrong conf) raises `WeightSwapError` with the OLD weights
  still serving; both outcomes leave a typed `weight_swap` event
  (step, restore_ms, generation, ok). `CheckpointWatcher` polls a
  checkpoint directory and hot-swaps each newly committed step — the
  training-fleet-publishes / server-follows loop.

* **Replica self-healing** — `ReplicaFaultInjector` carries
  `distributed/faults.py` replica-scoped specs (`r0:kill@batch3`,
  `r1:hang@batch2`, `r0:kill@decode5`) into the engine's worker
  threads; `FleetSupervisor.poll` detects a death from the thread's
  liveness or heartbeat staleness, reaps it (fails the in-flight batch
  loudly, drains its queued batches back to the batcher), and respawns
  it after a `RespawnBackoff` delay — re-running warmup before
  re-admission, which compiles NOTHING because the jit executables
  survive a thread death in-process, so the trace counter stays frozen
  (the chaos replay's zero-retrace gate).

* **Telemetry-driven autoscaling** — `autoscale_decision` is a pure
  function of (queue depth, recent p99, replica count, clock,
  hysteresis state); the supervisor samples the engine's batcher and
  the recorder's ring buffer, emits a typed `autoscale` event per poll
  (the occupancy headline's source), and grows/drains replicas through
  `engine.add_replica()` / `engine.retire_replica()` — scale-down
  drains: a retiring replica finishes its queued work before its
  thread exits.

Every decision surface (swap validation, supervisor reap/respawn,
autoscale hysteresis, backoff) is a pure function or takes an
injectable clock, so tier-1 drives the whole state machine with fake
clocks and zero sleeps. jax imports stay inside functions: the module
is importable under the graftlint AST stubs.

This module is the BLESSED param publish/flip path (graftlint G021):
assigning a serving worker's live params directly, or calling
`resume_from` on an engine's net anywhere else in serving/, bypasses
the double buffer, the validation, and the telemetry record.
"""

from __future__ import annotations

import os
import random
import threading
import time
from dataclasses import dataclass
from typing import Any, Optional

from deeplearning4j_tpu.distributed.faults import FaultSchedule


class WeightSwapError(RuntimeError):
    """A hot-swap restore was rejected (shape/placement mismatch,
    truncated checkpoint, no committed step); the old weights are still
    serving — rejection never interrupts traffic."""


class ReplicaKilled(RuntimeError):
    """An injected replica death (`rN:kill@...`). A thread cannot be
    SIGKILLed: the engine fails the in-flight batch loudly and lets the
    worker thread exit; the supervisor requeues + respawns."""


# ------------------------------------------------------------ weight store

@dataclass(frozen=True)
class WeightSet:
    """One immutable published param set. Frozen: a replica that read
    this set serves ALL of it — the flip can never hand out a mix."""

    generation: int
    step: int
    params: Any
    state: Any


class WeightStore:
    """The double buffer behind live hot-swap.

    `current` is a single attribute read (atomic under the GIL) of an
    immutable `WeightSet`; `publish` builds the standby set COMPLETELY
    before the one-reference flip, so a reader observes either the old
    or the new generation, never a partial write — and the old set
    object stays intact for batches that already grabbed it. Publishers
    serialize on a lock; readers never lock."""

    def __init__(self, params, state, step: int = 0):
        self._current = WeightSet(0, int(step), params, state)
        self._lock = threading.Lock()
        self.last_swap_ts: Optional[float] = None

    @property
    def current(self) -> WeightSet:
        return self._current

    @property
    def generation(self) -> int:
        return self._current.generation

    @property
    def step(self) -> int:
        return self._current.step

    def publish(self, params, state, step: int) -> WeightSet:
        """Flip to a new generation. The standby `WeightSet` is fully
        constructed BEFORE the assignment — the assignment IS the swap."""
        with self._lock:
            new = WeightSet(self._current.generation + 1, int(step),
                            params, state)
            self._current = new
            self.last_swap_ts = time.time()
            return new

    def describe(self) -> dict:
        return {"generation": self.generation, "step": self.step,
                "last_swap_ts": self.last_swap_ts}


def validate_swap(current_params, candidate_params) -> None:
    """The pre-flip gate: the candidate tree must match the served tree
    in structure and per-leaf shape/dtype, and every candidate leaf must
    live on this process's own devices (a leaf resharded onto a remote
    mesh would fail mid-forward, after the flip — too late). Raises
    `WeightSwapError` naming the first offending leaf."""
    import jax

    cur_leaves, cur_def = jax.tree.flatten(current_params)
    new_leaves, new_def = jax.tree.flatten(candidate_params)
    if cur_def != new_def:
        raise WeightSwapError(
            f"param tree structure mismatch: serving {cur_def} vs "
            f"candidate {new_def}")
    local = set(jax.local_devices())
    for i, (a, b) in enumerate(zip(cur_leaves, new_leaves)):
        if getattr(a, "shape", None) != getattr(b, "shape", None) or \
                str(getattr(a, "dtype", "")) != str(getattr(b, "dtype", "")):
            raise WeightSwapError(
                f"leaf {i} mismatch: serving "
                f"{getattr(a, 'shape', None)}/{getattr(a, 'dtype', None)} "
                f"vs candidate "
                f"{getattr(b, 'shape', None)}/{getattr(b, 'dtype', None)}")
        devs = getattr(getattr(b, "sharding", None), "device_set", None)
        if devs is not None and not set(devs) <= local:
            raise WeightSwapError(
                f"leaf {i} is placed on non-local devices "
                f"{set(devs) - local} — the restore must target this "
                "serving process's own mesh")


# -------------------------------------------------------- restore + swap

def restore_for_serving(net, checkpoint_dir: str, step=None) -> int:
    """The blessed serving restore: reshard-aware `resume_from` onto
    this process's OWN one-device data mesh (the checkpoint may have
    been written by any training fleet shape — reshard/ plans the
    placement and orbax reads only the needed slices). Engines call
    this at startup; `hot_swap` calls it against a shadow net. Returns
    the restored step (0 = cold start)."""
    import jax

    from deeplearning4j_tpu.parallel.mesh import make_mesh

    return int(net.resume_from(
        checkpoint_dir, step=step,
        target_mesh=make_mesh({"data": 1}, devices=jax.local_devices())))


def _shadow_net(net):
    """A fresh net with the same configuration — the double-buffered
    restore target. Its params are the standby slot; the serving net's
    own params are never touched."""
    clone = getattr(net, "clone", None)
    if callable(clone):
        return clone()
    import copy

    shadow = type(net)(copy.deepcopy(net.conf))
    shadow.init()
    return shadow


def latest_step(checkpoint_dir: str) -> Optional[int]:
    """Newest fully-committed step under a ShardedCheckpointer layout
    (meta.json is written last, so a step without one is mid-write).
    Pure stdlib — the watcher polls this without importing orbax."""
    try:
        entries = os.listdir(checkpoint_dir)
    except OSError:
        return None
    steps = []
    for d in entries:
        if d.startswith("step_") and os.path.exists(
                os.path.join(checkpoint_dir, d, "meta.json")):
            try:
                steps.append(int(d.split("_", 1)[1]))
            except ValueError:
                pass
    return max(steps) if steps else None


def validate_checkpoint_shapes(current_params, checkpoint_dir: str,
                               step: int) -> None:
    """The PRE-restore gate: the checkpoint's RECORDED array metadata
    (orbax, written at save time) must match the served param tree
    leaf-for-leaf in structure, shape, and dtype. This must happen
    before any read: the reshard-aware restore path loads only the
    slices a target template asks for, so a wrong-architecture
    checkpoint would otherwise partially load into correctly-SHAPED
    garbage that a post-restore check cannot see. An unreadable /
    truncated step fails the same gate (rejection is the safe
    direction — the old weights keep serving)."""
    import jax
    import orbax.checkpoint as ocp

    model_dir = os.path.join(checkpoint_dir, f"step_{step}", "model")
    try:
        meta = ocp.StandardCheckpointer().metadata(model_dir)
    except Exception as exc:
        raise WeightSwapError(
            f"checkpoint step {step} is unreadable (truncated or "
            f"corrupt): {exc}") from exc
    # orbax 0.11: StepMetadata, the recorded tree under item_metadata
    tree = getattr(getattr(meta, "item_metadata", None), "tree", None)
    recorded = tree.get("params") if isinstance(tree, dict) else None
    if recorded is None:
        raise WeightSwapError(
            f"checkpoint step {step} records no params tree")
    cur_leaves, cur_def = jax.tree.flatten(current_params)
    rec_leaves, rec_def = jax.tree.flatten(recorded)
    if cur_def != rec_def:
        raise WeightSwapError(
            f"checkpoint param tree structure mismatch: serving "
            f"{cur_def} vs checkpoint {rec_def}")
    for i, (a, b) in enumerate(zip(cur_leaves, rec_leaves)):
        a_shape = tuple(getattr(a, "shape", ()) or ())
        b_shape = tuple(getattr(b, "shape", ()) or ())
        if a_shape != b_shape or \
                str(getattr(a, "dtype", "")) != str(getattr(b, "dtype",
                                                            "")):
            raise WeightSwapError(
                f"checkpoint leaf {i} mismatch: serving "
                f"{a_shape}/{getattr(a, 'dtype', None)} vs checkpoint "
                f"{b_shape}/{getattr(b, 'dtype', None)} — wrong "
                "architecture for this engine")


def hot_swap(engine, checkpoint_dir: str, step=None) -> dict:
    """Restore `step` (default: latest) into a shadow net OFF the
    request path, validate, and atomically flip every replica onto the
    new generation. Emits the typed `weight_swap` event either way; on
    any failure the old weights keep serving and `WeightSwapError`
    raises with the cause."""
    rec = engine.recorder
    t0 = time.perf_counter()
    try:
        if getattr(engine, "_workers", None):
            raise WeightSwapError(
                "generation engines hot-swap by rolling replica "
                "restart, not a live flip: an in-flight generation's "
                "KV cache binds it to the weights that wrote it")
        target = step if step is not None else latest_step(checkpoint_dir)
        if target is None:
            raise WeightSwapError(
                f"no committed checkpoint under {checkpoint_dir}")
        served = engine.weights.current.params
        validate_checkpoint_shapes(served, checkpoint_dir, target)
        shadow = _shadow_net(engine.net)
        restored = restore_for_serving(shadow, checkpoint_dir,
                                       step=target)
        validate_swap(served, shadow.params)
        new = engine.weights.publish(shadow.params, shadow.state, restored)
    except Exception as exc:
        restore_ms = round(1000.0 * (time.perf_counter() - t0), 3)
        rec.error("weight_swap", exc=exc)
        rec.event("weight_swap", ok=False, step=step,
                  restore_ms=restore_ms,
                  generation=engine.weights.generation,
                  error=f"{type(exc).__name__}: {exc}")
        if isinstance(exc, WeightSwapError):
            raise
        raise WeightSwapError(f"hot swap failed, old weights still "
                              f"serving: {exc}") from exc
    restore_ms = round(1000.0 * (time.perf_counter() - t0), 3)
    rec.event("weight_swap", ok=True, step=new.step,
              restore_ms=restore_ms, generation=new.generation)
    return {"step": new.step, "generation": new.generation,
            "restore_ms": restore_ms}


class CheckpointWatcher:
    """Follow a training fleet's checkpoint directory: each newly
    committed step hot-swaps into the engine. A step whose restore is
    REJECTED is remembered (never retried in a hot loop) and the old
    weights keep serving. `poll_once` is the testable unit; `start`
    wraps it in a daemon thread for live use."""

    def __init__(self, engine, checkpoint_dir: str, *,
                 interval_s: float = 0.5):
        self.engine = engine
        self.checkpoint_dir = checkpoint_dir
        self.interval_s = float(interval_s)
        self.seen_step = int(engine.weights.step)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def poll_once(self) -> Optional[dict]:
        """One watch tick: swap the newest unseen committed step, if
        any. Returns the swap record, a rejection record (`ok: False`),
        or None when nothing is new."""
        step = latest_step(self.checkpoint_dir)
        if step is None or step <= self.seen_step:
            return None
        self.seen_step = step  # even a rejected step is not re-tried
        try:
            out = hot_swap(self.engine, self.checkpoint_dir, step=step)
        except WeightSwapError as exc:
            return {"ok": False, "step": step, "error": str(exc)}
        out["ok"] = True
        return out

    def start(self) -> "CheckpointWatcher":
        def loop():
            while not self._stop.wait(self.interval_s):
                self.poll_once()

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="fleet-ckpt-watch")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)


# ------------------------------------------------------- fault injection

class ReplicaFaultInjector:
    """The serving half of the fault harness: replica-scoped specs from
    `distributed/faults.py` fire inside the worker thread that owns the
    unit counter. One-shot per fault (a respawned replica restarts its
    batch counter; the same spec must not re-kill it forever). The
    `fault` telemetry event lands BEFORE the fault acts — same contract
    as the process-scoped runtime."""

    def __init__(self, schedule, recorder=None):
        if not isinstance(schedule, FaultSchedule):
            schedule = FaultSchedule.parse(schedule)
        self.faults = [f for f in schedule if f.scope == "replica"]
        self.recorder = recorder
        self._fired: set = set()
        self._lock = threading.Lock()

    def _rec(self):
        if self.recorder is not None:
            return self.recorder
        from deeplearning4j_tpu.telemetry import get_default

        return get_default()

    def check(self, replica_index: int, unit: str, count: int) -> None:
        """Fire any scheduled fault for (replica, unit, count). kill
        raises `ReplicaKilled`; hang parks this thread forever (the
        supervisor's heartbeat bound reaps it)."""
        for f in self.faults:
            if (f.process_id != replica_index or f.unit != unit
                    or f.step != count):
                continue
            with self._lock:
                if f in self._fired:
                    continue
                self._fired.add(f)
            self._rec().fault(f"replica-{f.kind}", replica=replica_index,
                              spec=f.spec(), unit=unit, count=count,
                              fired=True)
            if f.kind == "kill":
                raise ReplicaKilled(f.spec())
            if f.kind == "hang":
                threading.Event().wait()  # forever; reaped by heartbeat


# ------------------------------------------------------- respawn backoff

class RespawnBackoff:
    """Exponential respawn delay with a deterministic, CAPPED jitter: a
    replica that keeps dying (a poisoned warmup, a bad weight set) must
    not be respawned in a tight loop, and a fleet of supervisors must
    not respawn in lockstep. Seeded stdlib Random — the same seed
    always produces the same delays (fake-clock testable)."""

    def __init__(self, base_s: float = 0.05, factor: float = 2.0,
                 cap_s: float = 2.0, jitter_frac: float = 0.2,
                 seed: int = 0):
        if not 0.0 <= jitter_frac <= 1.0:
            raise ValueError(f"jitter_frac must be in [0, 1], got "
                             f"{jitter_frac}")
        self.base_s = float(base_s)
        self.factor = float(factor)
        self.cap_s = float(cap_s)
        self.jitter_frac = float(jitter_frac)
        self._rng = random.Random(seed)
        self.attempt = 0

    def next(self) -> float:
        """Delay before the next respawn attempt: min(base * factor^k,
        cap) plus jitter in [0, jitter_frac * delay] — the jitter is
        capped BY the capped delay, so the total never exceeds
        cap_s * (1 + jitter_frac)."""
        delay = min(self.base_s * (self.factor ** self.attempt),
                    self.cap_s)
        self.attempt += 1
        return delay + self._rng.uniform(0.0, self.jitter_frac * delay)

    def reset(self) -> None:
        """A replica that served again cleanly earns a fresh ladder."""
        self.attempt = 0


# ------------------------------------------------------------ autoscaling

@dataclass(frozen=True)
class AutoscalePolicy:
    """The hysteresis knobs. Scale UP when queue depth or recent p99
    crosses its high-water mark (a burst is building faster than the
    fleet drains it); scale DOWN only when BOTH are under the low-water
    marks (either signal still hot holds the fleet). Separate
    cooldowns: growing is cheap and urgent, draining is neither."""

    min_replicas: int = 1
    max_replicas: int = 4
    up_queue_depth: int = 8
    down_queue_depth: int = 1
    up_p99_ms: float = float("inf")
    down_p99_ms: float = float("inf")
    cooldown_up_s: float = 0.25
    cooldown_down_s: float = 2.0
    # HBM headroom floor (0 disables). In-process replicas SHARE the
    # device, so low headroom vetoes growth (a new replica's KV pages
    # would land on an already-tight HBM) and, past the floor, drains
    # one replica to free pages — the memory analogue of the latency
    # signal, fed from the telemetry `memory` events.
    min_headroom: float = 0.0


@dataclass
class AutoscaleState:
    """The supervisor's per-fleet hysteresis memory."""

    last_up_t: float = float("-inf")
    last_down_t: float = float("-inf")


def autoscale_decision(policy: AutoscalePolicy, state: AutoscaleState, *,
                       queue_depth: int, p99_ms: float, n_replicas: int,
                       now: float, headroom: Optional[float] = None) -> int:
    """The pure scale decision: +1 (grow), -1 (drain one), or 0. Mutates
    only `state` (the hysteresis marks) — fake-clock testable. A
    scale-up also arms the DOWN cooldown so a burst's tail can't
    immediately drain what its head grew. `headroom` (fraction of HBM
    left, None = no signal) gates against `policy.min_headroom`: a
    breach vetoes growth and drains one replica on the usual DOWN
    cooldown — memory pressure outranks latency pressure."""
    breached = (policy.min_headroom > 0 and headroom is not None
                and headroom < policy.min_headroom)
    if breached:
        if n_replicas > policy.min_replicas \
                and now - state.last_down_t >= policy.cooldown_down_s:
            state.last_down_t = now
            return -1
        return 0
    over = (queue_depth >= policy.up_queue_depth
            or p99_ms >= policy.up_p99_ms)
    if over and n_replicas < policy.max_replicas \
            and now - state.last_up_t >= policy.cooldown_up_s:
        state.last_up_t = now
        state.last_down_t = now
        return 1
    under = (queue_depth <= policy.down_queue_depth
             and p99_ms <= policy.down_p99_ms)
    if under and n_replicas > policy.min_replicas \
            and now - state.last_down_t >= policy.cooldown_down_s \
            and now - state.last_up_t >= policy.cooldown_down_s:
        state.last_down_t = now
        return -1
    return 0


def recent_p99_ms(recorder, n: int = 64) -> float:
    """p99 of the last `n` successful `request` events' `total_s` in the
    recorder's in-memory ring — the supervisor's latency signal (0.0
    when no requests have completed yet)."""
    lat = [1000.0 * float(ev["total_s"]) for ev in recorder.events
           if ev.get("event") == "request" and ev.get("ok")
           and "total_s" in ev][-n:]
    if not lat:
        return 0.0
    lat.sort()
    k = min(len(lat) - 1, max(0, int(round(0.99 * (len(lat) - 1)))))
    return lat[k]


def recent_headroom(recorder) -> Optional[float]:
    """Min per-device HBM headroom (1 - bytes_in_use/bytes_limit) from
    the LATEST `memory` event in the recorder's in-memory ring — the
    supervisor's memory signal, same shape as recent_p99_ms. None when
    no memory event carries device limits (off-TPU, or sampling off):
    no signal, not "plenty of room"."""
    for ev in reversed(recorder.events):
        if ev.get("event") != "memory":
            continue
        ratios = []
        for row in (ev.get("devices") or {}).values():
            limit = float(row.get("bytes_limit", 0) or 0)
            if limit > 0:
                ratios.append(
                    1.0 - float(row.get("bytes_in_use", 0)) / limit)
        return min(ratios) if ratios else None
    return None


# ------------------------------------------------------------- supervisor

class FleetSupervisor:
    """The per-engine operations loop: replica self-healing plus
    (optionally) telemetry-driven autoscaling.

    `poll(now)` is the whole state machine — injectable clock, no
    internal sleeps — and `run_in_thread` wraps it for live fleets.
    Each tick:

    1. **Detect** — a worker is dead when its thread has exited without
       draining (the kill path marks itself dead) or when it holds a
       batch past `death_after_s` of heartbeat silence (the hang path:
       a wedged thread cannot report its own death).
    2. **Reap** — `engine.fleet_reap` fails the in-flight batch loudly
       (its requests get `request` events with `ok: false` — the
       BOUNDED failure set) and drains queued batches back to the
       batcher FIFO, where live replicas pick them up.
    3. **Respawn** — after the backoff delay, `engine.fleet_respawn`
       re-runs warmup on the same jit wrappers (zero compiles: the
       executables survive a thread death) and re-admits the replica;
       a `replica-respawn` fault event carries `respawn_ms`.
    4. **Autoscale** — when a policy is set: sample queue depth, the
       recorder ring's recent p99, and the latest `memory` event's HBM
       headroom (recent_headroom — the memory analogue of the
       straggler signal), apply `autoscale_decision`, and grow/drain
       through the engine; every tick emits a typed `autoscale` event
       (the occupancy bench row's only source) carrying the headroom
       it acted on.
    """

    def __init__(self, engine, *, policy: Optional[AutoscalePolicy] = None,
                 death_after_s: float = 2.0,
                 backoff: Optional[RespawnBackoff] = None,
                 clock=time.monotonic, recorder=None):
        self.engine = engine
        self.policy = policy
        self.death_after_s = float(death_after_s)
        self.backoff = backoff or RespawnBackoff()
        self._clock = clock
        self.recorder = recorder if recorder is not None else engine.recorder
        self.scale_state = AutoscaleState()
        self._respawn_due: dict = {}  # worker -> due time
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------- tick
    def _is_dead(self, w, now: float) -> bool:
        if not w.alive:
            return True  # marked itself dead (the kill path)
        thread = getattr(w, "_thread", None)
        if thread is not None and not thread.is_alive() \
                and w.lifecycle == "serving":
            return True  # exited without draining
        if getattr(w, "current_batch", None) is not None \
                and now - w.last_beat > self.death_after_s:
            return True  # wedged mid-batch: heartbeat went stale
        return False

    def poll(self, now: Optional[float] = None) -> dict:
        now = self._clock() if now is None else now
        actions = {"reaped": [], "respawned": [], "scale": 0}
        for w in self.engine.fleet_workers():
            if w.lifecycle in ("draining", "retired"):
                continue  # scale-down drain is not a death
            if w in self._respawn_due:
                continue
            if w.lifecycle == "dead" or self._is_dead(w, now):
                requeued = self.engine.fleet_reap(
                    w, reason="heartbeat-stale" if w.alive else "died")
                delay = self.backoff.next()
                self._respawn_due[w] = now + delay
                self.recorder.fault(
                    "replica-dead", replica=w.index, requeued=requeued,
                    respawn_in_s=round(delay, 4))
                actions["reaped"].append(w.index)
        for w, due in list(self._respawn_due.items()):
            if now < due:
                continue
            del self._respawn_due[w]
            t0 = time.perf_counter()
            self.engine.fleet_respawn(w)
            respawn_ms = round(1000.0 * (time.perf_counter() - t0), 3)
            self.backoff.reset()
            self.recorder.fault("replica-respawn", replica=w.index,
                                respawn_ms=respawn_ms)
            actions["respawned"].append(w.index)
        if self.policy is not None:
            snap = self.engine.fleet_snapshot()
            p99 = recent_p99_ms(self.recorder)
            headroom = recent_headroom(self.recorder)
            d = autoscale_decision(
                self.policy, self.scale_state,
                queue_depth=snap["queue_depth"], p99_ms=p99,
                n_replicas=snap["n_replicas"], now=now,
                headroom=headroom)
            if d > 0:
                self.engine.add_replica()
            elif d < 0:
                self.engine.retire_replica()
            actions["scale"] = d
            fields = {}
            if headroom is not None:
                fields["headroom"] = round(headroom, 4)
            self.recorder.event(
                "autoscale", n_serving=snap["n_serving"] + max(0, d),
                n_replicas=snap["n_replicas"] + d,
                queue_depth=snap["queue_depth"],
                p99_ms=round(p99, 3), action=d,
                max_replicas=self.policy.max_replicas, **fields)
        return actions

    # ------------------------------------------------------------- live
    def run_in_thread(self, interval_s: float = 0.05) -> "FleetSupervisor":
        def loop():
            while not self._stop.wait(interval_s):
                try:
                    self.poll()
                except Exception as exc:  # keep supervising; log loudly
                    self.recorder.error("fleet-supervisor", exc=exc)

        self._thread = threading.Thread(target=loop, daemon=True,
                                        name="fleet-supervisor")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)


__all__ = [
    "AutoscalePolicy",
    "AutoscaleState",
    "CheckpointWatcher",
    "FleetSupervisor",
    "ReplicaFaultInjector",
    "ReplicaKilled",
    "RespawnBackoff",
    "WeightSet",
    "WeightStore",
    "WeightSwapError",
    "autoscale_decision",
    "hot_swap",
    "latest_step",
    "recent_p99_ms",
    "restore_for_serving",
    "validate_swap",
]
