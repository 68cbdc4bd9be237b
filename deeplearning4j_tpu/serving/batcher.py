"""Dynamic batching: single requests coalesce into bucket-shaped
batches under a max-wait deadline.

The state machine (documented in ARCHITECTURE §Serving):

    submit() appends a PendingRequest to a FIFO ->
    the dispatcher blocks in next_batch() ->
      CUT a batch when the compatible FIFO prefix fills the largest
      batch bucket, OR when the OLDEST pending request has waited
      max_wait (latency bound beats batch efficiency), OR on drain
      (close() flushes leftovers) ->
    assemble() pads the group into its lattice bucket (zero padding +
    a validity mask) and hands a Batch to the engine.

`plan_batch` — the cut decision — is a pure function of (pending, now),
so the deadline/coalescing logic is unit-tested with a fake clock and
no real sleeps; the Batcher wraps it in a condition variable for the
live threaded path. Assembly is host-side numpy only: the device never
sees a per-request array, just the padded bucket batch.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from deeplearning4j_tpu.serving.buckets import Bucket, BucketLattice

_req_counter = itertools.count()


@dataclass
class PendingRequest:
    """One admitted request: the raw (unpadded) features, timing marks,
    and the completion event the front-end blocks on."""

    features: np.ndarray
    mask: np.ndarray | None = None
    request_id: str = ""
    t_enqueue: float = 0.0
    # filled by the engine on completion
    t_assembled: float = 0.0
    t_done: float = 0.0
    result: np.ndarray | None = None
    error: str | None = None
    done: threading.Event = field(default_factory=threading.Event)

    def wait(self, timeout: float | None = None) -> bool:
        return self.done.wait(timeout)

    @property
    def length(self) -> int:
        """Time length for sequence requests (first axis)."""
        return int(self.features.shape[0])


@dataclass
class Batch:
    """One assembled bucket batch: padded arrays plus the requests whose
    rows they carry (row i of `features` is requests[i] for i < n_real;
    rows beyond are padding and are sliced off after the forward)."""

    bucket: Bucket
    features: np.ndarray
    mask: np.ndarray | None
    requests: list
    t_cut: float = 0.0
    assemble_seconds: float = 0.0
    # correlation handoff (telemetry/recorder.py): the trace this batch
    # roots and the span the replica thread's `forward` parents to —
    # the cut's `queue` -> `batch_assemble` chain and the forward/
    # request events become ONE tree across the thread boundary
    trace_id: str | None = None
    parent_span: str | None = None

    @property
    def n_real(self) -> int:
        return len(self.requests)


def _compatible(a: PendingRequest, b: PendingRequest,
                sequence: bool) -> bool:
    """Whether two requests can share a batch: same dtype and same
    trailing feature dims (sequence models may differ in length — the
    first axis — which padding absorbs; fixed-shape models must match
    exactly)."""
    if a.features.dtype != b.features.dtype:
        return False
    if sequence:
        return a.features.shape[1:] == b.features.shape[1:]
    return a.features.shape == b.features.shape


def plan_batch(pending, now: float, max_wait_s: float,
               lattice: BucketLattice, *, sequence: bool = False,
               closed: bool = False) -> int:
    """The cut decision — how many requests to take off the head of the
    FIFO right now (0 = keep waiting). Pure function of its arguments so
    the deadline/coalescing logic tests with a fake clock.

    Cuts happen when (in priority order):
      1. the compatible FIFO prefix fills the LARGEST batch bucket
         (a full batch never waits);
      2. the oldest pending request has waited `max_wait_s` — the
         latency deadline beats batch efficiency;
      3. the batcher is draining (`closed`): flush what's there.
    """
    if not pending:
        return 0
    head = pending[0]
    take = 1
    for req in itertools.islice(pending, 1, None):
        if take >= lattice.max_batch:
            break
        if not _compatible(head, req, sequence):
            break  # FIFO order preserved: an incompatible request ends
            # the group rather than being skipped over
        take += 1
    if take >= lattice.max_batch:
        return lattice.max_batch
    if closed:
        return take
    if now - head.t_enqueue >= max_wait_s:
        return take
    return 0


def assemble(requests: list, lattice: BucketLattice, *,
             sequence: bool = False) -> Batch:
    """Pad a compatible group into its bucket: zero padding on the batch
    axis (rows sliced off after the forward — inference-mode forwards
    are row-independent, proven at atol 0 in tier-1) and, for sequence
    models, zero padding on the time axis with a [B, T] validity mask
    (1 = real token) so masked attention never reads a padded key."""
    if not requests:
        raise ValueError("cannot assemble an empty batch")
    n = len(requests)
    if sequence:
        max_len = max(r.length for r in requests)
        bucket = lattice.select(n, max_len)
        feat0 = requests[0].features
        shape = (bucket.batch, bucket.seq) + feat0.shape[1:]
        features = np.zeros(shape, dtype=feat0.dtype)
        mask = np.zeros((bucket.batch, bucket.seq), dtype=np.float32)
        for i, r in enumerate(requests):
            features[i, :r.length] = r.features
            if r.mask is not None:
                mask[i, :r.length] = np.asarray(r.mask, np.float32)
            else:
                mask[i, :r.length] = 1.0
        # padding ROWS keep an all-zero mask: a fully-masked row is a
        # valid (if degenerate) sequence and its output is discarded
        return Batch(bucket, features, mask, list(requests))
    bucket = lattice.select(n, None)
    feat0 = requests[0].features
    features = np.zeros((bucket.batch,) + feat0.shape, dtype=feat0.dtype)
    for i, r in enumerate(requests):
        features[i] = r.features
    return Batch(bucket, features, None, list(requests))


@dataclass
class GenRequest:
    """One admitted generation request: the raw prompt tokens, the
    output budget, timing marks, the emitted-token record, and a
    per-request stream queue the HTTP handler drains (None-terminated)
    so tokens flow to the client as they decode. Each stream item is
    `(token, now)`: the engine's clock reading of the step that made
    the token rides beside it, so the handler can say how long the
    token took from the step to the socket."""

    tokens: np.ndarray            # [L] int prompt
    max_new_tokens: int = 16
    request_id: str = ""
    t_enqueue: float = 0.0
    t_admitted: float = 0.0
    t_first_token: float = 0.0    # TTFT mark: prefill's last chunk done
    t_done: float = 0.0
    emitted: list = field(default_factory=list)
    error: str | None = None
    done: threading.Event = field(default_factory=threading.Event)
    stream: queue.Queue = field(default_factory=queue.Queue)

    @property
    def prompt_len(self) -> int:
        return int(self.tokens.shape[0])

    def wait(self, timeout: float | None = None) -> bool:
        return self.done.wait(timeout)

    def emit(self, token: int, now: float) -> None:
        if not self.emitted:
            self.t_first_token = now
        self.emitted.append(int(token))
        self.stream.put((int(token), now))

    def finish(self, now: float, error: str | None = None) -> None:
        self.error = error
        self.t_done = now
        self.stream.put(None)     # stream sentinel: no more tokens
        self.done.set()


class _Slot:
    """One decode slot's live state: the request it carries, how far its
    prompt has prefilled (`start`), the position its NEXT token writes
    (`pos`), the output tokens whose program has been dispatched (`sent`)
    and the pages it holds. `start`, `pos` and `sent` are the engine's own
    count and advance when a program is DISPATCHED; `request.emitted` and
    `last_token` follow when its tokens have come home."""

    __slots__ = ("request", "start", "pos", "sent", "pages", "last_token")

    def __init__(self, request: GenRequest, pages: int):
        self.request = request
        self.start = 0            # prompt tokens already prefilled
        self.pages = pages
        self.pos = 0              # next write position once decoding
        self.sent = 0             # output tokens dispatched (>= emitted)
        self.last_token: int | None = None


class DecodeSlots:
    """The decode-slot state machine (ARCHITECTURE §Serving prefill/
    decode): a fixed number of slots — the decode step's batch rows —
    each FREE, PREFILLING (start < prompt_len) or DECODING (prompt in
    cache, output budget unspent). Admission binds a free slot to a
    request (the caller reserves its pages first); `next_prefill` picks
    the OLDEST prefilling slot so the engine interleaves exactly one
    prompt chunk between decode steps; completion frees the slot and
    reports the pages to release. Pure bookkeeping — no locks, no
    device state — owned by one engine worker thread."""

    def __init__(self, n_slots: int):
        if n_slots < 1:
            raise ValueError(f"need n_slots >= 1, got {n_slots}")
        self.slots: list = [None] * int(n_slots)

    @property
    def n_slots(self) -> int:
        return len(self.slots)

    def free_index(self) -> int | None:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    def admit(self, index: int, request: GenRequest, pages: int) -> "_Slot":
        if self.slots[index] is not None:
            raise ValueError(f"slot {index} is occupied")
        slot = _Slot(request, pages)
        self.slots[index] = slot
        return slot

    def next_prefill(self) -> int | None:
        """Index of the oldest slot still prefilling (FIFO by admission
        time), or None."""
        best, best_t = None, None
        for i, s in enumerate(self.slots):
            if s is None or s.start >= s.request.prompt_len:
                continue
            if best_t is None or s.request.t_admitted < best_t:
                best, best_t = i, s.request.t_admitted
        return best

    def decoding(self) -> list:
        """Indices of slots with their whole prompt in cache and output
        budget left — the decode step's active rows. The budget is
        counted in tokens DISPATCHED (`sent`): completion is by count
        alone, so the rows of the next step are known before the last
        step's tokens are."""
        return [i for i, s in enumerate(self.slots)
                if s is not None and s.start >= s.request.prompt_len
                and s.sent < s.request.max_new_tokens]

    def busy(self) -> bool:
        return any(s is not None for s in self.slots)

    def release(self, index: int) -> int:
        """Free a slot; returns the pages to hand back to the pool."""
        slot = self.slots[index]
        if slot is None:
            raise ValueError(f"slot {index} is already free")
        self.slots[index] = None
        return slot.pages


class Batcher:
    """The live threaded coalescer around `plan_batch`/`assemble`.

    One producer side (`submit`, called from HTTP handler threads) and
    one consumer side (`next_batch`, called by the engine's dispatcher).
    `clock` is injectable for tests; the default is time.monotonic."""

    def __init__(self, lattice: BucketLattice, max_wait_ms: float = 5.0,
                 *, sequence: bool = False, clock=time.monotonic,
                 recorder=None):
        self.lattice = lattice
        self.max_wait_s = float(max_wait_ms) / 1000.0
        self.sequence = sequence
        self._clock = clock
        self._recorder = recorder
        self._pending: deque[PendingRequest] = deque()
        self._cv = threading.Condition()
        self._closed = False

    # ------------------------------------------------------------ producer
    def submit(self, features, mask=None,
               request_id: str | None = None) -> PendingRequest:
        """Admit one request. Validates the shape against the lattice
        up front (a too-long prompt is the CLIENT's 400, not a retrace
        or a mid-batch crash) and wakes the dispatcher."""
        feats = np.asarray(features)
        if self.sequence:
            if feats.ndim < 1:
                raise ValueError("sequence request needs at least a "
                                 "[T] feature array")
            self.lattice.seq_bucket(int(feats.shape[0]))  # raises if too long
        req = PendingRequest(
            features=feats,
            mask=None if mask is None else np.asarray(mask),
            request_id=request_id or f"r{next(_req_counter)}",
            t_enqueue=self._clock())
        with self._cv:
            if self._closed:
                raise RuntimeError("batcher is draining; request refused")
            self._pending.append(req)
            self._cv.notify_all()
        return req

    # ------------------------------------------------------------ consumer
    def next_batch(self, timeout: float | None = None):
        """Block until a batch cuts (full bucket / deadline / drain
        flush). Returns None when draining finished (closed and empty)
        or `timeout` elapsed with nothing to cut."""
        deadline = None if timeout is None else self._clock() + timeout
        with self._cv:
            while True:
                now = self._clock()
                take = plan_batch(self._pending, now, self.max_wait_s,
                                  self.lattice, sequence=self.sequence,
                                  closed=self._closed)
                if take:
                    group = [self._pending.popleft() for _ in range(take)]
                    break
                if self._closed:
                    return None
                waits = []
                if self._pending:
                    waits.append(self._pending[0].t_enqueue
                                 + self.max_wait_s - now)
                if deadline is not None:
                    remaining = deadline - now
                    if remaining <= 0:
                        return None
                    waits.append(remaining)
                # bounded wait: re-plan on submit()/close() notify or when
                # the head request's deadline arrives
                self._cv.wait(timeout=max(min(waits), 0.0005)
                              if waits else None)
        t0 = time.perf_counter()
        batch = assemble(group, self.lattice, sequence=self.sequence)
        batch.t_cut = self._clock()
        batch.assemble_seconds = time.perf_counter() - t0
        for r in group:
            r.t_assembled = batch.t_cut
        if self._recorder is not None:
            # span names documented in telemetry/recorder.py: `queue` is
            # the head request's wait (the latency the deadline bounds),
            # `batch_assemble` the host-side padding cost. The cut roots
            # a TRACE: queue -> batch_assemble here, then the replica
            # thread's forward/compile/request events join the tree
            # through the Batch's correlation handoff fields.
            rec = self._recorder
            batch.trace_id = f"b{next(_req_counter)}"
            q_sid = rec.new_span_id()
            a_sid = rec.new_span_id()
            batch.parent_span = a_sid
            rec.event(
                "span", name="queue", ok=True,
                seconds=round(batch.t_cut - group[0].t_enqueue, 6),
                n_requests=len(group), trace_id=batch.trace_id,
                span_id=q_sid)
            rec.event(
                "span", name="batch_assemble", ok=True,
                seconds=round(batch.assemble_seconds, 6),
                bucket=list(batch.bucket.key()), n_real=batch.n_real,
                trace_id=batch.trace_id, span_id=a_sid, parent_id=q_sid)
        return batch

    def requeue(self, requests) -> None:
        """Put already-admitted requests BACK at the FIFO head — the
        dead-replica queue drain (serving/fleet.py): batches a reaped
        replica never ran dissolve back into pending requests, keeping
        their original enqueue times (their queue-wait telemetry stays
        honest), and live replicas pick them up on the next cut. Works
        even while draining: these requests were admitted before the
        close and the drain flush owes them a completion."""
        with self._cv:
            for r in reversed(list(requests)):
                self._pending.appendleft(r)
            self._cv.notify_all()

    # ------------------------------------------------------------- drain
    def close(self) -> None:
        """Begin draining: refuse new submits, flush pending groups on
        the next next_batch() calls (which return None once empty)."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    @property
    def depth(self) -> int:
        with self._cv:
            return len(self._pending)
