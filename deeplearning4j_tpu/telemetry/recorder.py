"""Structured run telemetry — typed JSONL events for every phase a
training or bench run passes through.

VERDICT r5 demonstrated what the repo loses without this: the round
artifact's gate fields vanished to the driver's 2000-byte tail
truncation, the `transformer_large` traceback was unrecoverable, and the
DP-speedup swing had no spread data to diagnose it. The reference stack
has no tracing at all (SURVEY §5); this module is the TPU build's
equivalent of the per-phase characterization methodology of
Awan et al. (arXiv:1810.11112) — record every phase, keep distributions,
never let a crash or a truncation destroy the evidence.

Event schema — one JSON object per line, every event carrying
``{"event": <type>, "ts": <unix seconds>, "run": <run id>, "seq": <n>}``:

| event    | payload |
|---|---|
| `meta`   | run header: argv, platform, pid, free-form fields |
| `step`   | per-iteration training metrics: `iteration`, `score`, throughput fields (fed by `TelemetryListener` without hot-path host syncs) |
| `span`   | a timed region: `name` ("compile", "step", "mode:vgg16", ...), `seconds` wall-clock, `ok`, `t0` / `t1` (`time.perf_counter()` at entry and exit: the process's monotonic clock, the one a profiler trace is laid over; `ts` is the wall clock at the span's END, rounded to 1 ms), caller fields |
| `metric` | a bench metric line verbatim (same dict `bench._emit` prints) |
| `eval`   | evaluation results (accuracy/f1/stats dict) |
| `memory` | device-memory snapshot: `live_array_bytes`, `live_array_count`, per-device `memory_stats` when the backend exposes them (`bytes_in_use`, `peak_bytes_in_use`, `bytes_limit`; CPU backends return None — live-array accounting only). Ledger-attributed snapshots (telemetry/memstat.py) additionally carry `ledger` (per-subsystem `{params, opt_state, kv_pages, prefetch, activations, other}` byte map summing to `ledger_total_bytes`) and `source` ("fit" / "stats_tick" / "sampler") — emitted strictly at batch boundaries or on the sampler thread, never inside a jitted region (G029) |
| `error`  | `where`, `error` (repr), `traceback` (FULL string — never truncated at the source) |
| `fault`  | fault-injection / elastic-recovery record: `kind` (an injected fault kind from distributed/faults.py or a launcher exit class), `process_id`, `step`, free-form fields — written BEFORE the fault acts, so even a SIGKILL leaves its line |
| `bucket_plan` | the DP-overlap bucket schedule a net was configured with (parallel/placement.py): `axis`, `n_buckets`, `bucket_bytes`, `mode`, per-bucket `{index, n_leaves, bytes}` — the per-rank collective sequence on the record before any step runs; the bench's per-bucket micro-timings ride `span` events named `bucket_reduce` (`bucket`, `bytes`, `n_leaves`, `seconds`) |
| `kernel_tune` | one kernel-autotune micro-bench measurement (tools/kerneltune.py): `kernel`, `key` (the ops/autotune.py config key), `params` (the candidate block sizes), `seconds` (per-call wall clock), `role` ("default" / "candidate" / "chosen"), free-form fields — the provenance trail behind every tuning_table.json entry |
| `request` | one served inference request (serving/engine.py): `id`, `ok`, `bucket` ([batch, seq]), `replica`, `queue_s` (enqueue -> batch cut), `batch_assemble_s` (host-side padding), `forward_s` (jitted forward incl. batch-boundary fetch), `total_s` (enqueue -> result), `seq_len`/`padded_seq` for sequence models, `weight_gen` (the published weight generation the batch served against — serving/fleet.py), `error` on a failed batch — the ONLY record serving/replay.py reconstructs p50/p99/QPS from. Generation requests carry `kind: "generate"` plus `prompt_len`, `prompt_bucket`, `new_tokens`, and `ttft_s` (enqueue -> first token, i.e. the prefill's final chunk) — the rows tokens/sec and TTFT percentiles reconstruct from |
| `page_pool` | KV-cache page accounting snapshot (serving/kvcache.py), emitted on every reserve/release: `replica`, `pages_total`, `page_size`, `pages_in_use`, `pages_peak` — the cache-occupancy headline's only source |
| `admit` | one generation request bound to a decode slot (serving/engine.py `_admit`): `id`, `slot`, `replica`, `queue_s` (enqueue -> admission) — with it a request's decode steps are the `decode_step` spans whose `slots` hold its slot between this event and its `request` event: queue, prefill chunks, decode steps and stream of one request join on one id with no per-token field |
| `stream` | one `/generate` response as the front door wrote it (serving/server.py), emitted once at the request's end by the handler thread: `id`, `n` (token lines written), `lag_s` (per token: the line flushed, on the engine's clock, minus the engine's stamp of the step that produced it — the per-slot `queue.put`, the handler thread's wake-up, the JSON line and the socket flush), `parse_s` (body read + JSON parse before `submit_generate`) |
| `draft` | one speculative verify step's draft accounting (serving/engine.py): `replica`, `k` (window width), `n_active`, `emitted` (tokens emitted this step across slots), `accepted` (accepted drafts = emitted minus the per-slot bonus token), `drafted` ((k-1) * n_active proposals offered), `overhead_us` (host-side proposer wall clock) — the `accepted_tokens_per_step` and `draft_overhead_us` bench rows reconstruct from exactly these |
| `reshard_plan` | a portable-resharding plan (reshard/) put on the record BEFORE any transfer: `path` ("live" / "checkpoint"), `src`/`dst` placement descriptions, `n_leaves`, per-action counts, `bytes_total`, `bytes_moved`, `bytes_lower_bound`; the transfer itself runs inside a `span` named `reshard` carrying the same byte fields |
| `placement_search` | one automatic-placement-search run (reshard/search.py) put on the record BEFORE any mesh is built: `path` ("cli" = the `plan` dry-run, "elastic" = a worker's per-generation re-plan, "reform" = the supervisor's pre-relaunch search, "bench" = the placement_search bench), `fleet` ("2x4"), `profile`, `candidates_considered` / `candidates_feasible` / `pruned`, `winner` (the placement description), the winner's score breakdown (`winner_score`, `winner_memory_bytes`, `winner_collective_bytes`, `winner_bubble_cost`, `winner_idle_cost`), and `search_ms` — the elastic timeline test asserts one per worker per generation |
| `host_gather` | a full-value host materialization of genuinely SHARDED leaves (util/orbax_checkpoint.host_materialize): `n_leaves`, `bytes` — resharded restore paths must show ZERO of these (asserted by the elastic timeline test) |
| `weight_swap` | one live hot-swap attempt (serving/fleet.hot_swap): `ok`, `step` (the checkpoint step restored), `restore_ms` (shadow-net restore + validation, all OFF the request path), `generation` (the WeightStore generation after a flip / still serving after a rejection), `error` on rejection — paired with the `weight_gen` field every serving `request` event carries, the flip's visibility in the traffic record |
| `autoscale` | one fleet-supervisor autoscale tick (serving/fleet.FleetSupervisor): `n_serving`, `n_replicas`, `queue_depth`, `p99_ms` (the decision inputs), `action` (+1 grew / -1 drained / 0), `max_replicas` — the occupancy bench row's only source; replica self-healing rides `fault` events (`replica-kill`/`replica-hang` when an injected fault fires, `replica-dead` with the requeued count when the supervisor reaps, `replica-respawn` with `respawn_ms` on re-admission) |
| `anomaly` | one detector finding (telemetry/trace.py) put on the record by whoever ran the detector — the elastic supervisor's straggler watch, `tracetool check`, or the bench sweep: `kind` ("straggler" / "retrace" / "input_wait_spike" / "queue_spike" / "leak" / "headroom" / "cost_drift"), `process`, and the kind's evidence fields (`step`+`skew_ms` for stragglers, the offending span's name/seconds for retraces and spikes, byte counts + growth/ratio fields for the memory kinds) |
| `cost` | one compiled executable's cost-book entry (telemetry/costbook.py), harvested at warmup/compile time from XLA's own `cost_analysis()` / `memory_analysis()` — NEVER on the hot path (it rides the existing `compile` spans): `entry` (the jit wrapper's name: "forward", "prefill", "decode", "verify", "fit_scanned", ...), `shape` (the warmed shape key), `flops`, `bytes_accessed`, `peak_temp_bytes`, `argument_bytes`, `output_bytes`, `alias_bytes` (argument bytes the outputs reuse in place: at least the KV cache's bytes for the serving steps, which donate it; 0 for an executable that donates nothing), `generated_code_bytes` — the denominators behind the MFU gauge and the capacity planner's measured-cost side |
| `regions` | one compiled executable's map from instruction to region (telemetry/costbook.py, beside its `cost` event, from the same warm-up compile): `entry`, `shape` (as on `cost`), `module` (the HLO module's name, the one a profiler trace's `XLA Modules` line names the program by), `ops` ({instruction name: "top" or "top/child" of `REGIONS`} for every instruction outside a fusion's body that some region holds, by its `op_name` metadata; an instruction the compiler made with no scope takes that of the instruction it feeds) — a profiler trace's `XLA Ops` event names the instruction only, so this table is what lays a program's device time out by region |
| `cost_drift` | one predicted-vs-measured reconciliation of the placement cost model (reshard/search.py `winner_memory_bytes` vs a measured per-device peak from later `memory`/`cost` events): `predicted_bytes`, `measured_bytes`, `ratio` (measured/predicted), `factor` (the documented tolerance band — outside [1/factor, factor] is an anomaly), `source` — emitted once after the first real step, the calibration loop closing over the search's exact-rational predictions |

**Correlation fields** (the fleet-timeline contract, tools/tracetool.py):
every event MAY carry `trace_id` / `span_id` / `parent_id`. `span()`
allocates a fresh `span_id` per region and stamps `parent_id` from the
thread-local span stack, so nested spans (`forward` → `compile`) become
real trees without caller plumbing; `trace(trace_id, parent_id=...)`
installs a thread-local trace context so work handed across threads
(batch cut on the dispatcher, forward on a replica) stays one tree —
the serving batcher roots a trace per cut batch (`queue` →
`batch_assemble` → `forward`/`request`), generation requests trace by
their request id, and `step` events carry `trace_id: "step-<n>"` so the
SAME global step correlates across fleet processes by id join. Events
emitted outside any context carry no correlation fields (the process
run id is the implicit root).

**Registered schema** (graftlint G023): `EVENT_KINDS` and `SPAN_NAMES`
below are the ONLY event kinds / span names code outside `telemetry/`
may emit as string literals, and `REGION_NAMES` the only regions it may
name in a `jax.named_scope` or a layer impl's `region` — an unknown
literal is a lint finding, so
the fleet-timeline tooling (merge, stats, anomaly detection, Perfetto
export) never meets a name it cannot classify. Dynamic names
(f-strings like the bench sweep's `mode:<name>` spans) are exempt from
the static check and parse as opaque spans.

Generation serving adds three hot-loop span names: `prefill_chunk` (one
bucket-shaped prompt chunk — `bucket`, `start`, `final`, `replica`,
`n_real` = the prompt tokens in the bucket, the rest is padding),
`decode_step` (one fixed-shape step over every decode slot — `replica`,
`n_active`, `slots` = the active slot indices), and `verify_step`
(one fixed-shape speculative verification over every slot's k-token
draft window — `replica`, `n_active`, `k`, `slots`; it REPLACES
decode_step when the engine runs with
`speculative_k >= 2`, and each one pairs with a `draft` event carrying
the acceptance accounting); their first execution per shape nests a
`compile` span exactly like the predict path, and the
flat-across-prompt-buckets property of the decode_step timings is the
"decode cost independent of prompt length" gate in tier-1. Where the
served net has a counting layer (the dropless expert layer,
nn/layers/moe.py `DroplessMoELayer`), all three carry what a step's
program counted, handed home behind the tokens in the one fetch (a
plain step's span carries the counters of the program it `fetched`, the
one before its own: the host loop's paragraph below):
`moe_pairs` (the (token, held selected expert) pairs the step had to
compute, over all expert layers; the pad of a bucket and idle slots
select nothing), `moe_rows` (the expert rows it did compute for them:
rounds x held experts x rows a round, padding included) and
`moe_max_load` (the most pairs on one held expert in one layer). The
engine's `meta` event (`cache`) and /stats give, from the attention
layers' own cache specs, `rows` ({kind of cache row: bytes a token over
all layers}: "k" and "v", or "ckv" and "kpe" for a latent row) and
`bytes_per_token`, and for the layers whose entry is a state `states`
({kind of state: bytes a slot over all layers}: "s" and "z" of a
retention layer) and `state_bytes_per_slot`. A net with such a layer
also puts `state_resets` on its steps' spans: the rows whose state the
step zeroed because they start a sequence (a request's first
`prefill_chunk` counts 1, so the window's sum is the requests admitted;
a `decode_step` counts 0; read on the span that `fetched` the program).
A net with grouped-attention layers (nn/layers/grouped_attention.py)
puts `attn_rows_seen` (the cache rows some query of the step could see,
over all its layers: a window layer's are the `window` newest at the
most), `attn_wrapped` (the live rows of the step whose context is
past the window) and `attn_write_wraps` (the rows of a prefill chunk
whose written run passed the end of a layer's entry, so that its write
took the second of its two blocks: a window layer's ring wraps, a full
layer says 0; a decode step counts 0) on the same spans, before the
`moe_*` three, and its
`cache` gives `windows` ({kind of row: rows a slot} for the kinds that
are rings: "k_win", "v_win") and `bytes_per_slot` (what a slot's cache
is allocated: every kind of row times the positions it holds, plus the
states) beside `rows`, whose sum `bytes_per_token` is what a token
costs while every layer still holds it.

The generation engine's `meta` event, /stats and each worker's
`describe()` also say what the engine serves FROM (serving/engine.py
`_weights_facts`; the store holds the net's parameters in its compute
dtype, cast once when it is built: nn/decode.serving_params):

| field | what it is |
|---|---|
| `weights_dtype` | the floating dtype of the served leaves ("bfloat16" for a net stored in float32 that computes in bfloat16; several joined by "+" where they differ) |
| `weights_bytes` | the served set's bytes on the device (what the `params` entry of the memory ledger reads; the caller's own `net.params` are not in it) |
| `weights_cast_leaves` | the leaves whose dtype the engine changed when it built the store: every floating leaf where `compute_dtype != param_dtype`, 0 where they are equal (the store then holds the net's own arrays, no copy) |

The engine thread's host loop (serving/engine.py `_GenWorker`) is named
whole: every instant between two model steps lies in one of six LEAF
spans, so a device idle gap laid over them names what the host was
doing. THE LOOP RUNS ONE PROGRAM AHEAD (PR 36): a program (a prompt
chunk or a decode step) is fetched and emitted only after the next one
has been dispatched, so a plain step's span holds parts of TWO
programs, and says which. Per pass of the loop: `admit` (one admission
pass under the queue lock — `admitted`, `pending` = left waiting,
`blocked` = "slots" / "pages" / null, why the head of the queue
stayed), then per program `step_prepare` (the numpy build of the
step's arguments — `kind` "prefill" / "decode" / "verify"), then the
`prefill_chunk` / `decode_step` span of the program being LAUNCHED
(`program` = its sequence number on this replica; `n_active`, `slots`,
`bucket`, `start`, `n_real` are ITS rows and positions, as
the host counts them at dispatch; `ahead` = true when it was dispatched
while the program before it was un-retired, which in a busy server is
every step but the first after an `idle_wait`) with two children:
`dispatch` (of the launched program, until the jit call returns:
argument flattening and the enqueue) and `fetch` (of the program BEFORE
it, `fetched` = that program's number: the one batch-boundary
`np.asarray`, which waits for the device only as long as that earlier
program still runs; the counters behind its tokens — `moe_pairs`,
`moe_rows`, `moe_max_load`, `state_resets`, `attn_rows_seen`,
`attn_wrapped`, `attn_write_wraps` — land on the span under
which they came home, so a step's span carries the counters of program
`fetched`, not of `program`; a step with `ahead` false has no `fetch`
and no counters), and after the span `emit` of that earlier program
(`program` = its number; from the fetch's return to its last
completion: the per-slot stream puts, completions, `request` events,
the release of its device outputs — `tokens`, `release_s` = the part
spent dropping the fetched device arrays). So a step's span is no
longer "dispatch to ITS fetched tokens" but one pass of the loop's
device-facing part, and consecutive step spans still do not overlap:
the next `t0` less this `t1` is the host's time between two steps.
The last program of a busy spell is retired in a pass that dispatches
nothing: a `fetch` (`replica`, `fetched`, its counters) and an `emit`
that are children of no step. Or, with nothing to run and nothing in
flight, `idle_wait` (the `_cv.wait`: an idle device under it has an
idle engine, not a slow one). A `verify_step` (speculative mode) does
not run ahead: its span holds its own `dispatch` and `fetch`, as does
the span of a chunk on such a worker (`fetched` equals `program`).
`admit`, `step_prepare`, `fetch`, `emit` and `idle_wait` are opened
with `follows=True`: each starts where the region before it ended, so
the recorder's own emission lies inside a named leaf; the model step's
span and its `dispatch` keep their own start, just before the jit call.
`GenerationEngine.stats()` (`steps_ahead`, and per worker in `fleet`)
counts the programs dispatched `ahead`.

The input pipeline (data/pipeline.py) names an ``input_wait`` span
around EVERY batch dequeue in the fit loops: `pipelined` (false = the
synchronous fallback, where the span covers the whole host conversion +
device put — the stall it measures IS the input path) and `buffered`
(post-dequeue queue occupancy, pipelined only). Steady-state p99 of the
pipelined spans ~= 0 on a compute-bound workload is the starve-proof
gate the bench's `input_pipeline` mode records.

Serving also names three `span` events per batch: `queue` (the head
request's wait — what the batcher's max-wait deadline bounds),
`batch_assemble` (padding into the bucket), and `forward` (the jit call;
its FIRST execution per bucket shape nests a span named `compile`, so
the warmed compile count is reconstructable from telemetry alone — the
zero-retrace gate in tests/test_serving.py counts exactly these).

The embedding engine (embedding/) names three spans with explicit
byte accounting, surfaced in the trace timeline and the Prometheus
/metrics endpoint: `gather` (one sparse-gather embed lookup — `rows`,
`ep`, `bytes` = index + row traffic), `scatter_add` (one train step's
sparse (indices, values) update — `step` ("sgns"/"hs"), `rows`,
`bytes` = the COO pair's wire bytes, `ep`, `ep_gather_bytes` = the
forward gather's cross-rank row traffic at the ep axis), and
`ann_probe` (one batched partition-then-refine ANN lookup — `queries`,
`k`, `nprobe`, `bytes` = the probed partitions' candidate rows).

The file format is append-only JSONL so concurrent writers (bench runs
every mode in a subprocess) can share one log: each process appends
whole lines to the path named by the ``DL4J_TPU_TELEMETRY`` env var.

jax is imported lazily (only `memory()` needs it) so the module stays
importable under the graftlint AST stage's no-jax package stubs and adds
nothing to tools' startup.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys
import threading
import time
import traceback as _tb
from collections import deque

ENV_VAR = "DL4J_TPU_TELEMETRY"

# ------------------------------------------------------ registered schema
# The closed set of event kinds and span names the package emits —
# graftlint G023 holds every string-literal `event("...")`/`span("...")`
# outside telemetry/ to these sets, so the fleet-timeline tooling
# (telemetry/trace.py) can classify every record it merges. New kinds
# and names are REGISTERED HERE first, alongside their docstring row.
EVENT_KINDS = frozenset({
    "meta", "step", "span", "metric", "eval", "memory", "error", "fault",
    "bucket_plan", "kernel_tune", "request", "page_pool", "draft",
    "admit", "stream", "reshard_plan",
    "placement_search", "host_gather", "weight_swap", "autoscale",
    "anomaly", "cost", "cost_drift", "regions",
})

SPAN_NAMES = frozenset({
    # compile/step spine (nn/, bench)
    "compile", "step_scan", "profiler_trace",
    # serving batch pipeline (serving/batcher.py, engine.py)
    "queue", "batch_assemble", "forward", "prefill_chunk", "decode_step",
    "verify_step", "drain",
    # the generation engine's host loop, leaf spans (serving/engine.py)
    "admit", "step_prepare", "dispatch", "fetch", "emit", "idle_wait",
    # input pipeline (data/pipeline.py)
    "input_wait",
    # resharding + placement (reshard/)
    "reshard",
    # distributed runtime + elastic recovery (distributed/)
    "distributed_init", "distributed_launch", "elastic_generation",
    "elastic_resume",
    # bench harness (bench.py)
    "bucket_reduce", "bucket_reduce_capped", "overlap_sweep", "ab_repeat",
    # embedding engine + ANN serving (embedding/)
    "gather", "scatter_add", "ann_probe",
})

# The regions inside a compiled program: `jax.named_scope(<region>)` around
# each layer's ops (nn/decode._walk, nn/graph._forward: the layer impl's
# `region`), the engine's argmax, the train step's loss and update, and a
# loop's own operations (its pass counter and carry: `loop`; the layers
# inside its body keep their own regions, the innermost naming an op). A
# region is HLO metadata (`op_name`) and nothing else: the compiled
# instructions are the same with and without it. {top-level region: its
# child regions}; `cost`'s sibling event `regions` maps each instruction
# of a compiled program to "top" or "top/child" (telemetry/costbook.py),
# and an instruction under none reads as `other`. graftlint G023 holds
# every scope literal outside telemetry/ to REGION_NAMES.
REGIONS = {
    "embed": (), "norm": (), "attention": ("cache_write",),
    "moe": ("router", "experts", "shared_expert"), "ffn": (), "head": (),
    "loss": (), "optimizer": (), "loop": (),
}
REGION_NAMES = frozenset(REGIONS) | frozenset(
    child for children in REGIONS.values() for child in children)

# Ring-buffer length for the in-memory mirror of emitted events; large
# enough for a full bench sweep, bounded so a long fit() can't grow RSS.
DEFAULT_KEEP = 4096


class Recorder:
    """Appends typed JSONL events to a per-run file (and an in-memory
    ring buffer, inspectable as `.events`). `path=None` records in
    memory only — the unit-test and interactive mode."""

    # False on the NullRecorder: a call site asks before it builds fields
    # nobody keeps (`page_pool`'s `describe()`, the per-token stream lags)
    live = True

    def __init__(self, path: str | None = None, run_id: str | None = None,
                 keep: int = DEFAULT_KEEP):
        self.path = path
        self.run_id = run_id or f"{os.getpid():x}-{int(time.time()):x}"
        self.events: deque[dict] = deque(maxlen=keep)
        # serializes seq assignment, the ring buffer and the file
        # handle: replica/dispatcher/supervisor threads all emit
        # through one Recorder. Sinks fan out OUTSIDE the lock — a
        # sink that takes its own lock (the /metrics registry) must
        # never run under this one (D002 sink reentrancy).
        self._lock = threading.Lock()
        self._seq = 0
        self._span_seq = 0
        self._fh: io.TextIOBase | None = None
        # thread-local correlation context: the current trace id and the
        # open-span stack (span_id of each enclosing `span()` region on
        # THIS thread) — cross-thread handoff goes through `trace()`
        self._tloc = threading.local()
        # live event sinks (the /metrics registry subscribes here); a
        # sink failure never poisons the recording path
        self._sinks: list = []

    # ------------------------------------------------- correlation context
    def _stack(self) -> list:
        stack = getattr(self._tloc, "stack", None)
        if stack is None:
            stack = self._tloc.stack = []
        return stack

    def new_span_id(self) -> str:
        """A process-unique span id (unique within this run; merged
        timelines key spans by (process, span_id))."""
        with self._lock:
            self._span_seq += 1
            return f"s{self._span_seq:x}"

    @contextlib.contextmanager
    def trace(self, trace_id: str | None, parent_id: str | None = None):
        """Install a trace context on THIS thread: events emitted inside
        carry `trace_id` (and `parent_id` from the span stack —
        `parent_id` here seeds the stack with a foreign span, the
        cross-thread handoff: the batcher's `batch_assemble` span parents
        the replica thread's `forward`). `trace_id=None` is a no-op so
        un-traced callers (warmup batches) need no branching."""
        if trace_id is None:
            yield
            return
        prev = getattr(self._tloc, "trace_id", None)
        self._tloc.trace_id = trace_id
        stack = self._stack()
        pushed = parent_id is not None
        if pushed:
            stack.append(parent_id)
        try:
            yield
        finally:
            if pushed and stack and stack[-1] == parent_id:
                stack.pop()
            self._tloc.trace_id = prev

    def add_sink(self, fn) -> None:
        """Subscribe a live event callback (called with each emitted
        event dict, on the emitting thread). The /metrics registry feeds
        its rolling histograms through one of these."""
        with self._lock:
            self._sinks.append(fn)

    # ------------------------------------------------------------- core
    # `kind` is positional-only so a payload field may itself be named
    # "kind" (the `fault` events carry one)
    def event(self, kind: str, /, **fields) -> dict:
        rec = {"event": kind, "ts": round(time.time(), 3),
               "run": self.run_id}
        # ambient correlation: an active trace()/span() context stamps
        # its ids unless the caller passed explicit ones
        trace_id = getattr(self._tloc, "trace_id", None)
        if trace_id is not None and "trace_id" not in fields:
            rec["trace_id"] = trace_id
        stack = getattr(self._tloc, "stack", None)
        if stack and "parent_id" not in fields and "span_id" not in fields:
            rec["parent_id"] = stack[-1]
        rec.update(fields)
        with self._lock:
            rec["seq"] = self._seq
            self._seq += 1
            self.events.append(rec)
            self._write(rec)
            sinks = list(self._sinks)
        # fan out AFTER releasing: a sink acquiring its own lock (the
        # /metrics histogram update) must not run under `_lock`
        _fan_out(sinks, rec)
        return rec

    def _write(self, rec: dict) -> None:
        # caller holds `_lock` — seq order on disk matches assignment
        if self.path is None:
            return
        if self._fh is None:
            self._fh = open(self.path, "a")
        # one whole line per write: O_APPEND keeps concurrent bench
        # subprocesses' lines intact in the shared log
        self._fh.write(json.dumps(rec, default=_jsonable) + "\n")
        self._fh.flush()

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None

    # ------------------------------------------------------ typed events
    def meta(self, **fields) -> dict:
        fields.setdefault("argv", list(sys.argv))
        fields.setdefault("pid", os.getpid())
        return self.event("meta", **fields)

    def step(self, iteration: int, score=None, **fields) -> dict:
        if score is not None:
            fields["score"] = float(score)
        # the cross-process correlation key: every fleet member's step N
        # carries the same trace id, so the merged timeline joins step
        # completions by id (the straggler detector's input)
        fields.setdefault("trace_id", f"step-{int(iteration)}")
        return self.event("step", iteration=int(iteration), **fields)

    def metric(self, line: dict) -> dict:
        """Record a bench metric line verbatim (flattened into the event
        so artifact parsers treat telemetry logs and bench stdout
        uniformly — any dict with a `metric` key is a metric line)."""
        return self.event("metric", **line)

    def eval(self, stats, **fields) -> dict:
        if not isinstance(stats, dict):
            # Evaluation-like object: take its scalar summary methods
            # (best-effort — a half-filled Evaluation must not crash the
            # recording path)
            summary = {}
            for name in ("accuracy", "precision", "recall", "f1"):
                fn = getattr(stats, name, None)
                if callable(fn):
                    try:
                        summary[name] = float(fn())
                    except Exception:
                        pass
            stats = summary
        return self.event("eval", stats=stats, **fields)

    def error(self, where: str, exc: BaseException | None = None,
              traceback_str: str | None = None, **fields) -> dict:
        """An `error` event carries the FULL traceback string — the
        telemetry log is the truncation-proof home for what the driver's
        2000-byte stdout tail destroys (VERDICT r5 #1)."""
        if traceback_str is None and exc is not None:
            traceback_str = "".join(_tb.format_exception(
                type(exc), exc, exc.__traceback__))
        return self.event(
            "error", where=where,
            error=repr(exc) if exc is not None else fields.pop("error", ""),
            traceback=traceback_str or "", **fields)

    def fault(self, kind: str, **fields) -> dict:
        """A `fault` event: an injected failure firing
        (distributed/faults.py), a launcher exit classification, or an
        elastic-recovery lifecycle record. Emitted BEFORE the fault acts
        (`_write` flushes per line) so the full fault→recovery timeline
        is reconstructable from the JSONL even across SIGKILLs."""
        return self.event("fault", kind=kind, **fields)

    def anomaly(self, kind: str, **fields) -> dict:
        """An `anomaly` event: one detector finding (telemetry/trace.py)
        put on the record live — the elastic supervisor's straggler
        watch emits these on its heartbeat path so a skewing fleet is
        visible in the journal BEFORE the generation dies."""
        return self.event("anomaly", kind=kind, **fields)

    def kernel_tune(self, kernel: str, key: str, params: dict,
                    seconds: float | None = None, role: str = "candidate",
                    **fields) -> dict:
        """A `kernel_tune` event: one micro-bench measurement of a
        kernel block-size variant (tools/kerneltune.py). The telemetry
        log is the provenance trail behind tuning_table.json — every
        candidate's timing survives even if the sweep crashes before
        writing the table."""
        if seconds is not None:
            fields["seconds"] = round(float(seconds), 9)
        return self.event("kernel_tune", kernel=kernel, key=key,
                          params=dict(params), role=role, **fields)

    def request(self, request_id: str, *, ok: bool = True,
                **fields) -> dict:
        """A `request` event: one served inference request with its
        queue/batch_assemble/forward span breakdown
        (serving/engine.py). The traffic-replay bench reconstructs
        p50/p99 latency and sustained QPS from these events ALONE — the
        telemetry log, not in-process timers, is the serving
        scoreboard's source of truth."""
        return self.event("request", id=request_id, ok=bool(ok), **fields)

    def memory(self, **fields) -> dict:
        """Device-memory snapshot: bytes held by live jax arrays plus
        the backend's own memory_stats when exposed (TPU HBM; CPU
        backends return None). Costs a host-side walk only — no device
        sync — so it is safe between steps."""
        import jax

        live_bytes = 0
        count = 0
        for arr in jax.live_arrays():
            live_bytes += getattr(arr, "nbytes", 0) or 0
            count += 1
        devices = {}
        for dev in jax.local_devices():
            try:
                stats = dev.memory_stats()
            except Exception:
                stats = None
            if stats:
                devices[str(dev.id)] = {
                    k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                                          "bytes_limit") if k in stats}
        return self.event("memory", live_array_bytes=int(live_bytes),
                          live_array_count=count, devices=devices, **fields)

    def cost(self, entry: str, shape, **fields) -> dict:
        """A `cost` event: one warmed executable's XLA cost book entry
        (telemetry/costbook.py harvests flops / bytes accessed / peak
        temp at compile time — zero hot-path cost)."""
        return self.event("cost", entry=entry, shape=shape, **fields)

    def cost_drift(self, *, predicted_bytes: int, measured_bytes: int,
                   factor: float, source: str = "placement",
                   **fields) -> dict:
        """A `cost_drift` event: the placement cost model's predicted
        per-device memory reconciled against a measured peak. `ratio`
        (measured/predicted) outside [1/factor, factor] is the
        detector's trigger."""
        predicted = max(1, int(predicted_bytes))
        ratio = float(measured_bytes) / float(predicted)
        return self.event("cost_drift",
                          predicted_bytes=int(predicted_bytes),
                          measured_bytes=int(measured_bytes),
                          ratio=round(ratio, 6), factor=float(factor),
                          source=source, **fields)

    # -------------------------------------------------------------- spans
    def span(self, name: str, *, follows: bool = False,
             **fields) -> "_Span":
        """Time a region: `with rec.span("compile"): ...` emits a `span`
        event on exit with wall-clock `seconds` and the region's two
        ends `t0` / `t1` on `time.perf_counter()`. The yielded dict can
        be mutated to attach result fields. An exception inside the span
        emits an `error` event (full traceback) plus the span with
        `ok: false`, then re-raises.

        `follows=True` starts the region where the last region closed on
        this thread ended, not at the `with` statement: for a loop whose
        passes are tiled by spans (the serving engine's), so that what
        lies between two of them — the recorder's own emission of the
        first, the loop's glue — is counted into the second and no
        instant of the thread is left unnamed.

        Correlation: the region gets a fresh `span_id`, its `parent_id`
        is the enclosing open span on this thread (or the foreign parent
        a `trace()` context seeded), and events emitted INSIDE the
        region — nested spans, errors, page_pool snapshots — parent to
        it automatically.

        Where jax is loaded the region is also a
        `jax.profiler.TraceAnnotation(name)`: while a profiler trace
        runs, the program's spans lie in the trace's own host plane
        beside `PjitFunction(...)`, over the device's ops, with no clock
        arithmetic."""
        return _Span(self, name, follows, fields)


class _Span:
    """One `Recorder.span()` region: a plain context manager (no
    generator frame on the serving loop's six spans a step)."""

    __slots__ = ("_rec", "_name", "_follows", "_fields", "_ids", "_t0",
                 "_ann")

    def __init__(self, rec: Recorder, name: str, follows: bool,
                 fields: dict):
        self._rec, self._name, self._follows = rec, name, follows
        self._fields = fields

    def __enter__(self) -> dict:
        rec, fields = self._rec, self._fields
        stack = rec._stack()
        parent = fields.pop("parent_id", None) or (stack[-1] if stack
                                                   else None)
        sid = fields.pop("span_id", None) or rec.new_span_id()
        self._ids = {"span_id": sid}
        if parent is not None:
            self._ids["parent_id"] = parent
        stack.append(sid)
        self._ann = _trace_annotation(self._name)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        if self._follows:
            self._t0 = getattr(rec._tloc, "last_t1", self._t0)
        return fields

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        rec = self._rec
        rec._tloc.last_t1 = t1
        if exc is not None:
            # emitted while the span is still open: the error parents to it
            rec.error(f"span:{self._name}", exc=exc)
        rec._stack().pop()
        rec.event("span", name=self._name, ok=exc is None,
                  seconds=round(t1 - self._t0, 6), t0=round(self._t0, 6),
                  t1=round(t1, 6), **self._ids, **self._fields)
        return False


def _trace_annotation(name: str):
    """`jax.profiler.TraceAnnotation(name)` where jax is already loaded,
    else None: this module imports no jax, so the no-jax tools and the
    lint's package stubs pay nothing and miss nothing."""
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    cls = getattr(profiler, "TraceAnnotation", None)
    return cls(name) if cls is not None else None


def _fan_out(sinks, rec: dict) -> None:
    for sink in sinks:
        try:
            sink(rec)
        except Exception:
            pass  # a broken sink must never break recording


class _NullSpan:
    """What every `NullRecorder.span()` returns, one object for all:
    entering it costs a dict for the caller's result fields and nothing
    else (no generator, no clock, no ids)."""

    __slots__ = ()

    def __enter__(self) -> dict:
        return {}

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class NullRecorder(Recorder):
    """Telemetry disabled: every emit is a no-op so hooks threaded
    through hot loops (fused_fit, listeners) cost one attribute lookup.
    ``span`` still runs the body, recording nothing. One kind still
    reaches the sinks, `request` — one event a request, none a token or
    a step — so that a server's `/metrics` counts requests in a default
    deployment."""

    live = False

    def __init__(self):
        super().__init__(path=None, run_id="null", keep=1)

    def event(self, kind: str, /, **fields) -> dict:  # noqa: D102
        return {}

    def request(self, request_id: str, *, ok: bool = True,
                **fields) -> dict:
        with self._lock:
            sinks = list(self._sinks)
        if not sinks:
            return {}
        rec = {"event": "request", "ts": round(time.time(), 3),
               "run": self.run_id, "id": request_id, "ok": bool(ok),
               **fields}
        _fan_out(sinks, rec)
        return rec

    def eval(self, stats, **fields) -> dict:
        return {}  # skip the stats-dict materialization, not just the write

    def memory(self, **fields) -> dict:
        return {}  # skip the live-array walk

    def span(self, name: str, *, follows: bool = False,
             **fields) -> _NullSpan:
        return _NULL_SPAN


def _jsonable(obj):
    """json.dumps fallback: device scalars/arrays stringify via float/
    repr instead of crashing the log write."""
    try:
        return float(obj)
    except Exception:
        return repr(obj)


# ------------------------------------------------------- process default
_NULL = NullRecorder()
_default: Recorder | None = None


def set_default(recorder: Recorder | None) -> Recorder | None:
    """Install the process-global recorder; returns the previous one
    (None if the env-var/null fallback was in effect)."""
    global _default
    prev, _default = _default, recorder
    return prev


def _process_scoped(path: str) -> str:
    """Multi-process safety: N fleet processes inherit ONE
    `DL4J_TPU_TELEMETRY` value from their launcher, and while O_APPEND
    keeps whole lines intact, N interleaved event streams in one file are
    unattributable (and a `requote` recovery can't tell whose crash it is
    reading). When the rendezvous contract names a process id
    (distributed/bootstrap.py), each process appends to its own
    `<path>.p<id>` instead — two writers, two parseable logs."""
    try:
        from deeplearning4j_tpu.distributed.bootstrap import ENV_PROCESS_ID
    except Exception:  # pragma: no cover - stubbed package layouts
        return path
    process_id = os.environ.get(ENV_PROCESS_ID)
    if process_id is None:
        return path
    return f"{path}.p{process_id}"


def get_default() -> Recorder:
    """The process-global recorder. Resolution order: an explicit
    `set_default`, else a file recorder appending to `$DL4J_TPU_TELEMETRY`
    (created on first use; suffixed per process when the distributed
    rendezvous contract is active), else a no-op NullRecorder."""
    global _default
    if _default is not None:
        return _default
    path = os.environ.get(ENV_VAR)
    if path:
        _default = Recorder(_process_scoped(path))
        return _default
    return _NULL
