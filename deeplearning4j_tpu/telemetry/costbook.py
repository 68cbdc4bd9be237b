"""Compiled-cost book — per-executable XLA cost/memory analyses as
typed `cost` events, harvested at warmup/compile time.

Every jitted entry the repo warms (serving forward buckets, prefill
chunks, the decode/verify step, the fused fit scan) already runs under
a `compile` span. This module rides that moment: AFTER the warm call
has populated the jit cache, `jitted.lower(*args)` is a jaxpr-cache
hit — it does NOT re-trace, so the trace counters the zero-retrace
gates freeze stay frozen. Flops and bytes-accessed come straight off
the lowered program (`Lowered.cost_analysis()`, no backend compile);
`memory_analysis()` (peak temp, argument/output/alias/code bytes) needs the
AOT executable, so `.compile()` runs once per UNIQUE lowered program
per process (fingerprint cache — re-warmed replicas and respawns hit
it), paid entirely at warmup; ZERO hot-path cost, by construction.

The book is the denominator store for MFU: measured step wall-clock
over the recorded flops against the device's peak gives
`mfu_live`, the gauge /metrics and the bench summary expose. It is
also the measured side of the placement cost model's calibration loop:
`reconcile()` emits a typed `cost_drift` event naming the search's
predicted per-device bytes, the measured peak, and their ratio —
outside the documented factor is a detector anomaly
(telemetry/trace.py `detect_cost_drift`).

The documented drift factor: `DEFAULT_DRIFT_FACTOR = 8.0`. The search
predicts packed parameter-resident bytes per device from exact
rational arithmetic; a live process measures float32 live arrays plus
optimizer state plus runtime slack (and, off-TPU, live-array
accounting stands in for HBM). Within 8x in either direction is
calibration-pass; outside it the model has rotted and the
`cost_drift` anomaly fires.

Everything here is best-effort: an AOT API that a backend does not
implement degrades to a partial (or absent) book entry, never an
exception on the warmup path.
"""

from __future__ import annotations

import re
import threading

from deeplearning4j_tpu.telemetry.recorder import (REGIONS, NullRecorder,
                                                   Recorder)

DEFAULT_DRIFT_FACTOR = 8.0

# Published peaks per device kind, as `jax.devices()[0].device_kind`
# names it — the ONE table (bench.py and serving/engine.py read it).
# Only the chip this installation runs on is listed; a TPU whose kind is
# not here is an error, never a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s per chip",
    },
}


def peak_flops(device) -> float | None:
    """Peak dense bf16 FLOP/s of a jax device — the MFU denominator.
    Off-TPU there is no peak to divide by: None, and callers leave the
    field out. A TPU kind missing from DEVICE_PEAKS raises."""
    if getattr(device, "platform", None) != "tpu":
        return None
    kind = getattr(device, "device_kind", "")
    if kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peak for TPU device kind {kind!r}; add it to "
            f"telemetry/costbook.DEVICE_PEAKS with its source "
            f"(known: {sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[kind]["bf16_flops"]


def _first(analysis):
    """cost_analysis() returns a dict on some jax versions, a
    per-partition list of dicts on others — normalize to one dict."""
    if isinstance(analysis, (list, tuple)):
        return analysis[0] if analysis else {}
    return analysis or {}


_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(([^)]*)\)")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_REF = re.compile(r"%?([\w.\-]+)")
# a scope in an op_name's path: a name, or a transform of one ("jvp(moe)")
_SCOPE = re.compile(r"[\w\-]+(\([\w\-()]*\))?")
# instructions that never run as an operation of their own
_NOT_RUN = frozenset({"parameter", "constant", "tuple", "get-tuple-element"})


def region_path(op_name: str) -> str | None:
    """The region an `op_name` names: "top" or "top/child" of `REGIONS`,
    the innermost top-level region in the path ("jit(step)/loss/
    transpose(jvp(attention))/dot_general" is "attention"), or None. A
    part of the path that is no name (an argument's, "params['embed']
    ['W']") names no region."""
    top = child = None
    words = [w for part in op_name.split("/") if _SCOPE.fullmatch(part)
             for w in re.findall(r"[\w\-]+", part)]
    for word in words:
        if word in REGIONS:
            top, child = word, None
        elif top is not None and word in REGIONS[top]:
            child = word
    if top is None:
        return None
    return top if child is None else f"{top}/{child}"


def hlo_regions(text: str) -> dict:
    """{"module": the HLO module's name, "ops": {instruction: region}} of
    a compiled program's text (`Compiled.as_text()`), for the
    instructions a profiler trace can show: every one outside a fusion's
    body, but parameters, constants and tuples. An instruction's region
    is its `op_name`'s (`region_path`); one the compiler made with none
    (a prefetch, a relayout copy, an async `*-done`) takes the region of
    its `*-start`, else of the first instruction it feeds that has one,
    else of the first it reads; one left with none is not listed."""
    module, comp, fused = "", "", set()
    insts = []      # (name, opcode, operands, computation)
    region, users = {}, {}
    for line in text.splitlines():
        if not line.startswith(" "):
            if line.startswith("HloModule "):
                module = line.split()[1].rstrip(",")
            elif line.endswith("{"):
                comp = line.removeprefix("ENTRY ").split()[0].lstrip("%")
            continue
        if " = " not in line:
            continue
        name, rest = line.strip().removeprefix("ROOT ").split(" = ", 1)
        m = _OPCODE.search(rest)
        if m is None:
            continue
        name, op = name.lstrip("%"), m.group(1)
        operands = _REF.findall(m.group(2))
        if op == "fusion":
            fused.update(_CALLS.findall(rest))
        named = _OP_NAME.search(rest)
        region[name] = region_path(named.group(1)) if named else None
        for i in operands:
            users.setdefault(i, []).append(name)
        insts.append((name, op, operands, comp))
    for n, op, ins, _c in insts:
        if region[n] is None and op.endswith("-done") and ins:
            region[n] = region.get(ins[0])
    # a parameter, a constant or a tuple neither takes a region nor hands
    # one on: many layers read one
    for n, op, _ins, _c in reversed(insts):
        if region[n] is None and op not in _NOT_RUN:
            region[n] = next((region[u] for u in users.get(n, ())
                              if region.get(u)), None)
    for n, op, ins, _c in insts:
        if region[n] is None and op not in _NOT_RUN:
            region[n] = next((region[i] for i in ins if region.get(i)),
                             None)
    return {"module": module,
            "ops": {n: region[n] for n, op, _ins, c in insts
                    if region[n] and op not in _NOT_RUN and c not in fused}}


def _regions_of(lowered, compiled) -> dict:
    """`hlo_regions` of the compiled program. A persistent compile cache
    keys a program without its metadata, so the executable it hands back
    may be one compiled before the program named its regions (the same
    instructions, no scopes): then the program is compiled once more as
    it is lowered now, under a key that holds its metadata."""
    found = hlo_regions(compiled.as_text())
    if not found["ops"]:
        from jax._src import config  # the key's switch has no public API

        with config.compilation_cache_include_metadata_in_key(True):
            found = hlo_regions(lowered.compile().as_text())
    return found


# Fingerprint -> compile-derived field dict. memory_analysis() needs
# the AOT executable, and an explicit .compile() does NOT share the
# warm call's executable cache — it is one real XLA compile. Keying the
# result on the lowered module's text hash makes each unique program
# pay that compile once per process: re-warmed replicas, engine
# respawns, and identical configs all hit the cache (params ride as
# jit ARGUMENTS, so weights never land in the fingerprinted HLO).
_HARVEST_CACHE: dict = {}
_HARVEST_MU = threading.Lock()


def harvest(jitted, *args, **kwargs) -> dict:
    """Lower an ALREADY-WARMED jit wrapper and pull XLA's own analyses.
    Returns a (possibly partial) field dict; {} when the backend
    exposes nothing. Call this ONLY at warmup/compile time — graftlint
    G029 flags memory_analysis() anywhere near a hot loop outside
    telemetry/."""
    fields: dict = {}
    try:
        lowered = jitted.lower(*args, **kwargs)
    except Exception:
        return fields
    # flops / bytes accessed straight off the lowered (pre-optimization)
    # program where the jax version exposes it — no backend compile
    try:
        ca = _first(lowered.cost_analysis())
        if "flops" in ca:
            fields["flops"] = float(ca["flops"])
        if "bytes accessed" in ca:
            fields["bytes_accessed"] = float(ca["bytes accessed"])
    except Exception:
        pass
    fp = None
    try:
        import hashlib

        fp = hashlib.sha1(lowered.as_text().encode()).hexdigest()
        with _HARVEST_MU:
            cached = _HARVEST_CACHE.get(fp)
        if cached is not None:
            return {**cached, **fields}
    except Exception:
        pass
    try:
        compiled = lowered.compile()
    except Exception:
        return fields
    compiled_fields: dict = {}
    if "flops" not in fields or "bytes_accessed" not in fields:
        try:
            ca = _first(compiled.cost_analysis())
            if "flops" in ca:
                compiled_fields["flops"] = float(ca["flops"])
            if "bytes accessed" in ca:
                compiled_fields["bytes_accessed"] = float(
                    ca["bytes accessed"])
        except Exception:
            pass
    try:
        compiled_fields["regions"] = _regions_of(lowered, compiled)
    except Exception:
        pass
    try:
        ma = compiled.memory_analysis()
        for attr, key in (("temp_size_in_bytes", "peak_temp_bytes"),
                          ("argument_size_in_bytes", "argument_bytes"),
                          ("output_size_in_bytes", "output_bytes"),
                          # argument bytes the outputs reuse in place:
                          # what buffer donation bought (0 without it)
                          ("alias_size_in_bytes", "alias_bytes"),
                          ("generated_code_size_in_bytes",
                           "generated_code_bytes")):
            val = getattr(ma, attr, None)
            if val is not None:
                compiled_fields[key] = int(val)
    except Exception:
        pass
    if fp is not None and compiled_fields:
        with _HARVEST_MU:
            _HARVEST_CACHE.setdefault(fp, dict(compiled_fields))
    return {**compiled_fields, **fields}


class CostBook:
    """Per-(entry, shape) compiled-cost records + the typed `cost`
    event emitter. One book per engine/net; `record()` dedups, so
    respawn re-warms (which compile nothing) also emit nothing."""

    def __init__(self, recorder: Recorder):
        self.recorder = recorder
        # (entry, shape key) -> harvested field dict; `_mu` guards the
        # dict only — the lower/compile harvest and the emit run outside
        self._book: dict = {}
        self._mu = threading.Lock()

    @property
    def enabled(self) -> bool:
        return not isinstance(self.recorder, NullRecorder)

    @staticmethod
    def _key(entry: str, shape) -> tuple:
        try:
            frozen = tuple(shape) if isinstance(shape, (list, tuple)) \
                else (shape,)
        except Exception:
            frozen = (repr(shape),)
        return (entry, frozen)

    def record(self, entry: str, shape, jitted, args,
               kwargs=None, **extra) -> dict:
        """Harvest one warmed executable into the book and emit its
        `cost` event. `shape` is the warmed shape key (a bucket key
        list, a [B, T] pair, ...). Returns the event dict ({} when
        disabled, already recorded, or nothing harvestable)."""
        if not self.enabled:
            return {}
        key = self._key(entry, shape)
        with self._mu:
            if key in self._book:
                return {}
        fields = harvest(jitted, *args, **(kwargs or {}))
        regions = fields.pop("regions", None)
        if not fields:
            return {}
        with self._mu:
            if key in self._book:  # lost a warmup race: keep the first
                return {}
            self._book[key] = dict(fields)
        if regions is not None:
            self.recorder.event("regions", entry=entry, shape=list(key[1]),
                                **regions)
        return self.recorder.cost(entry, list(key[1]), **fields, **extra)

    # ------------------------------------------------------------- lookups
    def entries(self) -> dict:
        with self._mu:
            return {k: dict(v) for k, v in self._book.items()}

    def flops(self, entry: str | None = None, shape=None) -> float:
        """Recorded flops: for one (entry, shape), for every shape of
        one entry, or the whole book."""
        with self._mu:
            items = list(self._book.items())
        total = 0.0
        for (name, frozen), fields in items:
            if entry is not None and name != entry:
                continue
            if shape is not None and frozen != self._key(entry or name,
                                                         shape)[1]:
                continue
            total += float(fields.get("flops", 0.0) or 0.0)
        return total

    def peak_temp_bytes(self) -> int:
        """Max XLA peak-temp over the book — the compiled side of the
        memory headline."""
        with self._mu:
            vals = [int(f.get("peak_temp_bytes", 0) or 0)
                    for f in self._book.values()]
        return max(vals) if vals else 0

    @staticmethod
    def mfu(flops: float, seconds: float, peak: float) -> float:
        """Model FLOPs utilization for one executed step: achieved
        FLOP/s over the device peak, clamped to [0, 1]."""
        if seconds <= 0 or peak <= 0 or flops <= 0:
            return 0.0
        return max(0.0, min(1.0, (flops / seconds) / peak))


def measured_peak_bytes() -> int:
    """The measured side of the calibration loop: the max per-device
    `peak_bytes_in_use` the backend reports, else (off-TPU) the current
    live-array byte total."""
    from deeplearning4j_tpu.telemetry.memstat import (device_memory_stats,
                                                      live_array_totals)

    devices = device_memory_stats()
    peaks = [int(d.get("peak_bytes_in_use", 0) or 0)
             for d in devices.values()]
    peak = max(peaks) if peaks else 0
    if peak > 0:
        return peak
    total, _ = live_array_totals()
    return total


def reconcile(recorder: Recorder, predicted_bytes: int, *,
              measured_bytes: int | None = None,
              factor: float = DEFAULT_DRIFT_FACTOR,
              source: str = "placement", **fields) -> dict:
    """Close the cost-model loop: predicted per-device bytes (the
    placement search's `winner_memory_bytes`) vs a measured peak, as a
    typed `cost_drift` event. Run this AFTER the first real step so the
    measurement covers a steady-state footprint. Returns the event; {}
    under a NullRecorder or a non-positive prediction (nothing to
    reconcile)."""
    if isinstance(recorder, NullRecorder):
        return {}
    predicted = int(predicted_bytes or 0)
    if predicted <= 0:
        return {}
    if measured_bytes is None:
        measured_bytes = measured_peak_bytes()
    return recorder.cost_drift(predicted_bytes=predicted,
                               measured_bytes=int(measured_bytes),
                               factor=float(factor), source=source,
                               **fields)
