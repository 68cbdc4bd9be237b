"""Run telemetry: typed JSONL event recording for training and bench,
plus the fleet-wide trace timeline built on top of it.

`recorder` (Recorder/span API, correlation fields, process default) and
`artifact` (bench summary/parsing) are stdlib-only and import eagerly;
`trace` (shard merge / span stats / anomaly detection / Perfetto
export) and `metrics` (the Prometheus /metrics registry) are
stdlib-only too and resolve lazily alongside `TelemetryListener` so
the tools' no-jax package stubs can import this package.
"""

from deeplearning4j_tpu.telemetry.recorder import (  # noqa: F401
    ENV_VAR,
    EVENT_KINDS,
    REGION_NAMES,
    REGIONS,
    SPAN_NAMES,
    NullRecorder,
    Recorder,
    get_default,
    set_default,
)


def __getattr__(name):
    if name == "TelemetryListener":
        from deeplearning4j_tpu.telemetry.listener import TelemetryListener
        return TelemetryListener
    if name in ("MemoryLedger", "MemorySampler"):
        from deeplearning4j_tpu.telemetry import memstat
        return getattr(memstat, name)
    if name == "CostBook":
        from deeplearning4j_tpu.telemetry.costbook import CostBook
        return CostBook
    if name in ("trace", "metrics", "memstat", "costbook"):
        import importlib
        return importlib.import_module(
            f"deeplearning4j_tpu.telemetry.{name}")
    raise AttributeError(name)
