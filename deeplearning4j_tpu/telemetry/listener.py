"""TelemetryListener — feeds the run recorder from fit() without host
syncs on the hot path.

`model.score_value` is a property whose getter converts the jitted
step's DEVICE scalar to a python float — a blocking host readback
that waits for the step. A per-iteration listener that
reads it would serialize every step on the transfer (the G002 bug class
in listener form). This listener instead captures the RAW device scalar
(`model._score_raw`, no conversion) each iteration and materializes the
whole window in one batched fetch every `frequency` steps: one pipeline
stall per window instead of one per step. The scalars it fetches are
already `frequency` steps old by then — they are done computing, so the
stall is only the transfer latency of the newest one.
"""

from __future__ import annotations

import time

from deeplearning4j_tpu.optimize.listeners import IterationListener
from deeplearning4j_tpu.telemetry.recorder import Recorder, get_default


class TelemetryListener(IterationListener):
    """Emit a typed `step` event per iteration, buffered and flushed
    every `frequency` iterations (plus an optional `memory` snapshot per
    flush). Attach with `net.set_listeners(TelemetryListener())`; call
    `close()` (or rely on the final partial flush staying buffered at
    most `frequency-1` steps) after fit()."""

    def __init__(self, recorder: Recorder | None = None,
                 frequency: int = 50, snapshot_memory: bool = False):
        self.recorder = recorder
        self.frequency = max(1, frequency)
        self.snapshot_memory = snapshot_memory
        self._pending: list[tuple[int, object, float]] = []

    def _rec(self) -> Recorder:
        return self.recorder if self.recorder is not None else get_default()

    def iteration_done(self, model, iteration):
        # raw device scalar — NOT model.score_value (the float() there is
        # the per-step host sync this listener exists to avoid)
        raw = getattr(model, "_score_raw", None)
        self._pending.append((iteration, raw, time.perf_counter()))
        if len(self._pending) >= self.frequency:
            self.flush()

    def flush(self) -> None:
        """Materialize the buffered window: one batched host fetch, one
        `step` event per buffered iteration, throughput over the window."""
        if not self._pending:
            return
        rec = self._rec()
        window, self._pending = self._pending, []
        t_first, t_last = window[0][2], window[-1][2]
        its_per_sec = None
        if len(window) > 1 and t_last > t_first:
            its_per_sec = round((len(window) - 1) / (t_last - t_first), 4)
        for i, (iteration, raw, _t) in enumerate(window):
            score = None
            if raw is not None:
                try:
                    score = float(raw)
                except (TypeError, ValueError):
                    score = None
            fields = {}
            if i == len(window) - 1 and its_per_sec is not None:
                fields["iterations_per_sec"] = its_per_sec
            rec.step(iteration, score=score, **fields)
        if self.snapshot_memory:
            rec.memory(iteration=window[-1][0])

    def close(self) -> None:
        self.flush()
