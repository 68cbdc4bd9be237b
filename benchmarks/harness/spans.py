"""The program's spans and events, kept in memory for a traced run.

A `Recorder(path=None)` of the program is installed as its process
default (the program's own switch for telemetry), and a sink stamps each
event with this process's `perf_counter` as it is emitted: a span event
is emitted when the span ends, so its interval is [t - seconds, t].
Untraced runs install nothing, and the program records nothing.
"""

from __future__ import annotations

import threading
import time


class SpanLog:
    def __init__(self):
        self._lock = threading.Lock()
        self.spans = []     # (name, t_start, t_end, fields)
        self.events = []    # (kind, t, fields)

    def __call__(self, rec: dict) -> None:
        now = time.perf_counter()
        with self._lock:
            if rec.get("event") == "span":
                dur = float(rec.get("seconds", 0.0))
                self.spans.append((rec.get("name"), now - dur, now, rec))
            else:
                self.events.append((rec.get("event"), now, rec))

    def named(self, name: str, t0: float = float("-inf"),
              t1: float = float("inf")) -> list:
        """Spans of that name that ended inside [t0, t1]."""
        with self._lock:
            return [s for s in self.spans if s[0] == name and t0 <= s[2] <= t1]

    def of_kind(self, kind: str, t0: float = float("-inf"),
                t1: float = float("inf")) -> list:
        with self._lock:
            return [e for e in self.events if e[0] == kind and t0 <= e[1] <= t1]


def install() -> SpanLog:
    """Switch the program's telemetry on, in memory, and listen to it."""
    from deeplearning4j_tpu.telemetry import Recorder, set_default

    log = SpanLog()
    rec = Recorder(path=None, keep=1)
    rec.add_sink(log)
    set_default(rec)
    return log
