"""A profiler trace of part of the window, from a timer thread.

The trace covers [start_after, start_after + length] seconds of the
window. A `TraceAnnotation` right after the start ties the trace's
clock to this process's `perf_counter`, so that host spans can be laid
over the device's idle gaps. The Python tracer is off: it would record
every call of the serving loop and slow the host it measures.
"""

from __future__ import annotations

import shutil
import tempfile
import threading
import time

SYNC_NAME = "bench_clock_sync"


class WindowTrace:
    def __init__(self, start_after: float, length: float):
        self.start_after = float(start_after)
        self.length = float(length)
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.t_on = self.t_off = self.t_sync = None
        self.error = None
        self._thread = None

    def arm(self, t_window_start: float) -> None:
        """Start the timer: call when the window starts."""
        self._thread = threading.Thread(
            target=self._run, args=(t_window_start,), daemon=True,
            name="bench-trace")
        self._thread.start()

    def _run(self, t0: float) -> None:
        import jax

        try:
            time.sleep(max(0.0, t0 + self.start_after - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.t_on = time.perf_counter()
            with jax.profiler.TraceAnnotation(SYNC_NAME):
                self.t_sync = time.perf_counter()
            time.sleep(max(0.0, self.t_on + self.length - time.perf_counter()))
            self.t_off = time.perf_counter()
            jax.profiler.stop_trace()
        except Exception as exc:  # reported by finish()
            self.error = exc

    def finish(self) -> str:
        """Wait for the trace to be written; the `.xplane.pb` path."""
        from harness import xplane

        self._thread.join()
        if self.error is not None:
            raise self.error
        return xplane.find_xplane(self.dir)

    def cleanup(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)
