"""engine host loop (serving/engine.py `_GenWorker.loop`): the median, over
the window's consecutive model-step spans (`decode_step`, `verify_step`,
`prefill_chunk`) with no `idle_wait` between them, of the next span's `t0`
less this span's `t1`: the host time between two model steps when there
is a step to run (emit, admit, step_prepare and the recorder's own
emission), in milliseconds. Read over the part of the window before the
profiler's trace was stopped (`host_loop.quiet_window`)."""
import statistics

from harness import host_loop


def read(facts):
    spans = facts.get("spans")
    if spans is None:
        return None
    gaps = host_loop.step_gaps(spans, *host_loop.quiet_window(facts))
    return 1e3 * statistics.median(gaps) if gaps else None
