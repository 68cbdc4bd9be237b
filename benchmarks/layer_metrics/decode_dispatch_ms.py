"""engine host loop: the median `dispatch` child of the window's
`decode_step` spans, from just before the jit call to its return (the
parameter tree and the cache flattened, the program enqueued), in
milliseconds. The rest of a `decode_step` is its `fetch`, which waits for
the device. Read over the part of the window before the profiler's trace
was stopped (`host_loop.quiet_window`)."""
import statistics

from harness import host_loop


def read(facts):
    spans = facts.get("spans")
    if spans is None:
        return None
    xs = host_loop.children_of(spans, "decode_step", "dispatch",
                               *host_loop.quiet_window(facts))
    return 1e3 * statistics.median(xs) if xs else None
