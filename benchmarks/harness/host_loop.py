"""What the readers of the serving engine's host loop share: the
program's spans as intervals on this process's `perf_counter`.

A span event of the program carries `t0` / `t1`, `perf_counter` at the
region's two ends. A program that does not stamp them yet is read as the
`SpanLog` rebuilt it: [sink time - seconds, sink time]. A leaf span is one
that no other span names as its parent: the regions of the loop that hold
no further region of the program.
"""

from __future__ import annotations

# one model step each: their extent is from just before the jit call to
# the fetched tokens
STEP_SPANS = ("decode_step", "verify_step", "prefill_chunk")
WAIT_SPAN = "idle_wait"


def quiet_window(facts) -> tuple:
    """(t0, t1) of the window up to the instant the profiler's trace was
    stopped. From there on the profiler's export runs beside the server
    for most of what is left of the window and slows the server's host
    (PERF.md section 5: `dispatch` reads 3.6 ms before the stop and 10-12
    ms after it), so a reader of the host loop's own times reads the part
    before it. Where no trace was taken, the whole window."""
    t0, t1 = facts["window"]
    t_off = (facts.get("traced") or {}).get("t_off")
    return t0, t1 if t_off is None else max(t0, min(t1, t_off))


def interval(span) -> tuple:
    """(start, end) of one `SpanLog.spans` entry."""
    _name, a, b, fields = span
    if "t0" in fields and "t1" in fields:
        return float(fields["t0"]), float(fields["t1"])
    return a, b


def leaf_spans(log) -> list:
    """[(name, start, end)] of the spans with no child, in the order the
    program emitted them."""
    entries = list(log.spans)
    parents = {f.get("parent_id") for _n, _a, _b, f in entries}
    return [(s[0], *interval(s)) for s in entries
            if s[3].get("span_id") not in parents]


def children_of(log, parent_name: str, child_name: str, t0: float,
                t1: float) -> list:
    """Durations of the `child_name` spans whose parent is a
    `parent_name` span that ended inside [t0, t1]."""
    entries = list(log.spans)
    parents = {f.get("span_id") for n, _a, b, f in entries
               if n == parent_name and t0 <= b <= t1}
    out = []
    for s in entries:
        if s[0] == child_name and s[3].get("parent_id") in parents:
            a, b = interval(s)
            out.append(b - a)
    return out


def step_gaps(log, t0: float, t1: float) -> list:
    """Seconds from the end of one model-step span to the start of the
    next, over the consecutive model steps of each replica that ended
    inside [t0, t1] with no `idle_wait` between them: what the engine
    thread spends between two steps when it has a step to run."""
    entries = list(log.spans)
    waits = sorted(interval(s)[0] for s in entries if s[0] == WAIT_SPAN)
    by_replica = {}
    for s in entries:
        if s[0] in STEP_SPANS and t0 <= s[2] <= t1:
            by_replica.setdefault(s[3].get("replica"), []).append(interval(s))
    out = []
    for steps in by_replica.values():
        steps.sort()
        for (_a, end), (start, _b) in zip(steps, steps[1:]):
            if not any(end <= w <= start for w in waits):
                out.append(start - end)
    return out
