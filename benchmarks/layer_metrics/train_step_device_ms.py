"""train step: the median device duration of the step's XLA program in
the trace (the `jit_step` module), in milliseconds."""
import statistics

from harness import xplane


def read(facts):
    t = facts.get("traced")
    if not t:
        return None
    durs = xplane.module_durations(t["chips"], r"step")
    if not durs:
        return None
    return 1e3 * statistics.median(durs)
