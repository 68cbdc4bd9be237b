"""expert layer (nn/layers/moe.py `DroplessMoELayer`: router, held
experts and shared expert): the median device time of the region `moe`
in one decode program, in ms: the self time of the ops the program's own
table puts there, per matched decode program of the traced window up to
the trace's stop (harness/programs.py, harness/regions.py). A program
that records no table, or a net with no expert layer, gives nothing to
read."""
import statistics

from harness import regions


def read(facts):
    spent = regions.region_seconds(regions.of_kind(facts, "decode_step"),
                                   "moe")
    return 1e3 * statistics.median(spent) if spent else None
