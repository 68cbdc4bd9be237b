"""loop (nn/decode.py's walk runs a looped net's span as one scan over
the pass): the median device time of the region `loop` in one decode
program, in ms: the self time of the ops the program's own table puts
there (its `regions` event) and in no layer of the body, the pass
counter and the carry, per matched decode program of the traced window
up to the trace's stop (harness/programs.py, harness/regions.py). Near 0
where the carried cache entries are written in place; a pass's rows
copied would show here. A program with no loop, or one that records no
table, gives nothing to read."""
import statistics

from harness import regions


def read(facts):
    spent = regions.region_seconds(regions.of_kind(facts, "decode_step"),
                                   "loop")
    return 1e3 * statistics.median(spent) if spent else None
