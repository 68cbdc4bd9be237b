"""attention layer (every token mixer: nn/layers/attention.py,
latent_attention.py, grouped_attention.py, power_retention.py, their
cache writes included): the median device time of the region
`attention` in one decode program, in ms: the self time of the ops the
program's own table puts there (its `regions` event), per matched
decode program of the traced window up to the trace's stop
(harness/programs.py, harness/regions.py). A program that records no
table gives nothing to read."""
import statistics

from harness import regions


def read(facts):
    spent = regions.region_seconds(regions.of_kind(facts, "decode_step"),
                                   "attention")
    return 1e3 * statistics.median(spent) if spent else None
