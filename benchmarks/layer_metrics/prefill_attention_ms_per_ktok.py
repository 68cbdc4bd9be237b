"""attention layer (every token mixer, its cache writes included): the
device time of the region `attention` in the prefill chunks per
thousand real prompt tokens, in ms: the self time of the ops each
matched prefill program's own table puts there, summed over the traced
window up to the trace's stop, over the chunks' summed `n_real` / 1,000
(harness/programs.py, harness/regions.py). A program that records no
table gives nothing to read."""
from harness import regions


def read(facts):
    progs = [p for p in regions.of_kind(facts, "prefill_chunk")
             if p["regions"] is not None]
    spent, k = regions.region_seconds(progs, "attention"), regions.ktok(progs)
    return 1e3 * sum(spent) / k if spent and k > 0 else None
