"""kernels (ops/prefill_attention.py `gqa_prefill`): the least time the
chip could take for the prefill chunks' grouped attention over the time
the kernel took for them. Each prefill program (`counted_prefill`) of the
traced window, up to the trace's stop, is joined to the `prefill_chunk`
span that dispatched it (harness/programs.py), which says where the chunk
starts and how many of its tokens are real (`start`, `n_real`). The
least: the attention the chunk's REAL queries need, 4 Hq d operations a
key a query (a score and a weighted value a query head), over the keys a
query at position pos sees in all layers, the family's
`_rows_read(dims, pos + 1)` (a window layer's min(pos + 1, window)),
summed over start .. start + n_real - 1, at the peak bf16 rate
(compute-bound: 6 x 1,024 queries meet each key and value). The
projections, the bucket's pads and the masked parts of blocks are not
counted, so the share stays under 100 %. The time: the summed device
time of the kernel events inside those programs. A program without the
kernel (the parent's), or a family without the count, gives nothing to
read."""
import bisect

import numpy as np
from harness import programs, spec, xplane

KERNEL = "gqa_prefill"


def read(facts):
    peaks, dims, config = facts.get("peaks"), facts.get("dims") or {}, \
        facts.get("config")
    joined = programs.join(facts)
    if not peaks or not joined or not config or "Hq" not in dims \
            or "d" not in dims:
        return None
    rows_read = getattr(spec.family_of(config), "_rows_read", None)
    if rows_read is None:
        return None
    kernels = sorted((s, d) for n, s, d, detail
                     in facts["traced"]["chips"][0]["ops"]
                     if KERNEL in n and xplane.is_kernel(detail))
    starts = [s for s, _d in kernels]
    keys = spent = 0.0          # keys the real queries see; kernel seconds
    for p in joined["programs"]:
        span = p["span"]
        if p["kind"] != "prefill_chunk" or "start" not in span \
                or "n_real" not in span:
            continue
        lo = bisect.bisect_left(starts, p["start_ns"])
        hi = bisect.bisect_left(starts, p["start_ns"] + p["dur_ns"])
        inside = sum(d for _s, d in kernels[lo:hi])
        if not inside:
            continue
        pos = np.arange(span["start"], span["start"] + span["n_real"],
                        dtype=np.float64)
        keys += float(np.sum(rows_read(dims, pos + 1)))
        spent += inside / 1e9
    if spent <= 0.0:
        return None
    return (100.0 * 4 * dims["Hq"] * dims["d"] * keys / peaks["flops_bf16"]
            / spent)
