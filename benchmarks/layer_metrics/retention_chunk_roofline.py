"""kernels (ops/power_retention.py `retention_chunk`): the least time the
chip could take for the state's terms of the prefill chunks' power
retention over the time the kernel took for them. Each prefill program
(`counted_prefill`) of the traced window, up to the trace's stop, is
joined to the `prefill_chunk` span that dispatched it
(harness/programs.py), which says where the chunk starts and how many of
its tokens are real (`start`, `n_real`). The least, over all layers: the
state advanced by every real token, 2 Hk (d + 1) P operations (its
key's symmetric square into every value row and into z), and, where the
chunk starts past position 0, the state read by every real query,
2 Hq (d + 1) P; P = d (d + 1) / 2 is the algorithm's pairs (8,256 at d =
128; the program holds 8,320). At the peak bf16 rate: the kernel is
bound by the matrix unit (the state, 34 MB a row and layer, is read and
written once a chunk; its read-out takes 2 x 1,024 x 40 operations an
entry). The attention form inside a sub-chunk, the pads and the read-out
of a chunk that starts a sequence are not counted, so the share stays
under 100 % whatever the kernel skips. The time: the summed device time
of the kernel events inside those programs. A program without the kernel
(the parent's), or a cell without the retention layer's sizes, gives
nothing to read."""
import bisect

from harness import programs, xplane

KERNEL = "retention_chunk"
SIZES = ("Hq", "Hk", "d", "L")


def read(facts):
    peaks, dims = facts.get("peaks"), facts.get("dims") or {}
    joined = programs.join(facts)
    if not peaks or not joined or not all(k in dims for k in SIZES):
        return None
    kernels = sorted((s, d) for n, s, d, detail
                     in facts["traced"]["chips"][0]["ops"]
                     if KERNEL in n and xplane.is_kernel(detail))
    starts = [s for s, _d in kernels]
    d = dims["d"]
    per_head = 2 * (d + 1) * (d * (d + 1) // 2) * dims["L"]
    ops = spent = 0.0           # operations the real tokens need; seconds
    for p in joined["programs"]:
        span = p["span"]
        if p["kind"] != "prefill_chunk" or "start" not in span \
                or "n_real" not in span:
            continue
        lo = bisect.bisect_left(starts, p["start_ns"])
        hi = bisect.bisect_left(starts, p["start_ns"] + p["dur_ns"])
        inside = sum(t for _s, t in kernels[lo:hi])
        if not inside:
            continue
        heads = dims["Hk"] + (dims["Hq"] if span["start"] > 0 else 0)
        ops += float(span["n_real"]) * heads * per_head
        spent += inside / 1e9
    if spent <= 0.0:
        return None
    return 100.0 * ops / peaks["flops_bf16"] / spent
