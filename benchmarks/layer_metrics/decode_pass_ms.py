"""loop (nn/decode.py's walk runs a looped net's span once a pass): the
median device time of one decode program (as `decode_device_ms.py` reads
it) over the passes a live row of it ran, in ms: one pass of the loop's
body. The passes a live row ran are `loop_passes` (the passes of the
step summed over its live rows) over `n_active` (its live rows) of the
decode programs of the window: the counters of program n come home on
the span that fetched it, which names it (`fetched`), and its own
`decode_step` span holds `n_active` (serving/engine.py). Read up to the
trace's stop. A program that counts no passes gives nothing to read."""
import statistics

from harness import host_loop, regions


def read(facts):
    spans, progs = facts.get("spans"), regions.of_kind(facts, "decode_step")
    if spans is None or not progs:
        return None
    t0, t1 = host_loop.quiet_window(facts)
    live = {f["program"]: f["n_active"]
            for _n, _a, _b, f in spans.named("decode_step")
            if "program" in f and f.get("n_active")}
    per_row = [f["loop_passes"] / live[f["fetched"]]
               for name in ("decode_step", "prefill_chunk", "fetch")
               for _n, _a, _b, f in spans.named(name, t0, t1)
               if "loop_passes" in f and f.get("fetched") in live]
    if not per_row:
        return None
    return (1e3 * statistics.median(p["busy_s"] for p in progs)
            / statistics.median(per_row))
