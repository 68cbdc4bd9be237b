"""expert layer (nn/layers/moe.py): the (token, held selected expert)
pairs the steps had to compute over the expert rows their programs did
compute for them, `moe_pairs` over `moe_rows` of the window's
`decode_step` and `prefill_chunk` spans: what is left of the dispatch
once its padding (a round's empty rows) is taken away. Read up to the
trace's stop (`harness/host_loop.quiet_window`). Counts. A program that
does not count (no expert layer, or one from before the counters) gives
nothing to read."""
from harness import host_loop


def read(facts):
    spans = facts.get("spans")
    if spans is None:
        return None
    t0, t1 = host_loop.quiet_window(facts)
    steps = [f for name in ("decode_step", "prefill_chunk")
             for _n, _a, _b, f in spans.named(name, t0, t1) if "moe_rows" in f]
    rows = sum(f["moe_rows"] for f in steps)
    if not rows:
        return None
    return 100.0 * sum(f["moe_pairs"] for f in steps) / rows
