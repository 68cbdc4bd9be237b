"""kernels (decode step: ops/decode_attention.py + weight matmuls): the
least time a decode step could take over the median device time of the
decode program in the trace. The least is max(bytes / peak B/s, FLOPs /
peak FLOP/s) of what the algorithm needs, from shapes (the family's counts):
the weights once at the configuration's compute precision and every live
cached row once, however the program stores or walks them. It is bound by
memory."""
import statistics

from harness import serve_facts, spec


def read(facts):
    peaks, tw = facts.get("peaks"), serve_facts.trace_window(facts)
    steps = serve_facts.decode_steps_traced(facts)
    if not peaks or tw is None or not steps:
        return None
    events = [e for e in serve_facts.token_events(facts, *tw) if not e[1]]
    if not events:
        return None
    family, dims = spec.family_of(facts["config"]), facts["dims"]
    live = sum(ctx for ctx, _first, _r in events) / len(steps)
    batch = len(events) / len(steps)
    need_bytes = family.decode_step_min_bytes(dims, live)
    need_flops = batch * family.decode_flops(dims, live / max(batch, 1e-9))
    least = max(need_bytes / peaks["hbm_bytes_per_s"],
                need_flops / peaks["flops_bf16"])
    return 100.0 * least / statistics.median(steps)
