"""kernels (ops/decode_attention.py `gqa_decode`): the least time the chip
could take for the decode steps' grouped-attention kernels over the time
they took. The least is bytes over the peak B/s (a decode step's
attention is bound by memory: 6 queries meet each key and value row, 24
operations against 2 bytes an element): the family's
`gqa_decode_bytes(dims, contexts)`, for every token decoded in the traced
window the rows its query could see read once over all layers (a window
layer's are min(context, window)) plus its queries and outputs. The
contexts: of the generated tokens streamed in the traced window, as
`decode_step_roofline.py` reads its batch. The time: the summed device
time of the `gqa_decode` kernel events over the same window (only the
decode program calls the kernel), so bytes and time are both sums over
the window's decode programs. The kernel reads whole blocks of rows and
every row of a window past its fill: what it reads beyond the least
shows here as a share under 100 %. A program without the kernel, or a
family without the count, gives nothing to read."""
from harness import serve_facts, spec, xplane

KERNEL = r"gqa_decode"


def read(facts):
    peaks, tw = facts.get("peaks"), serve_facts.trace_window(facts)
    steps = serve_facts.decode_steps_traced(facts)
    if not peaks or tw is None or not steps:
        return None
    need = getattr(spec.family_of(facts["config"]), "gqa_decode_bytes", None)
    spent, calls = xplane.kernel_seconds(facts["traced"]["chips"], KERNEL)
    contexts = [ctx for ctx, first, _r in serve_facts.token_events(facts, *tw)
                if not first]
    if need is None or not calls or not contexts:
        return None
    least = need(facts["dims"], contexts) / peaks["hbm_bytes_per_s"]
    return 100.0 * least / spent
