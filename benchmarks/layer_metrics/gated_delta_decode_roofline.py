"""kernels (ops/gated_delta.py `gated_delta_decode`): the least time the
chip could take for a decode step's delta-rule kernels over the time they
took. The least is bytes over the peak B/s (the pass is bound by memory:
7 operations a state entry against 8 bytes): the family's
`gated_delta_decode_bytes(dims, live slots a step)`, every live slot's
state read once and written once over the delta-rule layers plus its
window and the step's small vectors. Live slots a step: the generated
tokens streamed in the traced window over the decode programs in it, as
`decode_step_roofline.py` reads its batch. The time: the summed device
time of the `gated_delta_decode` kernel events over the same decode
programs (only the decode program calls the kernel). A program without
the kernel, or a family without the count, gives nothing to read."""
from harness import serve_facts, spec, xplane

KERNEL = r"gated_delta_decode"


def read(facts):
    peaks, tw = facts.get("peaks"), serve_facts.trace_window(facts)
    steps = serve_facts.decode_steps_traced(facts)
    if not peaks or tw is None or not steps:
        return None
    need = getattr(spec.family_of(facts["config"]), "gated_delta_decode_bytes",
                   None)
    spent, calls = xplane.kernel_seconds(facts["traced"]["chips"], KERNEL)
    decoded = sum(1 for e in serve_facts.token_events(facts, *tw) if not e[1])
    if need is None or not calls or not decoded:
        return None
    least = need(facts["dims"], decoded / len(steps)) / peaks["hbm_bytes_per_s"]
    return 100.0 * least / (spent / len(steps))
