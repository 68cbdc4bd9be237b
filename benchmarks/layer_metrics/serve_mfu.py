"""whole serving step: model FLOPs of all prompt and generated tokens
processed in the traced window (the family's counts: a first token pays its
prompt's prefill, a later one a decode against its context) over the
window and the chip's peak."""
from harness import serve_facts


def read(facts):
    peaks, tw = facts.get("peaks"), serve_facts.trace_window(facts)
    if not peaks or tw is None:
        return None
    work = serve_facts.work_flops(facts, *tw)
    if work <= 0.0:
        return None
    return 100.0 * work / (tw[1] - tw[0]) / (
        peaks["flops_bf16"] * facts["device"]["count"])
