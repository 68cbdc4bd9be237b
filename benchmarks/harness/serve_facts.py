"""What the serving readers share: the load generator's records laid on
the server process's clock, and the traced part of the window."""

from __future__ import annotations

from harness import spec, xplane

DECODE_MODULE = r"counted_step"


def trace_window(facts):
    """(t_on, t_off) of the trace on the load generator's monotonic clock,
    or None."""
    t = facts.get("traced")
    if not t or t.get("t_on") is None:
        return None
    shift = facts["mono_minus_perf"]
    return t["t_on"] + shift, t["t_off"] + shift


def records(facts):
    return facts["load"]["records"]


def token_events(facts, a, b):
    """(context length, request) for every token streamed in [a, b] on the
    monotonic clock; the first token of a request is its prefill's."""
    out = []
    for r in records(facts):
        for i, t in enumerate(r["t_tokens"]):
            if a <= t <= b:
                out.append((r["prompt_len"] + i, i == 0, r))
    return out


def decode_steps_traced(facts):
    t = facts.get("traced")
    if not t or not t.get("chips"):
        return []
    return xplane.module_durations(t["chips"], DECODE_MODULE)


def work_flops(facts, a, b) -> float:
    """Model FLOPs of the prompt and generated tokens whose token came in
    [a, b], by the counts of the configuration's family: a request's
    first token pays its whole prefill."""
    family, dims = spec.family_of(facts["config"]), facts["dims"]
    total = 0.0
    for ctx, first, r in token_events(facts, a, b):
        total += (family.prefill_flops(dims, r["prompt_len"]) if first
                  else family.decode_flops(dims, ctx))
    return total
