"""input pipeline (data/pipeline.py): the share of the traced window that
the step loop spent in `input_wait` spans, waiting for the next batch."""


def read(facts):
    spans, t = facts.get("spans"), facts.get("traced") or {}
    if spans is None or t.get("t_on") is None:
        return None
    t0, t1 = t["t_on"], t["t_off"]
    waits = spans.named("input_wait", t0, t1)
    if not waits:
        return None
    return 100.0 * sum(min(b, t1) - max(a, t0) for _n, a, b, _f in waits) \
        / (t1 - t0)
