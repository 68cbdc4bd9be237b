"""admission and slots: mean share of the decode slots in use, `n_active`
over the deployment's slots, over the window's `decode_step` spans."""


def read(facts):
    spans = facts.get("spans")
    if spans is None:
        return None
    t0, t1 = facts["window"]
    steps = spans.named("decode_step", t0, t1)
    if not steps:
        return None
    slots = facts["config"]["deployment"]["slots"]
    return 100.0 * sum(f.get("n_active", 0) for _n, _a, _b, f in steps) \
        / (slots * len(steps))
