"""model step (nn/decode.py decode): the median `decode_step` span of the
window, dispatch to the fetched slot tokens, in milliseconds."""
import statistics


def read(facts):
    spans = facts.get("spans")
    if spans is None:
        return None
    steps = spans.named("decode_step", *facts["window"])
    if not steps:
        return None
    return 1e3 * statistics.median(b - a for _n, a, b, _f in steps)
