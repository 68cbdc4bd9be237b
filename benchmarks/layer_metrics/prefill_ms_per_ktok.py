"""model step (nn/decode.py prefill): the window's `prefill_chunk` span
time over the prompt kilotokens of the requests whose first token came in
the window."""


def read(facts):
    spans = facts.get("spans")
    if spans is None:
        return None
    t0, t1 = facts["window"]
    chunks = spans.named("prefill_chunk", t0, t1)
    shift = facts["mono_minus_perf"]
    toks = sum(r["prompt_len"] for r in facts["load"]["records"]
               if r["t_tokens"] and t0 + shift <= r["t_tokens"][0] <= t1 + shift)
    if not chunks or not toks:
        return None
    return 1e3 * sum(b - a for _n, a, b, _f in chunks) / (toks / 1e3)
