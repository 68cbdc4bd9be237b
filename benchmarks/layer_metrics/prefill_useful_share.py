"""model step (nn/decode.py prefill): prompt tokens over bucket positions
of the window's `prefill_chunk` spans, sum of `n_real` over sum of the
bucket length `bucket[1]`: what is left of a prefill once its padding is
taken away. Counts, so it repeats exactly for one mix."""


def read(facts):
    spans = facts.get("spans")
    if spans is None:
        return None
    chunks = [f for _n, _a, _b, f in
              spans.named("prefill_chunk", *facts["window"]) if "n_real" in f]
    if not chunks:
        return None
    return 100.0 * sum(f["n_real"] for f in chunks) \
        / sum(f["bucket"][1] for f in chunks)
