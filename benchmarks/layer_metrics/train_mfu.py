"""train step (nn/graph.fit -> nn/training.make_train_step): model FLOPs
utilization of the whole step. The benchmark's executed-FLOP count per
token (the family's count; recomputation not counted) times the tokens per
second of the traced window (batch x seq over the period between the
starts of consecutive step programs in the trace) over the chip's peak."""


def read(facts):
    t, peaks = facts.get("traced"), facts.get("peaks")
    if not t or not t.get("chips") or not peaks:
        return None
    starts = sorted(s for n, s, _d, _x in t["chips"][0]["modules"]
                    if "step" in n)
    if len(starts) < 2:
        return None
    period_s = (starts[-1] - starts[0]) / (len(starts) - 1) / 1e9
    tokens_per_s = facts["batch"] * facts["seq_len"] / period_s
    return 100.0 * facts["flops_per_token"] * tokens_per_s \
        / (peaks["flops_bf16"] * facts["device"]["count"])
