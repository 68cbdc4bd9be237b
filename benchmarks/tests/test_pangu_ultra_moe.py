"""The `pangu_ultra_moe` family: through `run.execute` at a tiny preset in
both serving traffic kinds (as `test_families.py` holds its twin), its
counts against a hand count at the published widths, the configuration's
deployment against what the family builds, and the reader of
`moe_useful_rows_share` on a recorded span list."""
import json
import os

import jax
import numpy as np
import presets
import pytest
import run
from harness import device, spec, spans

TINY = {"model_type": "pangu_ultra_moe", "hidden_size": 64,
        "num_attention_heads": 4, "num_hidden_layers": 3,
        "first_k_dense_replace": 1, "q_lora_rank": 24, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 160, "moe_intermediate_size": 32,
        "n_routed_experts": 3, "num_experts_per_tok": 4,
        "n_shared_experts": 1, "routed_scaling_factor": 2.5,
        "rope_theta": 25600000, "rms_norm_eps": 1e-5, "vocab_size": 256,
        "share": {"router_experts": 16, "first_expert": 0},
        "compute_dtype": "bfloat16", "param_dtype": "bfloat16",
        "seeded_weights": {"embed_gain": 1.0, "qk_gain": 2.0,
                           "router_gain": 1.0, "head_gain": 2.0},
        "deployment": {"slots": 4, "max_new_tokens": 16, "page_size": 16,
                       "kv_dtype": "f32", "prefill_seq_lens": [16, 32],
                       "prefill_chunk": 16, "replicas": 1, "max_queue": 64}}
# tiny bfloat16 weights against the float32 reference: sound CPU runs of
# both mixes read token_gap <= 0.21 and token_gap_mean <= 0.02 (seeds
# 2**31 + 21 .. 24); a token altered by one id reads token_gap 2 and more
LIMITS = {"token_gap": 1.0, "token_gap_mean": 0.08, "answered": 0,
          "min_sample_tokens": 4}
CELL = "serve_pangu718b_ep16_docgen"


@pytest.fixture(scope="module")
def family():
    return spec.family_of(TINY)


def published():
    bench = spec.load_benchmark()
    return spec.config_of(bench, spec.cell_of(bench, CELL))


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("like,mix", [
    ("serve_1p3b_chat", presets.OPEN_MIX), (CELL, presets.CLOSED_MIX)],
    ids=["serve_open", "serve_closed"])
def test_family_runs_the_serving_traffic_kinds(family, monkeypatch, like, mix,
                                               trace):
    asked = []
    for name in ("prefill_flops", "decode_flops", "decode_step_min_bytes"):
        monkeypatch.setattr(family, name, lambda *a, _real=getattr(family, name),
                            _name=name: (asked.append(_name), _real(*a))[1])
    monkeypatch.setattr(run, "_peaks", lambda *_a: device.PEAKS["TPU v5e"])
    line = run.execute("c", 2**31 + 21, 3, trace,
                       bench=presets.bench_with("c", like), config=TINY,
                       traffic=mix, limits=LIMITS, rehearsal=True)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 3
    assert line["checks"]["compiles_in_window"] == [0.0, 0]
    if not trace:
        assert {"tpot_ms_p95", "setup_s"} <= set(line["metrics"])
        return
    assert {"prefill_flops", "decode_flops"} <= set(asked)
    assert "serve_mfu" in line["metrics"]
    if like == CELL:    # the new cell's own list of per-layer metrics
        share = line["metrics"]["moe_useful_rows_share"]
        assert share["unit"] == "%" and 0 < share["value"] <= 100


def test_an_altered_token_is_not_correct(family):
    """The comparison notices a served token moved by one id."""
    from harness import serve_driver as sd

    dims = family.dims_of(TINY)
    net = family.serving_net(TINY, 3, dims)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, dims["V"], 20).tolist()
    toks = list(prompt)
    for _ in range(8):      # greedy, by the program's full forward
        probs = np.asarray(net.output(np.asarray(toks, np.int32)[None, :]))
        toks.append(int(np.argmax(probs[0, -1])))
    rec = {"id": "r0.0", "tokens": toks[20:], "max_new": 8, "error": None}
    sound = family.served_gaps([rec], {"r0": prompt}, 3, dims)
    assert sd.serve_checks([rec], sound, 0, LIMITS)["token_gap"][0] < 1.0
    low = family.served_gaps([rec], {"r0": prompt}, 3, dims, lowprec=True)
    assert low[0].shape == sound[0].shape and np.all(low[0] >= 0)
    bad = dict(rec, tokens=[(t + 1) % dims["V"] for t in rec["tokens"]])
    wrong = family.served_gaps([bad], {"r0": prompt}, 3, dims)
    assert sd.serve_checks([bad], wrong, 0, LIMITS)["token_gap"][0] > 1.0


def test_counts_at_the_published_widths(family):
    """4,919 M parameters as held, 5,760 cache bytes a token (ISSUE 31's
    arithmetic, redone here by hand)."""
    dims = family.dims_of(published())
    attn = (7680 * 1536 + 1536 + 1536 * 128 * 192 + 7680 * 576 + 512
            + 512 * 128 * 256 + 128 * 128 * 7680)
    dense = attn + 4 * 7680 + 3 * 7680 * 18432
    expert = attn + 4 * 7680 + 7680 * 256 + 17 * 3 * 7680 * 2048
    want = dense + 4 * expert + 2 * 19200 * 7680 + 7680
    assert family.count_params(dims) == want
    assert abs(want - 4919e6) / 4919e6 < 1e-3
    assert family.kv_bytes_per_token(dims) == 5 * 1152 == 5760
    # a decode step reads every held weight but the embedding's rows
    assert family.decode_step_min_bytes(dims, 0) == 2 * (want - 19200 * 7680)
    assert family.decode_step_min_bytes(dims, 1000) \
        - family.decode_step_min_bytes(dims, 0) == 1000 * 5760
    # a decoded token attends in the latent space: 2 x 128 heads x
    # (576 for the scores + 512 for the weighted sum) a key, a layer ...
    assert family.decode_flops(dims, 4000) - family.decode_flops(dims, 1000) \
        == 5 * 2 * 128 * 3000 * (576 + 512)
    # ... a prompt's token against expanded keys and values: (192 + 128)
    # a key, its own row's expansion once
    per_key = (family.prefill_flops(dims, 4001) / 4001
               - family.prefill_flops(dims, 2001) / 2001) / 1000
    assert per_key == pytest.approx(5 * 2 * 128 * (192 + 128), rel=1e-3)
    # the routed experts a uniform router sends here: 8 x 16 / 256 a token
    twice = dict(dims, held=32)
    assert family.decode_flops(twice, 1000) - family.decode_flops(dims, 1000) \
        == 4 * 0.5 * (2 * 3 * 7680 * 2048)
    with pytest.raises(NotImplementedError, match="54.5 GB"):
        family.training_net(published(), 1, dims)


def test_the_configuration_is_the_published_one_cut_as_it_says():
    cfg = published()
    row = {"attention_bias": False, "first_k_dense_replace": 3,
           "hidden_act": "silu", "hidden_size": 7680,
           "intermediate_size": 18432, "kv_lora_rank": 512,
           "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
           "moe_intermediate_size": 2048, "n_routed_experts": 256,
           "n_shared_experts": 1, "norm_topk_prob": True,
           "num_attention_heads": 128, "num_experts_per_tok": 8,
           "num_hidden_layers": 61, "num_key_value_heads": 128,
           "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
           "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
           "rms_norm_eps": 1e-05, "rope_theta": 25600000,
           "routed_scaling_factor": 2.5, "sandwich_norm": True,
           "tie_word_embeddings": False, "v_head_dim": 128,
           "vocab_size": 153600}
    differs = sorted(k for k, v in row.items() if cfg.get(k) != v)
    assert differs == sorted(cfg["reduced"]) == sorted(cfg["reduced_from"])
    assert cfg["share"]["router_experts"] == row["n_routed_experts"]
    assert cfg["share"]["chips_sharing_a_layer"] * cfg["n_routed_experts"] == 256
    assert cfg["vocab_size"] * 8 == row["vocab_size"]
    dep = cfg["deployment"]
    # the cache as the engine will plan it: 64 slots of 4,608 positions
    assert max(dep["prefill_seq_lens"]) + dep["max_new_tokens"] == 4608
    assert dep["slots"] * 4608 * 5760 == 1_698_693_120


def test_the_net_is_built_as_the_configuration_says(family):
    """Shapes only: the published widths, the share's experts, bfloat16."""
    from harness.weights import param_shapes
    from deeplearning4j_tpu.models.latent_moe import latent_moe_lm  # noqa: F401

    cfg = published()
    dims = family.dims_of(cfg)
    calls = {}

    def fake(net, seed, dims, like=None):
        calls["like"] = like

    orig, family.give_weights = family.give_weights, fake
    try:
        net = family.serving_net(cfg, 1, dims)
    finally:
        family.give_weights = orig
    like = calls["like"]
    leaves = jax.tree.leaves(like)
    assert sum(int(np.prod(l.shape)) for l in leaves) == family.count_params(dims)
    assert {l.dtype.name for l in leaves} == {"bfloat16"}
    assert like["blk1_ff"]["Wg"].shape == (7680, 256)
    assert like["blk4_ff"]["We_gate"].shape == (16, 7680, 2048)
    assert "Wgate" in like["blk0_ff"] and "b" not in like["out"]
    specs = net.kv_cache_specs(4608)
    assert specs == {f"blk{i}_attn": {"ckv": ((4608, 512), "bfloat16"),
                                      "kpe": ((4608, 64), "bfloat16")}
                     for i in range(5)}
    assert param_shapes(net).keys() == like.keys()


def test_reader_of_moe_useful_rows_share_on_recorded_spans():
    read = spec.layer_reader("moe_useful_rows_share")
    log = spans.SpanLog()

    def span(name, t1, **fields):
        log.spans.append((name, t1 - 0.01, t1, {"name": name, **fields}))

    span("decode_step", 1.0, moe_pairs=30, moe_rows=128, moe_max_load=5)
    span("prefill_chunk", 2.0, moe_pairs=500, moe_rows=1024, moe_max_load=50)
    span("decode_step", 3.0, moe_pairs=0, moe_rows=0, moe_max_load=0)
    span("verify_step", 3.5, moe_pairs=9, moe_rows=9)        # not read
    span("decode_step", 6.0, moe_pairs=128, moe_rows=128)    # after the stop
    span("decode_step", 9.0, moe_pairs=1, moe_rows=1)        # past the window
    facts = {"spans": log, "window": (0.0, 8.0), "traced": {"t_off": 5.0}}
    assert read(facts) == pytest.approx(100.0 * 530 / 1152)
    assert read(dict(facts, traced=None)) == pytest.approx(100.0 * 658 / 1280)
    # a program without the counters (the parent's), or no spans at all
    plain = spans.SpanLog()
    plain.spans.append(("decode_step", 0.9, 1.0, {"n_active": 3}))
    assert read(dict(facts, spans=plain)) is None
    assert read(dict(facts, spans=None)) is None
    assert json.dumps(read(facts))
