"""The `afmoe` family: through `run.execute` at a tiny preset in both
serving traffic kinds (as `test_families.py` holds its twin), its counts
against a hand count at the published widths, the configuration against
the catalog's row, an altered token, an ignored window and an ignored
selection bias against `correct` (`test_faults.py`'s way), and the
readers of `gqa_decode_roofline` and `gqa_decode_share` on a recorded
trace."""
import json

import jax
import numpy as np
import presets
import pytest
import run
from harness import device, spec

TINY = {"model_type": "afmoe", "hidden_size": 48, "num_attention_heads": 12,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 4,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
        "sliding_window": 8, "num_dense_layers": 1, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_experts": 4,
        "num_experts_per_tok": 2, "num_shared_experts": 1,
        "route_scale": 2.448, "rope_theta": 10000, "rms_norm_eps": 1e-5,
        "vocab_size": 256, "compute_dtype": "float32",
        "param_dtype": "float32",
        "share": {"chips_per_layer": 4, "router_experts": 16,
                  "first_expert": 0},
        "seeded_weights": {"embed_gain": 1.0, "qk_gain": 2.5,
                           "router_gain": 1.0, "bsel_std": 0.3,
                           "head_gain": 2.0},
        "deployment": {"slots": 4, "max_new_tokens": 16, "page_size": 16,
                       "kv_dtype": "f32", "prefill_seq_lens": [16, 32],
                       "prefill_chunk": 16, "replicas": 1, "max_queue": 64}}
# THE TINY PRESET COMPUTES IN FLOAT32: at this size (16 experts of which 4
# are held, 2 a token) a bfloat16 program swaps an expert against the
# float32 reference in one run of four, and a swap is a quarter of the
# routed sum (seeds 2**31 + 21, 22 in bfloat16: token_gap 2.75 and 0.21);
# the path is this file's matter, the precision the chip's and
# tests/test_grouped_attention.py's. Sound float32 runs of both mixes read
# 0 and 0; a token altered by one id reads token_gap 2 and more, a window
# ignored or a bias ignored token_gap_mean 0.2 and more
LIMITS = {"token_gap": 1.2, "token_gap_mean": 0.1, "answered": 0,
          "min_sample_tokens": 4}
CELL = "serve_trinity400b_ep32_mixedlen"


@pytest.fixture(scope="module")
def family():
    return spec.family_of(TINY)


def published():
    bench = spec.load_benchmark()
    return spec.config_of(bench, spec.cell_of(bench, CELL))


def line_of(like, mix, trace, seed=2**31 + 21):
    return run.execute("c", seed, 3, trace,
                       bench=presets.bench_with("c", like), config=TINY,
                       traffic=mix, limits=LIMITS, rehearsal=True)


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
    line = line_of(like, mix, trace)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 3
    assert line["checks"]["compiles_in_window"] == [0.0, 0]
    if not trace:
        assert {"tpot_ms_p95", "setup_s"} <= set(line["metrics"])
        return
    assert {"prefill_flops", "decode_flops"} <= set(asked)
    assert "serve_mfu" in line["metrics"]
    if like == CELL:
        # the expert layer's counters ride the spans beside attention's
        assert 0 < line["metrics"]["moe_useful_rows_share"]["value"] <= 100
    # off the chip the decode step runs the kernel's `jnp` twin: no
    # `gqa_decode` event, and the two readers leave their metrics out
    assert "gqa_decode_share" not in line["metrics"]
    assert "gqa_decode_roofline" not in line["metrics"]


def served(family, seed=3, n_prompt=40, n_new=8):
    dims = family.dims_of(TINY)
    net = family.serving_net(TINY, seed, dims)
    prompt = np.random.default_rng(0).integers(0, dims["V"], n_prompt).tolist()
    toks = list(prompt)
    for _ in range(n_new):      # greedy, by the program's full forward
        probs = np.asarray(net.output(np.asarray(toks, np.int32)[None, :]))
        toks.append(int(np.argmax(probs[0, -1])))
    rec = {"id": "r0.0", "tokens": toks[n_prompt:], "max_new": n_new,
           "error": None}
    return dims, rec, prompt


def test_an_altered_token_is_not_correct(family):
    """The comparison notices a served token moved by one id."""
    from harness import serve_driver as sd

    dims, rec, prompt = served(family)
    sound = family.served_gaps([rec], {"r0": prompt}, 3, dims)
    assert sd.serve_checks([rec], sound, 0, LIMITS)["token_gap"][0] < 1.2
    low = family.served_gaps([rec], {"r0": prompt}, 3, dims, lowprec=True)
    assert low[0].shape == sound[0].shape and np.all(low[0] >= 0)
    bad = dict(rec, tokens=[(t + 1) % dims["V"] for t in rec["tokens"]])
    wrong = family.served_gaps([bad], {"r0": prompt}, 3, dims)
    assert sd.serve_checks([bad], wrong, 0, LIMITS)["token_gap"][0] > 1.2


def test_an_ignored_window_is_not_correct(monkeypatch):
    """Sliding layers served as full ones: every query past the window
    sees keys the model hides from it. The rest of the run is the run's
    own (the ring is then as long as the capacity)."""
    from deeplearning4j_tpu.models import grouped_moe

    real = grouped_moe.GroupedAttentionLayer
    monkeypatch.setattr(grouped_moe, "GroupedAttentionLayer",
                        lambda **kw: real(**dict(kw, window=0)))
    line = line_of(CELL, presets.CLOSED_MIX, 0)
    assert line["correct"] is False
    assert line["checks"]["token_gap_mean"][0] > 2 * LIMITS["token_gap_mean"]


def test_an_ignored_selection_bias_is_not_correct(monkeypatch):
    """A router that selects by its scores alone."""
    from deeplearning4j_tpu.nn.layers import moe

    real = moe.route_sigmoid_topk
    monkeypatch.setattr(moe, "route_sigmoid_topk",
                        lambda x, Wg, k, scale, bsel=None: real(x, Wg, k, scale))
    line = line_of(CELL, presets.CLOSED_MIX, 0)
    assert line["correct"] is False
    assert line["checks"]["token_gap_mean"][0] > 2 * LIMITS["token_gap_mean"]


def test_counts_at_the_published_widths(family):
    """318.5 M parameters an expert layer, 2,559.5 M held, 243.3 MB of
    cache a slot (ISSUE 37's arithmetic, redone here by hand)."""
    dims = family.dims_of(published())
    attn = 3 * 3072 * 6144 + 2 * 3072 * 1024 + 2 * 128
    assert abs(attn - 62.91e6) < 0.01e6
    expert = 3 * 3072 * 3072
    layer = attn + 4 * 3072 + 3072 * 256 + 256 + 9 * expert
    assert abs(layer - 318.5e6) < 0.1e6
    dense = attn + 4 * 3072 + 3 * 3072 * 12288
    assert abs(dense - 176.2e6) < 0.1e6
    want = dense + 7 * layer + 2 * 25024 * 3072 + 3072
    assert family.count_params(dims) == want
    assert abs(want - 2559.5e6) < 0.2e6
    # a row: 8 key-value heads of 128, key and value, bfloat16
    assert family.kv_bytes_per_token(dims) == 8 * 4096
    slot = family.cache_bytes_per_slot(dims, 17408)
    assert slot == 6 * 4096 * 4096 + 2 * 17408 * 4096 == 243_269_632
    assert 32 * slot == 7_784_628_224
    # every layer at full capacity would not fit: 570 MB a slot
    assert 8 * 17408 * 4096 == 570_425_344
    # the kernel's least bytes: every visible row once, a window layer's
    # are min(context, 4096); q and o of 48 x 128 bfloat16 a layer
    small = 8 * 2 * 48 * 128 * 2
    assert family.gqa_decode_bytes(dims, [1000, 6000]) \
        == (8 * 1000 + 2 * 6000 + 6 * 4096) * 4096 + 2 * small
    # a decode step is told the SUM of its rows' contexts: the weights but
    # the embedding's rows once, the sum's rows in the two full layers and
    # one window's at the most in the six rings
    weights = 2 * (want - 25024 * 3072)
    assert family.decode_step_min_bytes(dims, 3000) \
        == weights + 8 * 3000 * 4096
    assert family.decode_step_min_bytes(dims, 32 * 5560) \
        == weights + (2 * 32 * 5560 + 6 * 4096) * 4096
    assert family.decode_step_min_bytes(dims, 32 * 5560) \
        < weights + family.gqa_decode_bytes(dims, [5560] * 32)
    # attention's FLOPs stop growing in the window layers at 4,096 keys
    per_key = 4 * 48 * 128
    assert family.decode_flops(dims, 3000) - family.decode_flops(dims, 2000) \
        == 8 * per_key * 1000
    assert family.decode_flops(dims, 9000) - family.decode_flops(dims, 8000) \
        == 2 * per_key * 1000
    assert family.prefill_flops(dims, 1) == family.decode_flops(dims, 1)
    with pytest.raises(NotImplementedError, match="25.7 GB"):
        family.training_net(published(), 1, dims)


def test_the_configuration_is_the_catalog_row_with_the_reduced_keys_changed():
    cfg = published()
    types = (["sliding_attention"] * 3 + ["full_attention"]) * 15
    row = {"global_attn_every_n_layers": 4, "head_dim": 128,
           "hidden_act": "silu", "hidden_size": 3072,
           "intermediate_size": 12288, "layer_types": types,
           "load_balance_coeff": 5e-05, "max_position_embeddings": 262144,
           "model_type": "afmoe", "moe_intermediate_size": 3072,
           "mup_enabled": True, "n_group": 1, "num_attention_heads": 48,
           "num_dense_layers": 6, "num_expert_groups": 1, "num_experts": 256,
           "num_experts_per_tok": 4, "num_hidden_layers": 60,
           "num_key_value_heads": 8, "num_limited_groups": 1,
           "num_shared_experts": 1, "rms_norm_eps": 1e-05,
           "rope_scaling": None, "rope_theta": 10000, "route_norm": True,
           "route_scale": 2.448, "score_func": "sigmoid",
           "sliding_window": 4096, "tie_word_embeddings": False,
           "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192}
    assert set(row) <= set(cfg)
    differs = sorted(k for k, v in row.items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"]) == sorted(cfg["reduced_from"]) \
        == ["layer_types", "num_dense_layers", "num_experts",
            "num_hidden_layers", "vocab_size"]
    assert cfg["layer_types"] == types[:8] and cfg["num_hidden_layers"] == 8
    assert (cfg["num_dense_layers"], cfg["num_experts"]) == (1, 8)
    assert cfg["vocab_size"] * 8 == 200192
    share = cfg["share"]
    assert (share["chips_per_layer"], share["router_experts"],
            share["first_expert"]) == (32, 256, 0)
    assert share["chips_per_layer"] * cfg["num_experts"] == 256
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == "trinity-large-preview-ep32-l8")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    dep = cfg["deployment"]
    assert max(dep["prefill_seq_lens"]) + dep["max_new_tokens"] == 17408
    assert (dep["slots"], dep["prefill_chunk"], dep["kv_dtype"]) \
        == (32, 1024, "f32")
    assert dep["prefill_chunk"] < cfg["sliding_window"]
    assert {"mup", "norms", "qk_norm", "rotary", "window", "full_layers",
            "gate", "router"} <= set(cfg["assumed"])


def test_the_net_is_built_as_the_configuration_says(family):
    """Shapes only: the published widths, bfloat16 weights and a float32
    selection bias, a ring of 4,096 rows in the six window layers
    whatever the capacity and `capacity` rows in the two full ones."""
    from harness.weights import param_shapes

    cfg = published()
    dims = family.dims_of(cfg)
    calls = {}
    orig = family.give_weights
    family.give_weights = lambda net, seed, dims, like=None: calls.update(
        like=like)
    try:
        net = family.serving_net(cfg, 1, dims)
    finally:
        family.give_weights = orig
    like = calls["like"]
    leaves = jax.tree.leaves(like)
    assert sum(int(np.prod(l.shape)) for l in leaves) == family.count_params(dims)
    assert {l.dtype.name for l in leaves} == {"bfloat16", "float32"}
    assert {n for n, p in like.items() for a, l in p.items()
            if l.dtype.name == "float32"} == {f"blk{i}_ff" for i in range(1, 8)}
    assert like["blk7_ff"]["bsel"].shape == (256,)
    assert like["blk7_ff"]["Wg"].shape == (3072, 256)
    assert like["blk7_ff"]["We_up"].shape == (8, 3072, 3072)
    assert like["blk0_ff"]["Wdown"].shape == (12288, 3072)
    assert like["blk7_attn"]["Wq"].shape == like["blk7_attn"]["Wg"].shape \
        == (3072, 6144)
    assert like["blk7_attn"]["Wk"].shape == (3072, 1024)
    assert "b" not in like["out"] and like["out"]["W"].shape == (3072, 25024)
    for cap in (2048, 17408):
        ring = min(cap, 4096)
        specs = net.kv_cache_specs(cap)
        assert specs == {
            f"blk{i}_attn": (
                {"k_win": ((8, ring, 128), "bfloat16", ring),
                 "v_win": ((8, ring, 128), "bfloat16", ring)} if i % 4 != 3
                else {"k": ((8, cap, 128), "bfloat16"),
                      "v": ((8, cap, 128), "bfloat16")})
            for i in range(8)}
    confs = [net.conf.vertices[f"blk{i}_attn"].layer for i in range(8)]
    assert [c.window for c in confs] == [4096, 4096, 4096, 0] * 2
    assert [c.rope_theta for c in confs] == [10000.0, 10000.0, 10000.0, 0.0] * 2
    assert net.conf.vertices["embed_scaled"].scale == 3072 ** 0.5
    assert param_shapes(net).keys() == like.keys()


def test_readers_of_the_grouped_kernel_on_a_recorded_trace():
    """Three decode programs of 12 ms, each with eight kernel events of
    0.6 ms, and a prefill program between them; 32 + 32 + 16 generated
    tokens streamed in the traced window, half of them past the window."""
    family = spec.family_of(TINY)
    dims = family.dims_of(published())
    ops, modules = [], []
    for step, t0 in enumerate((0.0, 30e6, 90e6)):
        modules.append((f"jit_counted_step({step})", t0, 12e6, ""))
        for layer in range(8):
            a = t0 + layer * 1.4e6
            ops.append((f"fusion.{layer}", a, 0.8e6, "kLoop"))
            ops.append((f"gqa_decode.{layer + 1}", a + 0.8e6, 0.6e6,
                        'custom_call_target="tpu_custom_call"'))
    modules.append(("jit_counted_prefill(9)", 55e6, 30e6, ""))
    ops.append(("fusion.77", 55e6, 30e6, "kOutput"))

    def request(prompt_len, n_tokens, first_at):
        return {"prompt_len": prompt_len,
                "t_tokens": [first_at + 0.03 * i for i in range(n_tokens)]}

    # 32 requests stream a token in each of the three steps' time, 16 of
    # them none in the last; every request's first token came earlier
    records = [request(2000, 4, 99.97) for _ in range(16)] \
        + [request(9000, 3, 99.97) for _ in range(16)]
    facts = {"config": published(), "dims": dims,
             "peaks": device.PEAKS["TPU v5e"], "mono_minus_perf": 100.0,
             "load": {"records": records},
             "traced": {"chips": [{"name": "/device:TPU:0", "ops": ops,
                                   "modules": modules}],
                        "t_on": -0.01, "t_off": 0.12, "window_s": 0.13}}
    share = spec.layer_reader("gqa_decode_share")(facts)
    assert share == pytest.approx(100.0 * (24 * 0.6e-3) / (3 * 12e-3))
    roof = spec.layer_reader("gqa_decode_roofline")(facts)
    contexts = [2000 + i for i in (1, 2, 3)] * 16 + [9000 + i for i in (1, 2)] * 16
    assert len(contexts) == 80
    least = family.gqa_decode_bytes(dims, contexts) / 819e9
    assert roof == pytest.approx(100.0 * least / (24 * 0.6e-3))
    assert 0 < roof < 100
    # a program without the kernel (the parent's): nothing to read
    plain = dict(facts, traced=dict(facts["traced"], chips=[{
        "name": "/device:TPU:0", "modules": modules,
        "ops": [o for o in ops if "gqa" not in o[0]]}]))
    for name in ("gqa_decode_share", "gqa_decode_roofline"):
        assert spec.layer_reader(name)(plain) is None
        assert spec.layer_reader(name)(dict(facts, traced=None)) is None
    # another family's cell: no count of the kernel's bytes
    gpt2 = dict(facts, config=presets.GPT2)
    assert spec.layer_reader("gqa_decode_roofline")(gpt2) is None
    assert json.dumps([share, roof])
