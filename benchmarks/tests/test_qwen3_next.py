"""The `qwen3_next` family: through `run.execute` at a tiny preset in both
serving traffic kinds (as `test_families.py` holds its twin), its counts
against a hand count at the published widths, the configuration against
the catalog's row, an altered token, a dropped correction and an unturned
head against `correct` (`test_faults.py`'s way), and the readers of
`gated_delta_decode_roofline` and `gated_delta_decode_share` on a recorded
trace."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import presets
import pytest
import run
from harness import device, spec

TINY = {"model_type": "qwen3_next", "hidden_size": 48,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
        "partial_rotary_factor": 0.25, "rope_theta": 10000,
        "linear_num_key_heads": 4, "linear_num_value_heads": 8,
        "linear_key_head_dim": 16, "linear_value_head_dim": 16,
        "linear_conv_kernel_dim": 4, "num_hidden_layers": 4,
        "full_attention_interval": 4, "decoder_sparse_step": 1,
        "mlp_only_layers": [], "moe_intermediate_size": 32,
        "shared_expert_intermediate_size": 32, "num_experts": 4,
        "num_experts_per_tok": 3, "rms_norm_eps": 1e-6, "vocab_size": 256,
        "compute_dtype": "float32", "param_dtype": "float32",
        "share": {"chips_per_layer": 4, "router_experts": 16,
                  "first_expert": 0},
        "seeded_weights": {"embed_gain": 1.0, "qk_gain": 6.0,
                           "memory_tokens": [3.0, 100.0], "a_gain": 0.5,
                           "router_gain": 1.0, "head_gain": 2.0},
        "deployment": {"slots": 4, "max_new_tokens": 16, "page_size": 16,
                       "kv_dtype": "f32", "prefill_seq_lens": [16, 32],
                       "prefill_chunk": 16, "replicas": 1, "max_queue": 64}}
# THE TINY PRESET COMPUTES IN FLOAT32, as test_afmoe.py's does: at this
# size a bfloat16 program swaps an expert against the float32 reference
# now and then, and a swap is a large part of the routed sum; the path is
# this file's matter, the precision the chip's and
# tests/test_gated_deltanet.py's. Sound float32 runs of both mixes read
# 1e-4 and less; an altered token reads token_gap 2 and more, a dropped
# correction or an unturned head token_gap_mean 0.2 and more. The query
# gain is 6 here: over prompts of 4-32 tokens the one full layer's
# rotary decides little at a gain of 2 (an unturned head read
# token_gap_mean 0.03-0.06 at 2, 0.16-0.27 at 4, 0.21-0.59 at 6)
LIMITS = {"token_gap": 1.2, "token_gap_mean": 0.1, "answered": 0,
          "min_sample_tokens": 4}
CELL = "serve_qwen3next80b_ep32_longgen"


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
        assert 0 < line["metrics"]["moe_useful_rows_share"]["value"] <= 100
    # off the chip the decode step runs the kernel's `jnp` twin: no
    # `gated_delta_decode` event, and the two readers leave their metrics out
    assert "gated_delta_decode_share" not in line["metrics"]
    assert "gated_delta_decode_roofline" not in line["metrics"]


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
    assert sd.serve_checks([rec], sound, 0, LIMITS)["token_gap"][0] < 1e-3
    low = family.served_gaps([rec], {"r0": prompt}, 3, dims, lowprec=True)
    assert low[0].shape == sound[0].shape and np.all(low[0] >= 0)
    bad = dict(rec, tokens=[(t + 1) % dims["V"] for t in rec["tokens"]])
    wrong = family.served_gaps([bad], {"r0": prompt}, 3, dims)
    assert sd.serve_checks([bad], wrong, 0, LIMITS)["token_gap"][0] > 1.2


def _no_correction_chunk(q, k, v, g, beta, S, *, keep=None, **_):
    """The chunk as plain gated linear attention: S = exp(g) S + beta k v^T."""
    keep = jnp.ones(g.shape[:2]) if keep is None else keep
    g, beta = g * keep[..., None], beta * keep[..., None]

    def step(s, t):
        q_t, k_t, v_t, g_t, b_t = t
        s = jnp.exp(g_t)[..., None, None] * s + (b_t[..., None, None]
                                                 * k_t[..., :, None]
                                                 * v_t[..., None, :])
        return s, jnp.sum(q_t[..., :, None] * s, axis=-2)

    s, o = jax.lax.scan(step, S.astype(jnp.float32), tuple(
        jnp.moveaxis(a.astype(jnp.float32), 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s.astype(S.dtype)


def _no_correction_decode(S, q, k, v, a, beta, live=None):
    """The decode step as plain gated linear attention."""
    s = a[..., None, None] * S.astype(jnp.float32) + (
        beta[..., None, None] * k[..., :, None] * v[..., None, :])
    if live is not None:
        s = jnp.where(live[:, None, None, None], s, S)
    return jnp.sum(q[..., :, None] * s, axis=-2), s.astype(S.dtype)


def test_a_dropped_correction_is_not_correct(monkeypatch):
    """The delta rule served as plain gated linear attention."""
    from deeplearning4j_tpu.nn.layers import gated_deltanet

    monkeypatch.setattr(gated_deltanet, "gated_delta_chunk",
                        _no_correction_chunk)
    monkeypatch.setattr(gated_deltanet, "gated_delta_decode",
                        _no_correction_decode)
    line = line_of(CELL, presets.CLOSED_MIX, 0)
    assert line["correct"] is False
    assert line["checks"]["token_gap_mean"][0] > 2 * LIMITS["token_gap_mean"]


def test_an_unturned_head_is_not_correct(monkeypatch):
    """Rotary over the whole head of the full layer, not its first quarter."""
    from deeplearning4j_tpu.models import hybrid_moe

    real = hybrid_moe.GroupedAttentionLayer
    monkeypatch.setattr(hybrid_moe, "GroupedAttentionLayer",
                        lambda **kw: real(**dict(kw, rotary_dim=0)))
    line = line_of(CELL, presets.CLOSED_MIX, 0)
    assert line["correct"] is False
    assert line["checks"]["token_gap_mean"][0] > LIMITS["token_gap_mean"]


def test_counts_at_the_published_widths(family):
    """88,250,560 parameters a delta-rule layer, 770.9 M held, 12.88 MB
    of state and 151.0 MB of rows a slot (the configuration's `bytes`, redone
    here by hand)."""
    dims = family.dims_of(published())
    h = 2048
    gdn = (h * 8192 + h * 4096 + 2 * h * 32 + 4 * 8192 + 2 * 32 + 128
           + 4096 * h)
    assert gdn == 33_718_464
    attn = 3 * h * 4096 + 2 * h * 512 + 2 * 256
    moe = h * 512 + 3 * h * 512 + h + 16 * 3 * h * 512
    assert h * 512 + 3 * h * 512 + h == 4_196_352
    assert gdn + moe + 2 * h == 88_250_560
    want = 6 * (gdn + moe + 2 * h) + 2 * (attn + moe + 2 * h) \
        + 2 * 18992 * h + h
    assert family.count_params(dims) == want
    assert abs(want - 770.9e6) < 0.05e6
    # a full layer's row: 2 key-value heads of 256, key and value, bf16
    assert family.kv_bytes_per_token(dims) == 2 * 2048
    assert family.state_bytes_per_slot(dims) == 6 * (
        32 * 128 * 128 * 4 + 3 * 8192 * 2) == 12_877_824
    slot = family.cache_bytes_per_slot(dims, 36864)
    assert slot - family.state_bytes_per_slot(dims) == 2 * 36864 * 2048 \
        == 150_994_944
    assert 64 * slot == 10_487_857_152
    # the kernel's least bytes: each live slot's six states read and
    # written, its windows, and the step's small vectors in bfloat16
    per = 2 * 4 * 32 * 128 * 128 + 3 * 8192 * 2 \
        + (2 * 16 * 128 + 2 * 32 * 128 + 2 * 32) * 2
    assert family.gated_delta_decode_bytes(dims, 64) == 64 * 6 * per
    small = 2 * 2 * 16 * 256 * 2
    assert family.gqa_decode_bytes(dims, [1000, 6000]) \
        == 2 * 7000 * 2048 + 2 * small
    # a decode step is told the SUM of its rows' contexts: the weights but
    # the embedding's rows once, one slot's states, the sum's rows in the
    # two full layers
    weights = 2 * (want - 18992 * h)
    assert family.decode_step_min_bytes(dims, 3000) \
        == weights + 6 * per + 2 * 3000 * 2048
    assert family.decode_step_min_bytes(dims, 64 * 4000) < weights \
        + family.gated_delta_decode_bytes(dims, 64) \
        + family.gqa_decode_bytes(dims, [4000] * 64)
    # attention's FLOPs grow with the keys in the two full layers alone
    per_key = 4 * 16 * 256
    assert family.decode_flops(dims, 3000) - family.decode_flops(dims, 2000) \
        == 2 * per_key * 1000
    assert family.prefill_flops(dims, 1) == family.decode_flops(dims, 1)
    with pytest.raises(NotImplementedError, match="backward kernel"):
        family.training_net(published(), 1, dims)


def test_the_configuration_is_the_catalog_row_with_the_reduced_keys_changed():
    cfg = published()
    row = {"decoder_sparse_step": 1, "full_attention_interval": 4,
           "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
           "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
           "linear_key_head_dim": 128, "linear_num_key_heads": 16,
           "linear_num_value_heads": 32, "linear_value_head_dim": 128,
           "max_position_embeddings": 262144, "mlp_only_layers": [],
           "model_type": "qwen3_next", "moe_intermediate_size": 512,
           "norm_topk_prob": True, "num_attention_heads": 16,
           "num_experts": 512, "num_experts_per_tok": 10,
           "num_hidden_layers": 48, "num_key_value_heads": 2,
           "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
           "rope_scaling": None, "rope_theta": 10000000,
           "shared_expert_intermediate_size": 512,
           "tie_word_embeddings": False, "use_sliding_window": False,
           "vocab_size": 151936}
    assert set(row) <= set(cfg)
    differs = sorted(k for k, v in row.items() if cfg[k] != v)
    assert differs == sorted(cfg["reduced"]) == sorted(cfg["reduced_from"]) \
        == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"]) == (8, 16)
    assert cfg["vocab_size"] * 8 == 151936
    share = cfg["share"]
    assert (share["chips_per_layer"], share["router_experts"],
            share["first_expert"]) == (32, 512, 0)
    assert share["chips_per_layer"] * cfg["num_experts"] == 512
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == "qwen3-next-80b-a3b-ep32-l8")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    dep = cfg["deployment"]
    assert max(dep["prefill_seq_lens"]) + dep["max_new_tokens"] == 36864
    assert (dep["slots"], dep["prefill_chunk"], dep["kv_dtype"]) \
        == (64, 1024, "f32")
    assert {"norms", "mtp", "q_gate_layout", "rotary", "gdn_layout", "conv",
            "delta_rule", "router", "shared_expert"} <= set(cfg["assumed"])


def test_the_net_is_built_as_the_configuration_says(family):
    """Shapes only: the published widths, bfloat16 weights with float32
    decays, a state and a window a slot in the six delta-rule layers
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
    assert {(n, a) for n, p in like.items() for a, l in p.items()
            if l.dtype.name == "float32"} == {
        (f"blk{i}_gdn", a) for i in (0, 1, 2, 4, 5, 6)
        for a in ("A_log", "dt_bias")}
    assert like["blk0_gdn"]["Wqkv"].shape == (2048, 8192)
    assert like["blk0_gdn"]["conv"].shape == (4, 8192)
    assert like["blk3_attn"]["Wq"].shape == like["blk3_attn"]["Wg"].shape \
        == (2048, 4096)
    assert like["blk7_ff"]["Wg"].shape == (2048, 512)
    assert like["blk7_ff"]["We_up"].shape == (16, 2048, 512)
    assert like["blk7_ff"]["Ws_g"].shape == (2048, 1)
    assert like["out"]["W"].shape == (2048, 18992)
    for cap in (2048, 36864):
        specs = net.kv_cache_specs(cap)
        assert specs == {
            f"blk{i}_{'attn' if i % 4 == 3 else 'gdn'}": (
                {"k": ((2, cap, 256), "bfloat16"),
                 "v": ((2, cap, 256), "bfloat16")} if i % 4 == 3
                else {"S": ((32, 128, 128), "float32", "slot"),
                      "conv": ((3, 8192), "bfloat16", "slot")})
            for i in range(8)}
    attn = net.conf.vertices["blk3_attn"].layer
    assert (attn.rotary_dim, attn.rope_theta, attn.window) == (64, 1e7, 0)
    ff = net.conf.vertices["blk0_ff"].layer
    assert (ff.router, ff.shared_gate, ff.top_k, ff.n_held) == \
        ("softmax", True, 10, 16)
    assert param_shapes(net).keys() == like.keys()


def test_readers_of_the_delta_rule_kernel_on_a_recorded_trace():
    """Three decode programs of 12 ms, each with six kernel events of
    0.5 ms, and a prefill program between them; 64 + 64 + 32 generated
    tokens streamed in the traced window."""
    family = spec.family_of(TINY)
    dims = family.dims_of(published())
    ops, modules = [], []
    for step, t0 in enumerate((0.0, 30e6, 90e6)):
        modules.append((f"jit_counted_step({step})", t0, 12e6, ""))
        for layer in range(6):
            a = t0 + layer * 1.4e6
            ops.append((f"fusion.{layer}", a, 0.8e6, "kLoop"))
            ops.append((f"gated_delta_decode.{layer + 1}", a + 0.8e6, 0.5e6,
                        'custom_call_target="tpu_custom_call"'))
    modules.append(("jit_counted_prefill(9)", 55e6, 30e6, ""))
    ops.append(("fusion.77", 55e6, 30e6, "kOutput"))

    def request(prompt_len, n_tokens, first_at):
        return {"prompt_len": prompt_len,
                "t_tokens": [first_at + 0.03 * i for i in range(n_tokens)]}

    records = [request(2000, 4, 99.97) for _ in range(32)] \
        + [request(9000, 3, 99.97) for _ in range(32)]
    facts = {"config": published(), "dims": dims,
             "peaks": device.PEAKS["TPU v5e"], "mono_minus_perf": 100.0,
             "load": {"records": records},
             "traced": {"chips": [{"name": "/device:TPU:0", "ops": ops,
                                   "modules": modules}],
                        "t_on": -0.01, "t_off": 0.12, "window_s": 0.13}}
    share = spec.layer_reader("gated_delta_decode_share")(facts)
    assert share == pytest.approx(100.0 * (18 * 0.5e-3) / (3 * 12e-3))
    roof = spec.layer_reader("gated_delta_decode_roofline")(facts)
    least = family.gated_delta_decode_bytes(dims, 160 / 3) / 819e9
    assert roof == pytest.approx(100.0 * least / (18 * 0.5e-3 / 3))
    assert 0 < roof < 100
    # a program without the kernel (the parent's): nothing to read
    plain = dict(facts, traced=dict(facts["traced"], chips=[{
        "name": "/device:TPU:0", "modules": modules,
        "ops": [o for o in ops if "gated_delta" not in o[0]]}]))
    for name in ("gated_delta_decode_share", "gated_delta_decode_roofline"):
        assert spec.layer_reader(name)(plain) is None
        assert spec.layer_reader(name)(dict(facts, traced=None)) is None
    # another family's cell: no count of the kernel's bytes
    gpt2 = dict(facts, config=presets.GPT2)
    assert spec.layer_reader("gated_delta_decode_roofline")(gpt2) is None
    assert json.dumps([share, roof])
