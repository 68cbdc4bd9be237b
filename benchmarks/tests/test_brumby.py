"""The `brumby` family: through `run.execute` at a tiny preset in both
serving traffic kinds (as `test_families.py` holds its twin), its counts
against a hand count at the published widths, the configuration against
the catalog's row, an altered token and a stale state against `correct`
(`test_faults.py`'s way), and the readers of `retention_decode_roofline`
and `retention_decode_share` on a recorded trace."""
import json

import jax
import numpy as np
import presets
import pytest
import run
from harness import device, spec

TINY = {"model_type": "brumby", "hidden_size": 64, "num_attention_heads": 10,
        "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 3,
        "intermediate_size": 160, "vocab_size": 256, "rope_theta": 1000000,
        "rms_norm_eps": 1e-6, "compute_dtype": "bfloat16",
        "param_dtype": "bfloat16", "state_dtype": "float32",
        "seeded_weights": {"embed_gain": 1.0, "gate_bias": 4.0,
                           "gate_bias_step": 0.5, "gate_gain": 1.0,
                           "out_gain": 1.0, "head_gain": 2.0},
        "deployment": {"slots": 4, "max_new_tokens": 16, "page_size": 16,
                       "kv_dtype": "f32", "prefill_seq_lens": [16, 32],
                       "prefill_chunk": 16, "replicas": 1, "max_queue": 64}}
# tiny bfloat16 weights against the float32 reference: sound CPU runs of
# both mixes read token_gap <= 0.25 and token_gap_mean <= 0.03 (seeds
# 2**31 + 21 .. 24); a token altered by one id reads token_gap 2 and
# more, a server that never resets a slot's state token_gap_mean 0.2 and
# more
LIMITS = {"token_gap": 1.0, "token_gap_mean": 0.08, "answered": 0,
          "min_sample_tokens": 4}
CELL = "serve_brumby14b_l8_longdoc"


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
    # off the chip the decode step runs the kernel's `jnp` twin: no
    # `retention_decode` event, and the two readers leave their metrics out
    assert "retention_decode_share" not in line["metrics"]


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
    assert sd.serve_checks([rec], sound, 0, LIMITS)["token_gap"][0] < 1.0
    low = family.served_gaps([rec], {"r0": prompt}, 3, dims, lowprec=True)
    assert low[0].shape == sound[0].shape and np.all(low[0] >= 0)
    bad = dict(rec, tokens=[(t + 1) % dims["V"] for t in rec["tokens"]])
    wrong = family.served_gaps([bad], {"r0": prompt}, 3, dims)
    assert sd.serve_checks([bad], wrong, 0, LIMITS)["token_gap"][0] > 1.0


def test_a_stale_state_is_not_correct(monkeypatch):
    """A server that never zeroes a slot's state for its next tenant: the
    first tenants of the four slots are served soundly, every later one
    reads the sum its slot's last tenant left. The rest of the run is the
    run's own."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.layers import power_retention

    monkeypatch.setattr(power_retention, "_starts", lambda step: jnp.zeros(
        step.positions.shape[0], bool))
    line = line_of(CELL, presets.CLOSED_MIX, 0)
    assert line["correct"] is False
    assert line["checks"]["token_gap_mean"][0] > 2 * LIMITS["token_gap_mean"]


def test_counts_at_the_published_widths(family):
    """330.4 M parameters a layer, 4,198.6 M held, 34.08 MB of state a
    slot a layer by the algorithm's pairs and 34.34 MB as held (ISSUE
    35's arithmetic, redone here by hand)."""
    dims = family.dims_of(published())
    layer = (2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 + 8 + 2 * 128
             + 3 * 5120 * 17408 + 2 * 5120)
    assert abs(layer - 330.3e6) < 0.1e6
    want = 8 * layer + 2 * 151936 * 5120 + 5120
    assert family.count_params(dims) == want
    assert abs(want - 4198e6) < 1e6
    assert family.kv_bytes_per_token(dims) == 0
    assert family.pairs(dims) == 128 * 129 // 2 == 8256
    a_layer = dict(dims, L=1)
    assert 4 * family.state_values_per_slot(a_layer, 8256) == 34_080_768
    assert family.state_bytes_per_slot(a_layer) == 34_344_960
    assert family.state_bytes_per_slot(dims) * 16 == 4_396_154_880
    # the kernel's least bytes go with the live slots: the pairs read and
    # written in float32, and a step's small vectors
    small = 8 * 4 * (2 * 40 * 128 + 2 * 8 * 128 + 8 + 40)
    assert family.retention_decode_bytes(dims, 16) \
        == 16 * (2 * 8 * 34_080_768 + small)
    # a decode step is told live TOKENS, so it counts one slot's state
    # whatever it is told: every held weight but the embedding's rows
    # once, in bfloat16
    assert family.decode_step_min_bytes(dims, 0) \
        == family.decode_step_min_bytes(dims, 50_000) \
        == 2 * (want - 151936 * 5120) + family.retention_decode_bytes(dims, 1)
    # retention is the attention form while that is cheaper (a score and
    # a weighted value a key a query head), then the recurrence, flat
    assert family.decode_flops(dims, 2000) - family.decode_flops(dims, 1000) \
        == 8 * 4 * 40 * 128 * 1000
    assert family.decode_flops(dims, 9000) == family.decode_flops(dims, 6000)
    assert family.decode_flops(dims, 9000) - family.decode_flops(dims, 0) \
        == 8 * (3 * 8 + 2 * 40) * 129 * 8256
    assert family.prefill_flops(dims, 1) == family.decode_flops(dims, 1)
    with pytest.raises(NotImplementedError, match="24 GB"):
        family.training_net(published(), 1, dims)


def test_the_configuration_is_the_catalog_row_cut_in_depth_alone():
    cfg = published()
    row = {"attention_bias": False, "head_dim": 128, "hidden_act": "silu",
           "hidden_size": 5120, "intermediate_size": 17408,
           "max_position_embeddings": 32768, "max_window_layers": 40,
           "model_type": "brumby", "num_attention_heads": 40,
           "num_hidden_layers": 40, "num_key_value_heads": 8,
           "rms_norm_eps": 1e-06, "rope_scaling": None,
           "rope_theta": 1000000, "sliding_window": None,
           "tie_word_embeddings": False, "use_sliding_window": False,
           "vocab_size": 151936}
    assert set(row) <= set(cfg)
    differs = sorted(k for k, v in row.items() if cfg[k] != v)
    assert differs == cfg["reduced"] == sorted(cfg["reduced_from"]) \
        == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 8
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == "brumby-14b-base-l8")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    dep = cfg["deployment"]
    assert max(dep["prefill_seq_lens"]) + dep["max_new_tokens"] == 9216
    assert (dep["slots"], dep["prefill_chunk"], dep["kv_dtype"]) \
        == (16, 1024, "f32")
    assert cfg["state_dtype"] == "float32"
    # a gate bias bfloat16 holds exactly (families/brumby.py)
    gains = cfg["seeded_weights"]
    biases = [gains["gate_bias"] + gains["gate_bias_step"] * (c - 3.5)
              for c in range(8)]
    assert biases[0] == 4.75 and biases[-1] == 8.25
    assert all(b * 16 % 1 == 0 for b in biases)


def test_the_net_is_built_as_the_configuration_says(family):
    """Shapes only: the published widths, bfloat16 weights, a float32
    state a slot whatever the capacity."""
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
    assert {l.dtype.name for l in leaves} == {"bfloat16"}
    assert like["blk7_ret"]["Wq"].shape == (5120, 5120)
    assert like["blk7_ret"]["Wk"].shape == (5120, 1024)
    assert like["blk7_ret"]["Wg"].shape == (5120, 8)
    assert like["blk0_ff"]["Wdown"].shape == (17408, 5120)
    assert "b" not in like["out"] and like["out"]["W"].shape == (5120, 151936)
    for cap in (1024, 9216):
        assert net.kv_cache_specs(cap) == {
            f"blk{i}_ret": {"s": ((8, 128, 8320), "float32", "slot"),
                            "z": ((8, 8320), "float32", "slot")}
            for i in range(8)}
    assert param_shapes(net).keys() == like.keys()


def test_readers_of_the_retention_kernel_on_a_recorded_trace():
    """Three decode programs of 20 ms, each with eight kernel events of
    1.5 ms, and a prefill program between them; 16 + 16 + 8 generated
    tokens streamed in the traced window."""
    family = spec.family_of(TINY)
    dims = family.dims_of(published())
    ops, modules = [], []
    for step, t0 in enumerate((0.0, 30e6, 90e6)):
        modules.append((f"jit_counted_step({step})", t0, 20e6, ""))
        for layer in range(8):
            a = t0 + layer * 2.4e6
            ops.append((f"fusion.{layer}", a, 0.8e6, "kLoop"))
            ops.append((f"retention_decode.{layer + 1}", a + 0.8e6, 1.5e6,
                        'custom_call_target="tpu_custom_call"'))
    modules.append(("jit_counted_prefill(9)", 55e6, 30e6, ""))
    ops.append(("fusion.77", 55e6, 30e6, "kOutput"))

    def request(n_tokens, first_at):
        return {"prompt_len": 2000,
                "t_tokens": [first_at + 0.03 * i for i in range(n_tokens)]}

    # 16 requests stream a token in each of the three steps' time, 8 of
    # them none in the last; every request's first token came earlier
    records = [request(4, 99.97) for _ in range(8)] \
        + [request(3, 99.97) for _ in range(8)]
    facts = {"config": published(), "dims": dims,
             "peaks": device.PEAKS["TPU v5e"], "mono_minus_perf": 100.0,
             "load": {"records": records},
             "traced": {"chips": [{"name": "/device:TPU:0", "ops": ops,
                                   "modules": modules}],
                        "t_on": -0.01, "t_off": 0.12, "window_s": 0.13}}
    share = spec.layer_reader("retention_decode_share")(facts)
    assert share == pytest.approx(100.0 * (24 * 1.5e-3) / (3 * 20e-3))
    roof = spec.layer_reader("retention_decode_roofline")(facts)
    least = family.retention_decode_bytes(dims, 40 / 3) / 819e9
    assert roof == pytest.approx(100.0 * least / (8 * 1.5e-3))
    assert 0 < roof < 100
    # a program without the kernel (the parent's): nothing to read
    plain = dict(facts, traced=dict(facts["traced"], chips=[{
        "name": "/device:TPU:0", "modules": modules,
        "ops": [o for o in ops if "retention" not in o[0]]}]))
    for name in ("retention_decode_share", "retention_decode_roofline"):
        assert spec.layer_reader(name)(plain) is None
        assert spec.layer_reader(name)(dict(facts, traced=None)) is None
    # another family's cell: no count of the kernel's bytes
    gpt2 = dict(facts, config=presets.GPT2)
    assert spec.layer_reader("retention_decode_roofline")(gpt2) is None
    assert json.dumps([share, roof])
