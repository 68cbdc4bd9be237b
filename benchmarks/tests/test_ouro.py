"""The `ouro` family: through `run.execute` at a tiny preset in both serving
traffic kinds (as `test_families.py` holds its twin), its counts against a
hand count at the published widths, the configuration against the
catalog's row, an altered token, the float8 control and the reference's
three faults against `correct`, and the loop's two readers on a written
record."""
import jax
import numpy as np
import presets
import pytest
import run
from harness import device, spec

TINY = {"model_type": "ouro", "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 96,
        "layer_types": ["full_attention"] * 3, "num_hidden_layers": 3,
        "total_ut_steps": 3, "early_exit_threshold": 1.0,
        "use_sliding_window": False, "rms_norm_eps": 1e-6,
        "rope_theta": 1000000, "vocab_size": 256,
        "compute_dtype": "float32", "param_dtype": "float32",
        "seeded_weights": {"embed_gain": 1.0, "q_gain": 2.0,
                           "head_gain": 2.0},
        "deployment": {"slots": 4, "max_new_tokens": 16, "page_size": 16,
                       "kv_dtype": "f32", "prefill_seq_lens": [16, 32],
                       "prefill_chunk": 16, "replicas": 1, "max_queue": 64}}
# THE TINY PRESET COMPUTES IN FLOAT32, as test_qwen3_next.py's does: the
# path is this file's matter, the precision the chip's. Sound float32 runs
# read 1e-4 and less; an altered token reads token_gap 1.5 and more, each
# fault of the reference token_gap_mean 0.2 and more
LIMITS = {"token_gap": 1.0, "token_gap_mean": 0.1, "answered": 0,
          "min_sample_tokens": 4}
CELL = "serve_ouro2p6b_reasoning"


@pytest.fixture(scope="module")
def family():
    return spec.family_of(TINY)


def published():
    bench = spec.load_benchmark()
    return spec.config_of(bench, spec.cell_of(bench, CELL))


def line_of(like, mix, trace, seed=2**31 + 23):
    return run.execute("c", seed, 3, trace,
                       bench=presets.bench_with("c", like), config=TINY,
                       traffic=mix, limits=LIMITS, rehearsal=True)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("like,mix", [
    ("serve_1p3b_chat", presets.OPEN_MIX), (CELL, presets.CLOSED_MIX)],
    ids=["serve_open", "serve_closed"])
def test_family_runs_the_serving_traffic_kinds(family, monkeypatch, like, mix,
                                               trace):
    monkeypatch.setattr(run, "_peaks", lambda *_a: device.PEAKS["TPU v5e"])
    line = line_of(like, mix, trace)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 3
    assert line["checks"]["compiles_in_window"] == [0.0, 0]
    if not trace:
        # the cell reports tokens/s, not tpot (BENCHMARK.json's why)
        rate = "serve_tokens_per_s" if like == CELL else "tpot_ms_p95"
        assert {rate, "setup_s"} <= set(line["metrics"])
        return
    if like != CELL:
        assert "serve_mfu" in line["metrics"]
    # off the chip the steps run the kernels' `jnp` twins: no kernel event,
    # and the kernels' readers leave their metrics out
    assert "gqa_decode_roofline" not in line["metrics"]


class _Spans:
    """The span log's `named` over a list of (name, t0, t1, fields)."""

    def __init__(self, spans):
        self.spans = spans

    def named(self, name, t0=float("-inf"), t1=float("inf")):
        return [s for s in self.spans if s[0] == name and t0 <= s[2] <= t1]


def test_the_loop_readers(monkeypatch):
    """`decode_loop_ms` reads the region `loop` of the decode programs
    (the median, in ms), `decode_pass_ms` a decode program's time over
    the passes a live row of it ran: `loop_passes` over `n_active`, the
    counters of program n found on the span that fetched it. A program
    with no loop gives nothing to read."""
    from harness import host_loop, regions

    progs = [{"kind": "decode_step", "span": {}, "busy_s": b,
              "regions": {"loop": r, "attention": 0.01}}
             for b, r in ((0.030, 1e-5), (0.032, 2e-5), (0.040, 3e-5))]
    monkeypatch.setattr(regions, "of_kind", lambda facts, kind: (
        progs if kind == "decode_step" else []))
    monkeypatch.setattr(host_loop, "quiet_window", lambda facts: (0.0, 9.0))
    spans = _Spans([
        ("decode_step", 1.0, 1.1, {"program": 7, "n_active": 3}),
        ("decode_step", 2.0, 2.1, {"program": 8, "n_active": 4,
                                   "fetched": 7, "loop_passes": 12}),
        ("prefill_chunk", 3.0, 3.1, {"program": 9, "fetched": 8,
                                     "loop_passes": 16}),
        ("fetch", 4.0, 4.1, {"fetched": 9, "loop_passes": 4})])
    facts = {"spans": spans}
    loop_ms = spec.layer_reader("decode_loop_ms")(facts)
    pass_ms = spec.layer_reader("decode_pass_ms")(facts)
    assert loop_ms == pytest.approx(0.02)
    assert pass_ms == pytest.approx(32.0 / 4)
    for p in progs:
        del p["regions"]["loop"]
    assert spec.layer_reader("decode_loop_ms")(facts) is None
    assert spec.layer_reader("decode_pass_ms")(
        {"spans": _Spans(spans.spans[:1])}) is None


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


def test_an_altered_token_the_control_and_the_faults_are_not_correct(family):
    """The comparison notices a served token moved by one id, and each of
    the reference's faults (one pass, a cache shared by the passes, the
    final norm after the last pass only) read in the program's place; the
    float8 control's reading is a gap a token."""
    from harness import serve_driver as sd

    dims, rec, prompt = served(family)
    sound = family.served_gaps([rec], {"r0": prompt}, 3, dims)
    assert sd.serve_checks([rec], sound, 0, LIMITS)["token_gap"][0] < 1e-3
    low = family.served_gaps([rec], {"r0": prompt}, 3, dims, lowprec=True)
    assert low[0].shape == sound[0].shape and np.all(low[0] >= 0)
    bad = dict(rec, tokens=[(t + 1) % dims["V"] for t in rec["tokens"]])
    wrong = family.served_gaps([bad], {"r0": prompt}, 3, dims)
    assert sd.serve_checks([bad], wrong, 0, LIMITS)["token_gap"][0] > 1.0
    ref = family.ref
    for fault in ref.FAULTS:
        gaps = family.served_gaps([rec], {"r0": prompt}, 3, dims, fault=fault)
        checks = sd.serve_checks([rec], gaps, 0, LIMITS)
        assert checks["token_gap_mean"][0] > LIMITS["token_gap_mean"], fault


def test_the_reference_pieces_are_its_forward(family):
    """`served_gaps` runs the reference a block at a time for each pass,
    a row padded at its end; at the tiny size that is `ref.forward`."""
    dims, rec, prompt = served(family, seed=5)
    W = family.reference_weights(spec_key(5), dims)
    seq = np.asarray(prompt + rec["tokens"][:-1], np.int32)
    lg = np.asarray(family.ref.forward(W, seq, dims))
    at = np.arange(len(prompt) - 1, len(seq))
    want = lg[at].max(-1) - lg[at, rec["tokens"]]
    got = family.served_gaps([rec], {"r0": prompt}, 5, dims)[0]
    np.testing.assert_allclose(got, want, atol=1e-4)


def spec_key(seed):
    from harness.weights import seed_key

    return seed_key(seed)


def test_counts_at_the_published_widths(family):
    """51.39 M parameters a block, 2,667,972,608 held, 1,572,864 B of rows
    a token and 8.05 GB of cache over four slots (the configuration's
    `bytes`, redone here by hand)."""
    dims = family.dims_of(published())
    h, F, V = 2048, 5632, 49152
    block = 4 * h * h + 3 * h * F + 4 * h
    assert block == 51_388_416
    want = 48 * block + 2 * V * h + h
    assert family.count_params(dims) == want == 2_667_972_608
    # the published count holds the exit gate's [2048 -> 1] and its bias
    assert want + h + 1 == 2_667_974_657
    assert family.kv_bytes_per_token(dims) == 4 * 48 * 2 * 16 * 128 * 2 \
        == 1_572_864
    assert 4 * family.cache_bytes_per_slot(dims, 1280) == 8_053_063_680
    # a decode step reads the blocks once a pass, the head once, and the
    # live rows of every (layer, pass)
    weights = 2 * (4 * 48 * block + h + h * V)
    assert family.decode_step_min_bytes(dims, 1900) \
        == weights + 4 * 48 * 1900 * 8192
    assert abs(family.decode_step_min_bytes(dims, 4 * 475) - 22.92e9) < 0.01e9
    small = 4 * 48 * 2 * 16 * 128 * 2
    assert family.gqa_decode_bytes(dims, [100, 400]) \
        == 4 * 48 * 500 * 8192 + 2 * small
    assert family._rows_read(dims, 10) == 1920
    per_key = 4 * 16 * 128
    assert family.decode_flops(dims, 300) - family.decode_flops(dims, 200) \
        == 4 * 48 * per_key * 100
    assert abs(family.prefill_flops(dims, 256) - 5.10e12) < 0.01e12
    assert family.prefill_flops(dims, 1) == family.decode_flops(dims, 1)
    with pytest.raises(NotImplementedError, match="exit gate"):
        family.training_net(published(), 1, dims)
    with pytest.raises(ValueError, match="exit gate"):
        family.dims_of(dict(published(), early_exit_threshold=0.9))


def test_the_configuration_is_the_catalog_row_unchanged():
    cfg = published()
    row = {"head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
           "intermediate_size": 5632,
           "layer_types": ["full_attention"] * 48,
           "max_position_embeddings": 65536, "max_window_layers": 48,
           "model_type": "ouro", "num_attention_heads": 16,
           "num_hidden_layers": 48, "num_key_value_heads": 16,
           "rms_norm_eps": 1e-06, "rope_scaling": None,
           "rope_theta": 1000000, "sliding_window": None,
           "tie_word_embeddings": False, "total_ut_steps": 4,
           "early_exit_threshold": 1, "use_sliding_window": False,
           "vocab_size": 49152}
    assert {k: cfg[k] for k in row} == row
    assert cfg["reduced"] == []
    entry = next(c for c in spec.load_benchmark()["configs"]
                 if c["name"] == "ouro-2.6b")
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]
    dep = cfg["deployment"]
    assert max(dep["prefill_seq_lens"]) + dep["max_new_tokens"] == 1280
    assert (dep["slots"], dep["prefill_chunk"], dep["kv_dtype"]) \
        == (4, 256, "f32")
    assert {"bias", "norms", "attention", "ffn", "loop", "cache",
            "exit_gate", "context", "init"} <= set(cfg["assumed"])


def test_the_net_is_built_as_the_configuration_says(family):
    """Shapes only: the published widths in bfloat16, one set of weights
    for every pass, and every layer's rows with a pass axis of 4."""
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
    assert sum(int(np.prod(x.shape)) for x in leaves) \
        == family.count_params(dims)
    assert {x.dtype.name for x in leaves} == {"bfloat16"}
    assert sorted(like["blk0_attn"]) == ["Wk", "Wo", "Wq", "Wv"]
    assert like["blk47_ff"]["Wgate"].shape == (2048, 5632)
    assert like["out"]["W"].shape == (2048, 49152)
    assert net.loop[1][0] == "blk0_n1" and net.loop[1][-1] == "norm_f" \
        and net.loop[2] == 4
    specs = net.kv_cache_specs(1280)
    assert specs == {f"blk{i}_attn": {n: ((4, 16, 1280, 128), "bfloat16")
                                      for n in ("k", "v")}
                     for i in range(48)}
