"""The hybrid block of gated delta-rule and gated full-attention layers
(`models/hybrid_moe.py`) against the benchmark's plain reference, which is
loaded by path from `benchmarks/reference/qwen3_next.py` and imports
nothing of the program. Tiny widths that keep the ratios of the served
configuration: delta-rule layers of 4 key and 8 value heads of 16 with a
convolution of 4, full layers of 4 query and 2 key-value heads of 32
turned over their first 8, layer types G G G F, 16 experts of which 4 are
held, 3 a token, a shared expert behind its gate. Weights are seeded here,
in the reference's layout, and laid into the program's tree by name. The
reference runs the delta rule token by token; the program its chunked
form over a prompt and, a token at a time, one pass over the state."""
import importlib.util
import json
import os
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.grouped_moe import grouped_moe_lm
from deeplearning4j_tpu.models.hybrid_moe import hybrid_moe_lm
from deeplearning4j_tpu.models.latent_moe import latent_moe_lm
from deeplearning4j_tpu.models.retention import retention_lm
from deeplearning4j_tpu.models.transformer import transformer_lm
from deeplearning4j_tpu.nn.layers import gated_deltanet as layer
from deeplearning4j_tpu.ops import gated_delta as op
from deeplearning4j_tpu.serving.buckets import BucketLattice
from deeplearning4j_tpu.serving.engine import GenerationEngine
from deeplearning4j_tpu.serving.kvcache import CachePlan, bytes_per_slot
from deeplearning4j_tpu.serving.server import ServingServer
from deeplearning4j_tpu.telemetry import Recorder
from deeplearning4j_tpu.telemetry.memstat import tree_bytes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "benchmarks", "reference", "qwen3_next.py")
    spec = importlib.util.spec_from_file_location("ref_qwen3_next", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

TYPES = ("linear_attention",) * 3 + ("full_attention",)
DIMS = {"hidden": 48, "Hq": 4, "Hk": 2, "d": 32, "rotary": 8,
        "theta": 10000.0, "Hk_lin": 4, "Hv_lin": 8, "dk": 16, "dv": 16,
        "conv": 4, "L": 4, "full": tuple(t == "full_attention" for t in TYPES),
        "Fe": 32, "E": 16, "held": 4, "first_expert": 0, "top_k": 3,
        "eps": 1e-6, "V": 128}
CHANNELS = 2 * 4 * 16 + 8 * 16
CAPACITY = 64
# value head j's state fades over MEMORY[j] tokens: 3 .. 100, geometric
MEMORY = 3.0 * (100 / 3.0) ** (np.arange(8) / 7)


def seeded_weights(seed, dims=DIMS, held=None, first=None):
    """The reference's weights: matrices N(0, gain^2 / fan_in) (the full
    layers' query norm x 4: scores that pick rows; the decay's input
    x 0.5; the head x 2), norm gains 1 + N(0, 0.02), a unit embedding,
    decays that fade a state over 3 to 100 tokens head by head."""
    rng = np.random.default_rng(seed)
    held = dims["held"] if held is None else held

    def mat(*shape, fan, gain=1.0):
        return jnp.asarray(rng.normal(0, gain / fan ** 0.5, shape), jnp.float32)

    def vec(n, gain=1.0):
        return jnp.asarray(gain * (1 + 0.02 * rng.normal(size=n)), jnp.float32)

    h, E, Fe = dims["hidden"], dims["E"], dims["Fe"]
    layers = []
    for full in dims["full"]:
        # every expert's weights are drawn, the held ones kept: a share
        # holds the same numbers the whole layer would
        gate, up, down = (mat(E, h, Fe, fan=h), mat(E, h, Fe, fan=h),
                          mat(E, Fe, h, fan=Fe))
        lo = dims["first_expert"] if first is None else first
        w = {"n1": vec(h), "n2": vec(h), "Wr": mat(h, E, fan=h),
             "We_gate": gate[lo:lo + held], "We_up": up[lo:lo + held],
             "We_down": down[lo:lo + held], "Ws_gate": mat(h, Fe, fan=h),
             "Ws_up": mat(h, Fe, fan=h), "Ws_down": mat(Fe, h, fan=Fe),
             "Ws_g": mat(h, 1, fan=h)}
        if full:
            Hq, Hk, d = dims["Hq"], dims["Hk"], dims["d"]
            w.update(Wq=mat(h, Hq * d, fan=h), Wk=mat(h, Hk * d, fan=h),
                     Wv=mat(h, Hk * d, fan=h), Wg=mat(h, Hq * d, fan=h),
                     q_norm=vec(d, 4.0), k_norm=vec(d),
                     Wo=mat(Hq * d, h, fan=Hq * d))
        else:
            Hv, dv = dims["Hv_lin"], dims["dv"]
            w.update(Wqkv=mat(h, CHANNELS, fan=h), Wz=mat(h, Hv * dv, fan=h),
                     Wb=mat(h, Hv, fan=h), Wa=mat(h, Hv, fan=h, gain=0.5),
                     conv=mat(dims["conv"], CHANNELS, fan=dims["conv"]),
                     A_log=jnp.zeros((Hv,), jnp.float32),
                     dt_bias=jnp.asarray(np.log(np.expm1(1 / MEMORY)),
                                         jnp.float32),
                     norm=vec(dv), Wo=mat(Hv * dv, h, fan=Hv * dv))
        layers.append(w)
    return {"embed": jnp.asarray(rng.normal(size=(dims["V"], h)), jnp.float32),
            "norm_f": vec(h), "Wout": mat(h, dims["V"], fan=h, gain=2.0),
            "layers": layers}


_ATTN = ("Wq", "Wk", "Wv", "Wg", "q_norm", "k_norm", "Wo")
_GDN = ("Wqkv", "Wz", "Wb", "Wa", "conv", "A_log", "dt_bias", "norm", "Wo")
_FF = ("We_gate", "We_up", "We_down", "Ws_gate", "Ws_up", "Ws_down", "Ws_g")


def program_params(W, dtype=jnp.float32):
    """The reference's weights under the names `hybrid_moe_lm` gives them;
    the router is `Wg` of the expert layer there, and the decays stay
    float32 whatever the rest is held in."""
    out = {"embed": {"W": W["embed"]}, "norm_f": {"gamma": W["norm_f"]},
           "out": {"W": W["Wout"]}}
    for i, w in enumerate(W["layers"]):
        p = f"blk{i}"
        out[f"{p}_n1"], out[f"{p}_n2"] = {"gamma": w["n1"]}, {"gamma": w["n2"]}
        if "Wq" in w:
            out[f"{p}_attn"] = {k: w[k] for k in _ATTN}
        else:
            out[f"{p}_gdn"] = {k: w[k] for k in _GDN}
        out[f"{p}_ff"] = dict({k: w[k] for k in _FF}, Wg=w["Wr"])
    out = jax.tree.map(lambda x: x.astype(dtype), out)
    for i, w in enumerate(W["layers"]):
        if "A_log" in w:
            out[f"blk{i}_gdn"].update(A_log=w["A_log"], dt_bias=w["dt_bias"])
    return out


def tiny_net(W, dtype="float32", dims=DIMS, **conf_changes):
    net = hybrid_moe_lm(
        dims["V"], dims["hidden"], TYPES, n_k_heads=dims["Hk_lin"],
        n_v_heads=dims["Hv_lin"], k_head_dim=dims["dk"], v_head_dim=dims["dv"],
        conv_kernel=dims["conv"], n_heads=dims["Hq"], n_kv_heads=dims["Hk"],
        head_dim=dims["d"], rotary_dim=dims["rotary"], rope_theta=dims["theta"],
        n_experts=dims["E"], top_k=dims["top_k"], d_expert=dims["Fe"],
        first_expert=dims["first_expert"], n_held=dims["held"],
        eps=dims["eps"], dtype=dtype, param_dtype=dtype)
    net.params = program_params(W, jnp.dtype(dtype))
    net.state = {n: {} for n in net.params}
    for name, changes in conf_changes.items():
        for key, value in changes.items():
            setattr(net.conf.vertices[name].layer, key, value)
    return net


def log_probs_ref(W, tokens, dims=DIMS):
    return np.asarray(jax.nn.log_softmax(
        ref.forward(W, jnp.asarray(tokens), dims), axis=-1))


def logp(probs):
    return np.log(np.asarray(probs, np.float64) + 1e-30)


@pytest.fixture(scope="module")
def W():
    return seeded_weights(41)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(0, DIMS["V"], 64).astype(np.int32)


@pytest.fixture(scope="module")
def want(W, tokens):
    return log_probs_ref(W, tokens)


# float32 program against the float32 reference: the two differ by the
# order of float32 sums alone (the reference steps the delta rule token by
# token, the program solves a sub-chunk's corrections at once and passes
# over the state in another order; attention is a running softmax over
# blocks against one masked row), 1.1e-5 in a log-probability here
# through the cache; 2e-4 leaves that eighteen times of room and lies a
# hundred times under what every fault below reads through the cache (a
# bfloat16 state 0.042; a correction dropped 5.1, a window not carried
# 7.6, a decay of 1 6.0, no L2 norm 8.7, rotary over the whole head 2.3,
# a sigmoid router 1.2, an ungated shared expert 6.7)
TOL = 2e-4


def test_full_forward_matches_the_reference(W, tokens, want):
    net = tiny_net(W)
    with jax.default_matmul_precision("highest"):
        probs = net.output(tokens[None, :])
    assert np.abs(logp(probs[0]) - want).max() < TOL


# the prompt's 40 tokens in four chunks of a 16-token bucket with unequal
# padding; the second holds 2 tokens, fewer than the convolution's 3 of
# memory, so the window it leaves reaches back into the first chunk
CHUNKS = ((0, 13), (13, 2), (15, 9), (24, 16))


def _through_the_cache(net, tokens, slot=1, slots=3, dirty=None,
                       chunks=CHUNKS, bucket=16):
    """{position: log-probabilities}: the prompt in `chunks` (start, real
    tokens) of a `bucket`-token bucket, the last real row of each read,
    then one decode step a token to the end. `dirty`: a cache to start
    from in place of a zeroed one. -> (that, the cache, each step's
    counters)."""
    prefill = jax.jit(net.prefill_fn())
    step = jax.jit(net.incremental_decode_fn())
    names = step.counters
    row = np.array([slot], np.int32)
    out, counted = {}, []
    with jax.default_matmul_precision("highest"):
        cache = (net.init_kv_cache(slots, CAPACITY) if dirty is None
                 else dirty)
        for start, n in chunks:
            chunk = np.zeros((1, bucket), np.int32)
            chunk[0, :n] = tokens[start:start + n]
            keep = (np.arange(bucket) < n).astype(np.float32)[None, :]
            probs, cache, c = prefill(
                net.params, net.state, cache, chunk, keep, row,
                np.array([start], np.int32), np.array([n - 1], np.int32))
            counted.append(dict(zip(names, np.asarray(c).tolist())))
            out[start + n - 1] = logp(probs[0])
        for t in range(chunks[-1][0] + chunks[-1][1], len(tokens)):
            tok = np.zeros(slots, np.int32)
            pos = np.full(slots, CAPACITY - 1, np.int32)    # the scratch
            live = np.zeros(slots, bool)
            tok[slot], pos[slot], live[slot] = tokens[t], t, True
            probs, cache, c = step(net.params, net.state, cache, tok, pos,
                                   live)
            counted.append(dict(zip(names, np.asarray(c).tolist())))
            out[t] = logp(probs[slot])
    return out, cache, counted


def _worst(got, want):
    return max(np.abs(got[t] - want[t]).max() for t in got)


def test_prefill_across_the_convolution_then_decode_matches_the_reference(
        W, tokens, want):
    got, cache, counted = _through_the_cache(tiny_net(W), tokens)
    assert sorted(got) == [12, 14, 23] + list(range(39, 64))
    assert _worst(got, want) < TOL
    assert {a.shape for e in cache.values() for a in e.values()} \
        == {(3, 8, 16, 16), (3, 3, CHANNELS), (3, 2, CAPACITY, 32)}
    # one reset, the prompt's first chunk's; the full layer's rows seen
    assert [c["state_resets"] for c in counted] == [1] + [0] * 27
    assert [c["attn_rows_seen"] for c in counted[:4]] == [13, 15, 24, 40]
    assert counted[4]["attn_rows_seen"] == 41
    assert all(c["moe_pairs"] <= c["moe_rows"] for c in counted)


def test_a_bfloat16_state_where_float32_is_stated_fails(W, tokens, want):
    """Weights, activations and products in float32, the state alone in
    bfloat16: every step rounds the corrected state to eight bits."""
    net = tiny_net(W, **{f"blk{i}_gdn": {"state_dtype": "bfloat16"}
                         for i in range(3)})
    got, cache, _ = _through_the_cache(net, tokens)
    assert cache["blk0_gdn"]["S"].dtype == jnp.bfloat16
    assert _worst(got, want) > 100 * TOL


def _no_correction_chunk(q, k, v, g, beta, S, *, keep=None, **_):
    """The chunk as plain gated linear attention: S = exp(g) S + beta k v^T."""
    keep = jnp.ones(g.shape[:2]) if keep is None else keep
    g, beta = g * keep[..., None], beta * keep[..., None]

    def step(s, t):
        q_t, k_t, v_t, g_t, b_t = t
        s = jnp.exp(g_t)[..., None, None] * s + (
            b_t[..., None, None] * k_t[..., :, None] * v_t[..., None, :])
        return s, jnp.sum(q_t[..., :, None] * s, axis=-2)

    s, o = jax.lax.scan(step, S.astype(jnp.float32), tuple(
        jnp.moveaxis(a.astype(jnp.float32), 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s.astype(S.dtype)


def _no_correction_decode(S, q, k, v, a, beta, live=None):
    s = a[..., None, None] * S.astype(jnp.float32) + (
        beta[..., None, None] * k[..., :, None] * v[..., None, :])
    if live is not None:
        s = jnp.where(live[:, None, None, None], s, S)
    return jnp.sum(q[..., :, None] * s, axis=-2), s.astype(S.dtype)


FAULTS = ["correction_dropped", "window_not_carried", "decay_of_1",
          "no_l2_norm", "rotary_over_the_whole_head", "sigmoid_router",
          "shared_gate_ignored"]


@pytest.mark.parametrize("fault", FAULTS)
def test_a_fault_in_the_block_fails(W, tokens, want, fault, monkeypatch):
    """Each departs from the equations in one place; through the cache it
    reads a hundred times the tolerance, and by the full forward too
    where the full forward has the part (it has no window to carry)."""
    real_conv, real_inputs = layer._conv, layer._rule_inputs
    conf = {}
    if fault == "correction_dropped":
        monkeypatch.setattr(layer, "gated_delta_chunk", _no_correction_chunk)
        monkeypatch.setattr(layer, "gated_delta_decode", _no_correction_decode)
    elif fault == "window_not_carried":
        monkeypatch.setattr(layer, "_conv", lambda params, u, window: real_conv(
            params, u, jnp.zeros_like(window)))
    elif fault == "decay_of_1":
        def no_decay(*a):
            q, k, v, g, beta = real_inputs(*a)
            return q, k, v, jnp.zeros_like(g), beta
        monkeypatch.setattr(layer, "_rule_inputs", no_decay)
    elif fault == "no_l2_norm":
        monkeypatch.setattr(layer, "_l2n", lambda x: x)
    elif fault == "rotary_over_the_whole_head":
        conf = {"blk3_attn": {"rotary_dim": 0}}
    elif fault == "sigmoid_router":
        conf = {f"blk{i}_ff": {"router": "sigmoid"} for i in range(4)}
    else:
        conf = {f"blk{i}_ff": {"shared_gate": False} for i in range(4)}
    net = tiny_net(W, **conf)
    got, _, _ = _through_the_cache(net, tokens)
    assert _worst(got, want) > 100 * TOL
    if fault != "window_not_carried":
        with jax.default_matmul_precision("highest"):
            probs = net.output(tokens[None, :])
        assert np.abs(logp(probs[0]) - want).max() > 100 * TOL


def test_a_slots_second_shorter_tenant_is_served_as_a_fresh_cache_would(
        W, tokens):
    """Slot 1 serves the 64 tokens, then a prompt of 30 from position 0:
    its states and windows are zeroed, and the log-probabilities are those
    of a zeroed cache, bit for bit."""
    net = tiny_net(W)
    second = np.random.default_rng(9).integers(0, DIMS["V"], 30).astype(np.int32)
    short = ((0, 13), (13, 9))
    fresh, _, _ = _through_the_cache(net, second, chunks=short)
    _, used, _ = _through_the_cache(net, tokens)
    assert all(np.abs(np.asarray(a)[1]).max() > 0
               for e in used.values() for a in e.values())
    again, _, counted = _through_the_cache(net, second, dirty=used,
                                           chunks=short)
    assert sorted(again) == [12, 21] + list(range(22, 30))
    assert counted[0]["state_resets"] == 1
    assert all(np.array_equal(fresh[t], again[t]) for t in fresh)


def test_an_idle_rows_state_is_bit_identical_after_a_step(W, tokens):
    net = tiny_net(W)
    _, cache, _ = _through_the_cache(net, tokens, slot=1)
    _, cache, _ = _through_the_cache(net, tokens[::-1].copy(), slot=2,
                                     dirty=cache)
    before = jax.tree.map(np.asarray, cache)
    step = jax.jit(net.incremental_decode_fn())
    tok = np.array([5, 0, 0], np.int32)
    pos = np.array([0, CAPACITY - 1, CAPACITY - 1], np.int32)
    _, after, counts = step(net.params, net.state, cache, tok, pos,
                            np.array([True, False, False]))
    assert dict(zip(step.counters, np.asarray(counts).tolist()))[
        "state_resets"] == 1         # row 0 starts a sequence by a decode step
    for name, arrays in before.items():
        for arr, old in arrays.items():
            new = np.asarray(after[name][arr])
            assert np.array_equal(new[1:], old[1:]), (name, arr)
            assert np.abs(old[1:]).max() > 0


def test_a_pad_token_changes_nothing(W, tokens):
    """`keep` 0 adds nothing, decays nothing and shifts nothing into the
    window: a chunk of 9 real tokens in a bucket of 16 leaves the entry a
    bucket of 9 leaves. Not bit for bit: the chunked form then solves
    sub-chunks of 16 and 9 tokens, and the sums' order differs by the
    float32 rounding of values near 1 (under 1e-6 read); a pad that wrote, or
    decayed a state, would move them by tenths."""
    net = tiny_net(W)
    prefill = jax.jit(net.prefill_fn())
    caches = []
    for bucket in (9, 16):
        chunk = np.zeros((1, bucket), np.int32)
        chunk[0, :9] = tokens[:9]
        chunk[0, 9:] = 77       # the pad holds a token, to no effect
        keep = (np.arange(bucket) < 9).astype(np.float32)[None, :]
        _, cache, _ = prefill(net.params, net.state, net.init_kv_cache(
            2, CAPACITY), chunk, keep, np.array([0], np.int32),
            np.array([0], np.int32), np.array([8], np.int32))
        caches.append(cache)
    for i in range(3):
        for arr in ("S", "conv"):
            a, b = (np.asarray(c[f"blk{i}_gdn"][arr]) for c in caches)
            assert np.abs(a).max() > 0
            assert np.allclose(a, b, rtol=0, atol=1e-5), (i, arr)
    # the window holds the last three real inputs, as the program had them:
    # the first layer's are the normed embeddings times Wqkv
    p = jax.tree.map(np.asarray, net.params)
    x = p["embed"]["W"][tokens[6:9]]
    x = x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-6) \
        * p["blk0_n1"]["gamma"]
    assert np.allclose(np.asarray(caches[1]["blk0_gdn"]["conv"][0]),
                       x @ p["blk0_gdn"]["Wqkv"], atol=1e-5)


def test_speculative_decoding_is_refused_with_the_layer_named(W):
    net = tiny_net(W)
    with pytest.raises(ValueError, match=r"blk0_gdn \(GatedDeltaNetLayer\)"):
        net.verify_decode_fn()
    with pytest.raises(ValueError, match=r"blk2_gdn \(GatedDeltaNetLayer\)"):
        GenerationEngine(net, BucketLattice(batch_sizes=(1,), seq_lens=(8,)),
                         slots=2, max_new_tokens=8, page_size=8,
                         speculative_k=2)
    # a window of tokens handed to the layer itself is refused too
    from deeplearning4j_tpu.nn.decode import CacheStep

    conf = net.conf.vertices["blk0_gdn"].layer
    entry = net.init_kv_cache(2, 16)["blk0_gdn"]
    with pytest.raises(ValueError, match="one token a row"):
        layer.GatedDeltaNetImpl().apply_cached(
            conf, net.params["blk0_gdn"], jnp.zeros((2, 3, 48)), entry,
            CacheStep(None, jnp.zeros((2, 3), jnp.int32)))
    assert not layer.GatedDeltaNetImpl.rewindable(conf)


@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16"])
def test_kernel_in_interpret_mode_equals_its_jnp_twin(state_dtype):
    """Five slots, one of them not live, one starting anew (decay 0), the
    value heads in blocks of 8 and in one block of all 16."""
    rng = np.random.default_rng(2)
    B, H, dk, dv = 5, 16, 16, 8
    S = jnp.asarray(rng.normal(size=(B, H, dk, dv)), state_dtype)
    q, k = (jnp.asarray(rng.normal(size=(B, H, dk)), jnp.float32) for _ in "qk")
    v = jnp.asarray(rng.normal(size=(B, H, dv)), jnp.float32)
    a = jnp.asarray(rng.uniform(0.5, 1.0, (B, H)), jnp.float32).at[4].set(0.0)
    beta = jnp.asarray(rng.uniform(size=(B, H)), jnp.float32)
    live = jnp.asarray([True, True, False, True, True])
    o_twin, s_twin = op.gated_delta_decode_jnp(S, q, k, v, a, beta, live)
    for hb in (8, 16):
        o, s = op.gated_delta_decode_kernel(S, q, k, v, a, beta, live,
                                            interpret=True, head_block=hb)
        assert o.shape == (B, H, dv) and s.dtype == S.dtype
        # the same float32 sums in the same order
        assert np.abs(np.asarray(o) - np.asarray(o_twin)).max() < 1e-5
        assert np.abs(np.asarray(s, np.float32)
                      - np.asarray(s_twin, np.float32)).max() < 1e-5
        assert np.array_equal(np.asarray(s[2]), np.asarray(S[2]))
    # against the rule written out, in float64
    Sf = np.asarray(S, np.float64)
    for i in (0, 4):
        for h in range(H):
            st = float(a[i, h]) * Sf[i, h]
            kf, vf = np.asarray(k[i, h], np.float64), np.asarray(v[i, h])
            st = st + np.outer(kf, float(beta[i, h]) * (vf - st.T @ kf))
            assert np.abs(st.T @ np.asarray(q[i, h]) - o_twin[i, h]).max() \
                < (1e-4 if state_dtype == "float32" else 1e-2)


def test_the_chunked_form_equals_the_recurrence():
    """150 tokens (three sub-chunks of 64, the last one short), a state
    as found, a row whose last ten tokens are a bucket's pad: the chunked
    form's outputs and final state against the rule stepped token by
    token in float64."""
    rng = np.random.default_rng(0)
    b, T, H, dk, dv = 2, 150, 3, 16, 8

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(b, T, H, dk))) / 4
    k = unit(rng.normal(size=(b, T, H, dk)))
    v = rng.normal(size=(b, T, H, dv))
    g = -np.exp(rng.normal(size=(b, T, H)) - 3)
    beta = 1 / (1 + np.exp(-rng.normal(size=(b, T, H))))
    S0 = 0.1 * rng.normal(size=(b, H, dk, dv))
    keep = np.ones((b, T))
    keep[1, 140:] = 0
    o, S = op.gated_delta_chunk(*(jnp.asarray(x, jnp.float32)
                                  for x in (q, k, v, g, beta, S0)),
                                keep=jnp.asarray(keep))
    St, want = S0.copy(), np.zeros((b, T, H, dv))
    for t in range(T):
        for i in range(b):
            if not keep[i, t]:
                continue
            for h in range(H):
                s = St[i, h] * np.exp(g[i, t, h])
                s = s + np.outer(k[i, t, h], beta[i, t, h]
                                 * (v[i, t, h] - s.T @ k[i, t, h]))
                St[i, h], want[i, t, h] = s, s.T @ q[i, t, h]
    assert np.abs(np.asarray(o) - want)[keep > 0].max() < 1e-5
    assert np.abs(np.asarray(S) - St).max() < 1e-5


def test_the_four_expert_shares_add_up_to_the_uncut_layer():
    """One chip's share leaves out what the absent experts would add: the
    outputs of the four shares (experts 0-3, 4-7, 8-11, 12-15), with the
    gated shared expert counted once, are the uncut reference's layer."""
    from deeplearning4j_tpu.nn.layers.moe import DroplessMoELayer, dropless_moe

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(40, 48)), jnp.float32)
    whole = seeded_weights(43, held=16, first=0)["layers"][0]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.experts(x, whole, dict(DIMS, first_expert=0),
                                      ref.mm_highest))
        shared = np.asarray(jax.nn.sigmoid(ref.mm_highest(x, whole["Ws_g"]))
                            * ref.gated(x, whole["Ws_gate"], whole["Ws_up"],
                                        whole["Ws_down"], ref.mm_highest))
        total, pairs = -3.0 * shared, 0
        for first in (0, 4, 8, 12):
            w = seeded_weights(43, first=first)["layers"][0]
            assert np.array_equal(w["We_up"], whole["We_up"][first:first + 4])
            conf = DroplessMoELayer(
                n_in=48, n_out=48, n_experts=16, top_k=3, d_hidden=32,
                first_expert=first, n_held=4, n_shared=1, router="softmax",
                shared_gate=True, activation="silu")
            y, counts = dropless_moe(conf, dict(w, Wg=w["Wr"]), x)
            total, pairs = total + np.asarray(y), pairs + int(counts["moe_pairs"])
    assert pairs == 40 * 3          # every selected pair lies in one share
    assert np.abs(total - want).max() < 1e-4


def _spans(rec, name):
    return [e for e in rec.events
            if e.get("event") == "span" and e.get("name") == name]


def test_engine_serves_the_block_over_http_in_bfloat16(W):
    """`POST /generate` through `ServingServer` and `GenerationEngine`:
    no step retraces after the warm-up, every warmed step aliases the
    whole cache, the spans carry the delta-rule layers' `state_resets`
    beside the full layer's `attn_rows_seen` and the expert layer's
    three, and the `meta` event and /stats say what a slot's rows and
    states cost."""
    net = tiny_net(W, "bfloat16")
    rec = Recorder(path=None)
    engine = GenerationEngine(
        net, BucketLattice(batch_sizes=(1,), seq_lens=(8, 16, 32)), slots=3,
        max_new_tokens=16, page_size=8, prefill_chunk=16, recorder=rec)
    assert engine.warmup() == 3     # chunks of 8 and 16, the decode step
    worker = engine.fleet_workers()[0]
    states = 3 * (8 * 16 * 16 * 4 + 3 * CHANNELS * 2)   # S f32, window bf16
    rows = 48 * 2 * 2 * 32 * 2      # capacity x (k, v) x [2, 32] bf16
    assert tree_bytes(worker.cache) == 3 * (states + rows)
    assert worker.cache["blk0_gdn"]["S"].dtype == jnp.float32
    costs = [e for e in rec.events if e.get("event") == "cost"]
    assert len(costs) == 3 and all(
        e["alias_bytes"] == 3 * (states + rows) for e in costs), costs
    meta = [e for e in rec.events if e.get("event") == "meta"
            and e.get("role") == "generation-engine"][0]
    for described in (meta["cache"], engine.stats()["cache"]):
        assert described["capacity"] == 48
        assert described["rows"] == {"k": 128.0, "v": 128.0}
        assert described["states"] == {"S": 3 * 8 * 16 * 16 * 4,
                                       "conv": 3 * 3 * CHANNELS * 2}
        assert described["state_bytes_per_slot"] == states
        assert described["bytes_per_slot"] == states + rows
    regions = {e["entry"]: set(e["ops"].values()) for e in rec.events
               if e.get("event") == "regions"}
    assert {"attention", "moe/router", "moe/experts", "moe/shared_expert",
            "norm", "embed", "head"} <= regions["decode"] & regions["prefill"]
    assert "attention/cache_write" in regions["prefill"]
    server = ServingServer(engine, port=0).start()
    asked = ((5, 16), (30, 9), (16, 3), (27, 16))
    try:
        rng = np.random.default_rng(2)
        for plen, new in asked:
            body = json.dumps({"tokens": rng.integers(0, 128, plen).tolist(),
                               "max_new_tokens": new}).encode()
            req = urllib.request.Request(
                f"{server.url}/generate", data=body,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=120) as resp:
                lines = [json.loads(l) for l in resp.read().splitlines() if l]
            assert lines[-1]["done"] and len(lines[-1]["tokens"]) == new
    finally:
        server.stop()
    assert engine.trace_count == 3, "a step retraced after the warm-up"
    assert engine.failed == 0
    home = {e["fetched"]: e for e in rec.events
            if e.get("event") == "span" and e.get("fetched") is not None}
    names = ("state_resets", "attn_rows_seen", "moe_pairs", "moe_rows",
             "moe_max_load")
    chunks, steps = _spans(rec, "prefill_chunk"), _spans(rec, "decode_step")
    for e in chunks + steps:
        assert all(isinstance(home[e["program"]][n], int) for n in names)
    assert [home[e["program"]]["state_resets"] for e in chunks] \
        == [int(e["start"] == 0) for e in chunks]
    assert sum(home[e["program"]]["state_resets"] for e in chunks) \
        == len(asked)
    assert all(home[e["program"]]["state_resets"] == 0 for e in steps)
    assert [home[e["program"]]["attn_rows_seen"] for e in chunks] \
        == [e["start"] + e["n_real"] for e in chunks]


def test_a_second_request_in_a_slot_gets_the_tokens_a_fresh_engine_gives(W):
    """One slot, so the second, shorter request takes the first one's
    state and window."""
    rng = np.random.default_rng(3)
    first, second = (rng.integers(0, 128, n).tolist() for n in (30, 11))

    def engine():
        return GenerationEngine(
            tiny_net(W), BucketLattice(batch_sizes=(1,), seq_lens=(8, 16, 32)),
            slots=1, max_new_tokens=16, page_size=8, prefill_chunk=16).start()

    used, fresh = engine(), engine()
    try:
        assert len(used.generate(first, 16)) == 16
        assert used.generate(second, 8) == fresh.generate(second, 8)
    finally:
        used.drain()
        fresh.drain()


def test_bytes_per_slot_is_the_cache_trees_bytes_a_slot_and_describe_gives_both(W):
    net = tiny_net(W, "bfloat16")
    states = 3 * (8 * 16 * 16 * 4 + 3 * CHANNELS * 2)
    row = 2 * 32 * 2                # [2, 32] bfloat16
    for max_seq in (8, 120):
        plan = CachePlan(max_seq, 8, n_slots=5, page_size=8)
        cache = net.init_kv_cache(5, plan.capacity, "f32", 8)
        assert plan.bytes_per_slot(net) * 5 == tree_bytes(cache)
        assert bytes_per_slot(plan.cache_specs(net)) == plan.bytes_per_slot(net)
        said = plan.describe(net)
        assert said["rows"] == {"k": 1.0 * row, "v": 1.0 * row}
        assert said["bytes_per_token"] == 2 * row
        assert said["windows"] == {}
        assert said["state_bytes_per_slot"] == states
        assert said["bytes_per_slot"] == plan.bytes_per_slot(net) \
            == states + 2 * row * plan.capacity
        # the page pool counts the rows' pages alone
        assert plan.pages_per_slot == plan.capacity // 8


@pytest.mark.parametrize("model", ["transformer_lm", "latent_moe_lm",
                                   "grouped_moe_lm", "retention_lm"])
def test_the_other_nets_keep_their_steps(model):
    """A rotary that may turn part of a head, a router that may be a
    softmax and a shared expert that may be gated change nothing for the
    nets that were there: the same vertices, specs and counters, no
    shared gate held, the whole head turned, the tokens of the full
    forward."""
    if model == "transformer_lm":
        net = transformer_lm(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                             d_ff=64, max_length=64).init(seed=3)
        counters, arrays, extra = (), {"k", "v"}, 0
    elif model == "latent_moe_lm":
        net = latent_moe_lm(
            vocab_size=64, d_model=32, n_heads=2, n_layers=2, q_rank=12,
            kv_rank=8, nope_dim=8, rope_dim=4, v_dim=8, d_ff=48,
            n_dense_layers=1, n_experts=4, top_k=2, d_expert=16).init(seed=3)
        counters, arrays, extra = ("moe_pairs", "moe_rows", "moe_max_load"), \
            {"ckv", "kpe"}, 0
    elif model == "grouped_moe_lm":
        net = grouped_moe_lm(64, 32, 4, 2, 8, ["full_attention"] * 2, 16, 1,
                             48, 4, 2, 16, rope_theta=0.0).init(seed=3)
        counters, arrays, extra = ("attn_rows_seen", "attn_wrapped",
                                   "attn_write_wraps", "moe_pairs",
                                   "moe_rows", "moe_max_load"), {"k", "v"}, 0
        assert net.conf.vertices["blk0_attn"].layer.rotary_dim == 0
    else:
        net = retention_lm(vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2,
                           n_layers=2, d_ff=48, head_dim=8).init(seed=3)
        counters, arrays, extra = ("state_resets",), {"s", "z"}, 1
        assert list(net.conf.vertices) == ["embed"] + [
            f"blk{i}_{n}" for i in range(2)
            for n in ("n1", "ret", "res1", "n2", "ff", "res2")] \
            + ["norm_f", "out"]
    for name, v in net.conf.vertices.items():
        lc = getattr(v, "layer", None)
        if lc is not None and hasattr(lc, "router"):
            assert (lc.router, lc.shared_gate) == ("sigmoid", False)
            assert "Ws_g" not in net.params[name]
    specs = net.kv_cache_specs(32)
    assert all(set(e) == arrays and all(
        len(s) == 2 + extra and s[2:] == ("slot",) * extra
        for s in e.values()) for e in specs.values())
    fns = [net.prefill_fn(), net.incremental_decode_fn()]
    assert [f.counters for f in fns] == [counters] * len(fns)
    cache = net.init_kv_cache(3, 32)
    tokens = np.random.default_rng(4).integers(0, 64, 12).astype(np.int32)
    out = fns[0](net.params, net.state, cache, tokens[None, :8],
                 np.ones((1, 8), np.float32), np.array([2], np.int32),
                 np.array([0], np.int32), np.array([7], np.int32))
    got, cache = [np.asarray(out[0][0])], out[1]
    for t in range(8, 12):
        tok, pos = np.zeros(3, np.int32), np.full(3, 31, np.int32)
        live = np.zeros(3, bool)
        tok[2], pos[2], live[2] = tokens[t], t, True
        out = fns[1](net.params, net.state, cache, tok, pos, live)
        got.append(np.asarray(out[0][2]))
        cache = out[1]
    full = np.asarray(net.output(tokens[None, :]))[0]
    assert np.abs(np.stack(got) - full[7:]).max() < 1e-5
