"""The power-retention block (`models/retention.py`) against the
benchmark's plain reference, which is loaded by path from
`benchmarks/reference/brumby.py` and imports nothing of the program. Tiny
widths that keep the ratios of the served configuration: 10 query heads
on 2 key-value heads of 16 (five queries a state), a gated feed-forward
block, three layers. Weights are seeded here, in the reference's layout,
and laid into the program's tree by name. The reference computes the
ATTENTION form (every weight of every earlier key); the program the
chunked form and, a token at a time, the recurrence over a fixed state."""
import importlib.util
import json
import os
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.latent_moe import latent_moe_lm
from deeplearning4j_tpu.models.retention import retention_lm
from deeplearning4j_tpu.models.transformer import transformer_lm
from deeplearning4j_tpu.nn.layers import power_retention as layer
from deeplearning4j_tpu.ops import power_retention as op
from deeplearning4j_tpu.serving.buckets import BucketLattice
from deeplearning4j_tpu.serving.engine import GenerationEngine
from deeplearning4j_tpu.serving.kvcache import CachePlan, bytes_per_slot
from deeplearning4j_tpu.serving.server import ServingServer
from deeplearning4j_tpu.telemetry import Recorder
from deeplearning4j_tpu.telemetry.memstat import tree_bytes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "benchmarks", "reference", "brumby.py")
    spec = importlib.util.spec_from_file_location("ref_brumby", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

DIMS = {"hidden": 64, "Hq": 10, "Hk": 2, "d": 16, "L": 3, "F": 160,
        "theta": 1e6, "eps": 1e-6, "V": 128}
D = op.state_dim(16)        # 144: nine diagonals of 16
GATE_BIAS = 3.0             # g about 0.95: forty tokens feel the decay


def seeded_weights(seed, dims=DIMS, gate_bias=GATE_BIAS):
    """The reference's weights: matrices N(0, gain^2 / fan_in) (retention
    output x 4, head x 2: the layer's mean over many values is small, and
    logits should spread), norm gains 1 + N(0, 0.02), a unit embedding,
    every gate bias `gate_bias`."""
    rng = np.random.default_rng(seed)

    def mat(*shape, fan, gain=1.0):
        return jnp.asarray(rng.normal(0, gain / fan ** 0.5, shape), jnp.float32)

    def vec(n):
        return jnp.asarray(1 + 0.02 * rng.normal(size=n), jnp.float32)

    h, Hq, Hk, d, F = (dims[k] for k in ("hidden", "Hq", "Hk", "d", "F"))
    layers = [{"n1": vec(h), "n2": vec(h), "Wq": mat(h, Hq * d, fan=h),
               "Wk": mat(h, Hk * d, fan=h), "Wv": mat(h, Hk * d, fan=h),
               "Wg": mat(h, Hk, fan=h),
               "bg": jnp.full((Hk,), gate_bias, jnp.float32),
               "q_norm": vec(d), "k_norm": vec(d),
               "Wo": mat(Hq * d, h, fan=Hq * d, gain=4.0),
               "Wgate": mat(h, F, fan=h), "Wup": mat(h, F, fan=h),
               "Wdown": mat(F, h, fan=F)} for _ in range(dims["L"])]
    return {"embed": mat(dims["V"], h, fan=1.0), "norm_f": vec(h),
            "Wout": mat(h, dims["V"], fan=h, gain=2.0), "layers": layers}


_FF = ("Wgate", "Wup", "Wdown")


def program_params(W, dtype=jnp.float32):
    out = {"embed": {"W": W["embed"]}, "norm_f": {"gamma": W["norm_f"]},
           "out": {"W": W["Wout"]}}
    for i, w in enumerate(W["layers"]):
        p = f"blk{i}"
        out[f"{p}_n1"], out[f"{p}_n2"] = {"gamma": w["n1"]}, {"gamma": w["n2"]}
        out[f"{p}_ret"] = {k: x for k, x in w.items()
                           if k not in _FF and k not in ("n1", "n2")}
        out[f"{p}_ff"] = {k: w[k] for k in _FF}
    return jax.tree.map(lambda x: x.astype(dtype), out)


def tiny_net(W, dtype="float32", state_dtype="float32", dims=DIMS):
    net = retention_lm(
        vocab_size=dims["V"], d_model=dims["hidden"], n_heads=dims["Hq"],
        n_kv_heads=dims["Hk"], n_layers=dims["L"], d_ff=dims["F"],
        head_dim=dims["d"], rope_theta=dims["theta"], eps=dims["eps"],
        state_dtype=state_dtype, dtype=dtype, param_dtype=dtype)
    net.params = program_params(W, jnp.dtype(dtype))
    net.state = {n: {} for n in net.params}
    return net


def log_probs_ref(W, tokens):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.nn.log_softmax(
            ref.forward(W, jnp.asarray(tokens), DIMS), axis=-1))


def logp(probs):
    return np.log(np.asarray(probs, np.float64) + 1e-30)


@pytest.fixture(scope="module")
def W():
    return seeded_weights(31)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(0, DIMS["V"], 40).astype(np.int32)


# float32 program against the float32 reference: the two differ by the
# order of float32 sums alone (the reference adds up weights of keys, the
# program inner products with a running state of 144 entries a value),
# 5e-6 in a log-probability here; 2e-4 leaves that forty times of room
# and is 1 / 150 of what a bfloat16 STATE reads under float32 everywhere
# else (3e-2, `test_a_bfloat16_state_where_float32_is_stated_fails`)
TOL = 2e-4


def test_full_forward_matches_the_reference(W, tokens):
    net = tiny_net(W)
    with jax.default_matmul_precision("highest"):
        probs = net.output(tokens[None, :])
    assert np.abs(logp(probs[0]) - log_probs_ref(W, tokens)).max() < TOL


def _through_the_state(net, tokens, capacity=64, slot=1, slots=3, dirty=None,
                       resets=True):
    """{position: log-probabilities}: the prompt's 29 tokens in two
    chunks of unequal bucket padding (13 real tokens in a bucket of 16,
    then 16 in a bucket of 32), the last real row of each read, then 11
    decode steps through the state. `dirty`: a cache to start from in
    place of a zeroed one. `resets`: whether the layer resets (it counts
    the rows it did)."""
    prefill = jax.jit(net.prefill_fn())
    step = jax.jit(net.incremental_decode_fn())
    row = np.array([slot], np.int32)
    out = {}
    with jax.default_matmul_precision("highest"):
        cache = net.init_kv_cache(slots, capacity) if dirty is None else dirty
        for start, n, bucket in ((0, 13, 16), (13, 16, 32)):
            chunk = np.zeros((1, bucket), np.int32)
            chunk[0, :n] = tokens[start:start + n]
            keep = (np.arange(bucket) < n).astype(np.float32)[None, :]
            probs, cache, counted = prefill(
                net.params, net.state, cache, chunk, keep, row,
                np.array([start], np.int32), np.array([n - 1], np.int32))
            assert int(counted[0]) == (resets and start == 0)
            out[start + n - 1] = logp(probs[0])
        for t in range(29, len(tokens)):
            tok = np.zeros(slots, np.int32)
            pos = np.full(slots, capacity - 1, np.int32)
            live = np.zeros(slots, bool)
            tok[slot], pos[slot], live[slot] = tokens[t], t, True
            probs, cache, counted = step(net.params, net.state, cache, tok,
                                         pos, live)
            assert int(counted[0]) == 0
            out[t] = logp(probs[slot])
    return out, cache


def test_prefill_in_two_padded_chunks_then_decode_matches_the_reference(
        W, tokens):
    got, _ = _through_the_state(tiny_net(W), tokens)
    want = log_probs_ref(W, tokens)
    assert sorted(got) == [12, 28] + list(range(29, 40))
    assert max(np.abs(got[t] - want[t]).max() for t in got) < TOL


def test_a_bfloat16_state_where_float32_is_stated_fails(W, tokens):
    """Weights, activations and products in float32, the state alone in
    bfloat16: every step rounds the running sums to eight bits."""
    got, cache = _through_the_state(tiny_net(W, state_dtype="bfloat16"),
                                    tokens)
    assert {a.dtype.name for a in jax.tree.leaves(cache)} == {"bfloat16"}
    want = log_probs_ref(W, tokens)
    assert max(np.abs(got[t] - want[t]).max() for t in got) > 50 * TOL


def test_recurrence_equals_the_attention_form_over_300_tokens():
    """One layer's retention, 300 tokens, gates near 1 (log g in
    [-0.01, 0]: the first token still weighs a fifth at the last): the
    chunked form, and 300 steps of the recurrence from a zero state, give
    the attention form's y computed here in float64; the two states
    agree."""
    rng = np.random.default_rng(0)
    T, Hq, Hk, d = 300, 10, 2, 16
    q, k, v = (jnp.asarray(rng.normal(size=(1, T, H, d)), jnp.float32)
               for H in (Hq, Hk, Hk))
    log_g = jnp.asarray(-rng.uniform(0, 0.01, (1, T, Hk)), jnp.float32)
    G = np.cumsum(np.asarray(log_g, np.float64), 1)[0]
    qn, kn, vn = (np.asarray(a, np.float64)[0] for a in (q, k, v))
    want = np.zeros((T, Hq, d))
    for h in range(Hq):
        c = h // (Hq // Hk)
        w = np.tril(np.exp(G[:, None, c] - G[None, :, c])) \
            * (qn[:, h] @ kn[:, c].T) ** 2 / d
        want[:, h] = (w @ vn[:, c]) / (w.sum(-1, keepdims=True) + 1e-6)
    zero = jnp.zeros((1, Hk, d, D)), jnp.zeros((1, Hk, D))
    y, s_chunk, z_chunk = op.retention_chunk(q, k, v, log_g, *zero,
                                             sub_chunk=64)
    assert np.abs(np.asarray(y)[0] - want).max() < 1e-5
    step = jax.jit(op.retention_decode_jnp)
    s, z = zero
    for t in range(T):
        num, den, s, z = step(s, z, q[:, t], k[:, t], v[:, t],
                              jnp.exp(log_g[:, t]))
        got = np.asarray(num / (den + 1e-6)[..., None])[0]
        # the first tokens' whole sum of weights can be as small as eps
        # (one key, q . k near 0): the recurrence makes it from 144
        # products where the attention form squares one, and the ratio
        # to (sum + eps) magnifies the last float32 bit a thousandfold
        assert np.abs(got - want[t]).max() < (1e-4 if t >= 8 else 1e-2), t
    assert np.abs(np.asarray(s) - np.asarray(s_chunk)).max() < 1e-4
    assert np.abs(np.asarray(z) - np.asarray(z_chunk)).max() < 1e-4


def test_phi2_is_the_symmetric_square():
    """phi2(q) . phi2(k) = (q . k)^2 / d, from d (d / 2 + 1) entries: the
    d (d + 1) / 2 pairs and the half diagonal's second copy."""
    rng = np.random.default_rng(1)
    q, k = (jnp.asarray(rng.normal(size=(7, 16)), jnp.float32) for _ in "qk")
    assert op.phi2(q).shape == (7, D) and D == 16 * 17 // 2 + 8
    want = np.asarray((q * k).sum(-1)) ** 2 / 16
    assert np.abs(np.asarray((op.phi2(q) * op.phi2(k)).sum(-1)) - want).max() \
        < 1e-5
    assert op.state_dim(128) == 8320 == 128 * 129 // 2 + 64


@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16"])
def test_kernel_in_interpret_mode_equals_its_jnp_twin(state_dtype):
    rng = np.random.default_rng(2)
    B, Hq, Hk, d = 3, 10, 2, 16
    dt = jnp.dtype(state_dtype)
    s = jnp.asarray(rng.normal(size=(B, Hk, d, D)), dt)
    z = jnp.asarray(rng.normal(size=(B, Hk, D)), dt)
    q, k, v = (jnp.asarray(rng.normal(size=(B, H, d)), jnp.float32)
               for H in (Hq, Hk, Hk))
    g = jnp.asarray([[0.97, 0.9], [1.0, 1.0], [0.0, 0.0]], jnp.float32)
    k = k.at[1].set(0)      # row 1: decay 1 and no key, the idle row
    twin = op.retention_decode_jnp(s, z, q, k, v, g)
    kern = op.retention_decode_kernel(s, z, q, k, v, g, interpret=True,
                                      value_tile=8)
    tol = 1e-5 if state_dtype == "float32" else 0.0
    for a, b in zip(twin, kern):
        assert a.shape == b.shape and a.dtype == b.dtype
    for a, b in zip(twin[:2], kern[:2]):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 2e-5
    for a, b, old in zip(twin[2:], kern[2:], (s, z)):
        a, b, old = (np.asarray(x, np.float32) for x in (a, b, old))
        assert np.abs(a - b).max() <= tol
        assert np.array_equal(b[1], old[1])     # the idle row, bit for bit
        assert np.abs(b[2]).max() < 20 and not np.array_equal(b[2], old[2])


def test_a_slots_second_tenant_is_served_as_a_fresh_cache_would(
        W, tokens, monkeypatch):
    """Slot 1 serves the 40 tokens, then a second prompt from position 0:
    its log-probabilities are those of the same prompt through a zeroed
    cache, bit for bit. With the reset patched out they are not."""
    net = tiny_net(W)
    second = np.random.default_rng(9).integers(0, DIMS["V"], 40).astype(np.int32)
    fresh, _ = _through_the_state(net, second)
    _, used = _through_the_state(net, tokens)
    again, _ = _through_the_state(net, second, dirty=used)
    assert all(np.array_equal(fresh[t], again[t]) for t in fresh)
    monkeypatch.setattr(layer, "_starts", lambda step: jnp.zeros(
        step.positions.shape[0], bool))
    _, used = _through_the_state(net, tokens, resets=False)   # traced anew
    stale, _ = _through_the_state(net, second, dirty=used, resets=False)
    assert max(np.abs(fresh[t] - stale[t]).max() for t in fresh) > 50 * TOL


def test_an_idle_rows_state_is_bit_identical_after_a_step(W, tokens):
    net = tiny_net(W)
    _, cache = _through_the_state(net, tokens, slot=1)
    _, cache = _through_the_state(net, tokens[::-1].copy(), slot=2,
                                  dirty=cache)
    before = jax.tree.map(np.asarray, cache)
    step = jax.jit(net.incremental_decode_fn())
    tok, pos = np.array([5, 0, 0], np.int32), np.array([0, 63, 63], np.int32)
    _, after, resets = step(net.params, net.state, cache, tok, pos,
                            np.array([True, False, False]))
    assert int(resets[0]) == 1      # row 0 starts a sequence by a decode step
    for name, arrays in before.items():
        for arr, old in arrays.items():
            new = np.asarray(after[name][arr])
            assert np.array_equal(new[1:], old[1:]), (name, arr)
            assert np.abs(old[1:]).max() > 0 and not np.array_equal(new[0], old[0])


def test_speculative_decoding_is_refused_with_the_layer_named(W):
    net = tiny_net(W)
    with pytest.raises(ValueError, match=r"blk0_ret \(PowerRetentionLayer\)"):
        net.verify_decode_fn()
    with pytest.raises(ValueError, match=r"blk2_ret \(PowerRetentionLayer\)"):
        GenerationEngine(net, BucketLattice(batch_sizes=(1,), seq_lens=(8,)),
                         slots=2, max_new_tokens=8, page_size=8,
                         speculative_k=2)
    # a window of tokens handed to the layer itself is refused too
    from deeplearning4j_tpu.nn.decode import CacheStep

    conf = net.conf.vertices["blk0_ret"].layer
    entry = net.init_kv_cache(2, 16)["blk0_ret"]
    with pytest.raises(ValueError, match="one token a row"):
        layer.PowerRetentionImpl().apply_cached(
            conf, net.params["blk0_ret"], jnp.zeros((2, 3, 64)), entry,
            CacheStep(None, jnp.zeros((2, 3), jnp.int32)))


def _spans(rec, name):
    return [e for e in rec.events
            if e.get("event") == "span" and e.get("name") == name]


def test_engine_serves_the_block_over_http_in_bfloat16(W):
    """`POST /generate` through `ServingServer` and `GenerationEngine`:
    no step retraces after the warm-up, every warmed step aliases the
    whole state, the weights are held in bfloat16 and the state in
    float32, the window's `state_resets` add up to the requests admitted
    and the `meta` event and /stats say what a slot's state costs."""
    net = tiny_net(W, "bfloat16")
    rec = Recorder(path=None)
    engine = GenerationEngine(
        net, BucketLattice(batch_sizes=(1,), seq_lens=(8, 16)), slots=3,
        max_new_tokens=8, page_size=8, prefill_chunk=8, recorder=rec)
    assert engine.warmup() == 2      # the 8-token chunk and the decode step
    worker = engine.fleet_workers()[0]
    assert {a.dtype for a in jax.tree.leaves(engine.weights.current.params)} \
        == {jnp.dtype("bfloat16")}
    assert {a.dtype for a in jax.tree.leaves(worker.cache)} \
        == {jnp.dtype("float32")}
    per_slot = 3 * (2 * 16 * D + 2 * D) * 4     # layers x (s + z) x float32
    assert tree_bytes(worker.cache) == 3 * per_slot
    costs = [e for e in rec.events if e.get("event") == "cost"]
    assert len(costs) == 2 and all(
        e["alias_bytes"] == 3 * per_slot for e in costs), costs
    meta = [e for e in rec.events if e.get("event") == "meta"
            and e.get("role") == "generation-engine"][0]
    for described in (meta["cache"], engine.stats()["cache"]):
        assert described["rows"] == {} and described["bytes_per_token"] == 0
        assert described["states"] == {"s": 3 * 2 * 16 * D * 4,
                                       "z": 3 * 2 * D * 4}
        assert described["state_bytes_per_slot"] == per_slot
    # each warmed program's instructions by region (telemetry/costbook.py):
    # the state pass is attention's; a chunk writes the state, a decode
    # step's kernel writes it in place
    regions = {e["entry"]: set(e["ops"].values()) for e in rec.events
               if e.get("event") == "regions"}
    assert {"attention", "norm", "ffn", "head"} <= regions["decode"]
    assert "attention/cache_write" in regions["prefill"]
    server = ServingServer(engine, port=0).start()
    asked = ((5, 8), (13, 6), (16, 3), (7, 8), (9, 2))
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
    assert engine.trace_count == 2, "a step retraced after the warm-up"
    assert engine.failed == 0
    chunks = _spans(rec, "prefill_chunk")
    assert len(chunks) == 1 + 2 + 2 + 1 + 2         # chunks of 8
    # a program's counter comes home with its tokens, under the span that
    # says it `fetched` them: the step dispatched after it, or a lone fetch
    resets = {e["fetched"]: e["state_resets"] for e in rec.events
              if e.get("event") == "span" and e.get("fetched") is not None}
    assert [resets[e["program"]] for e in chunks] == [int(e["start"] == 0)
                                                      for e in chunks]
    admitted = [e for e in rec.events if e.get("event") == "admit"]
    assert sum(resets[e["program"]] for e in chunks) \
        == len(admitted) == len(asked)
    assert all(resets[e["program"]] == 0
               for e in _spans(rec, "decode_step"))
    assert sum(resets.values()) == len(asked)   # and nothing counted twice


def test_a_second_request_in_a_slot_gets_the_tokens_a_fresh_engine_gives(W):
    """One slot, so the second request takes the first one's place."""
    rng = np.random.default_rng(3)
    first, second = (rng.integers(0, 128, n).tolist() for n in (14, 11))

    def engine():
        return GenerationEngine(
            tiny_net(W), BucketLattice(batch_sizes=(1,), seq_lens=(8, 16)),
            slots=1, max_new_tokens=8, page_size=8, prefill_chunk=8).start()

    used, fresh = engine(), engine()
    try:
        used.generate(first, 8)
        assert used.generate(second, 8) == fresh.generate(second, 8)
    finally:
        used.drain()
        fresh.drain()


def test_bytes_per_slot_is_the_cache_trees_bytes_a_slot_and_describe_splits_it(W):
    net = tiny_net(W, "bfloat16")
    sizes = set()
    for max_seq in (24, 120):       # the state does not grow with capacity
        plan = CachePlan(max_seq, 8, n_slots=5, page_size=8)
        cache = net.init_kv_cache(5, plan.capacity, "f32", 8)
        assert plan.bytes_per_slot(net) * 5 == tree_bytes(cache)
        assert bytes_per_slot(plan.cache_specs(net)) == plan.bytes_per_slot(net)
        said = plan.describe(net)
        assert said["rows"] == {} and said["bytes_per_token"] == 0
        assert said["state_bytes_per_slot"] == plan.bytes_per_slot(net) \
            == sum(said["states"].values())
        sizes.add(plan.bytes_per_slot(net))
    assert sizes == {3 * (2 * 16 * D + 2 * D) * 4}
    # int8 has rows of keys and values to quantise; a state stays as it is
    assert net.kv_cache_specs(32, "int8", 8) == net.kv_cache_specs(32, "f32", 8)
    # a net of rows: all of a slot is billed to its positions
    rows = transformer_lm(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                          d_ff=64, max_length=64, dtype="bfloat16")
    plan = CachePlan(24, 8, n_slots=5, page_size=8)
    said = plan.describe(rows)
    assert said["states"] == {} and said["state_bytes_per_slot"] == 0
    assert said["bytes_per_token"] * plan.capacity == plan.bytes_per_slot(rows)


@pytest.mark.parametrize("model", ["transformer_lm", "latent_moe_lm"])
def test_nets_of_rows_keep_their_steps(model):
    """The walk's new third value and the plan's new question change
    nothing for a net whose cache is rows: the same specs (no array
    marked "slot"), the same counters, the same number of values from a
    step, a verify fn that builds, and the tokens of the full forward."""
    if model == "transformer_lm":
        net = transformer_lm(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                             d_ff=64, max_length=64).init(seed=3)
        counters, arrays = (), {"k", "v"}
    else:
        net = latent_moe_lm(
            vocab_size=64, d_model=32, n_heads=2, n_layers=2, q_rank=12,
            kv_rank=8, nope_dim=8, rope_dim=4, v_dim=8, d_ff=48,
            n_dense_layers=1, n_experts=4, top_k=2, d_expert=16).init(seed=3)
        counters, arrays = ("moe_pairs", "moe_rows", "moe_max_load"), \
            {"ckv", "kpe"}
    specs = net.kv_cache_specs(32)
    assert all(set(e) == arrays and all(len(s) == 2 for s in e.values())
               for e in specs.values())
    fns = (net.prefill_fn(), net.incremental_decode_fn(), net.verify_decode_fn())
    assert [f.counters for f in fns] == [counters] * 3
    n_out = 3 if counters else 2
    cache = net.init_kv_cache(3, 32)
    tokens = np.random.default_rng(4).integers(0, 64, 12).astype(np.int32)
    out = fns[0](net.params, net.state, cache, tokens[None, :8],
                 np.ones((1, 8), np.float32), np.array([2], np.int32),
                 np.array([0], np.int32), np.array([7], np.int32))
    assert len(out) == n_out
    got, cache = [np.asarray(out[0][0])], out[1]
    for t in range(8, 12):
        tok, pos = np.zeros(3, np.int32), np.full(3, 31, np.int32)
        tok[2], pos[2] = tokens[t], t
        out = fns[1](net.params, net.state, cache, tok, pos)
        assert len(out) == n_out
        got.append(np.asarray(out[0][2]))
        cache = out[1]
    full = np.asarray(net.output(tokens[None, :]))[0, 7:]
    assert np.abs(np.stack(got) - full).max() < 1e-5
