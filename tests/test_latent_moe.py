"""The latent-attention / dropless-expert block (`models/latent_moe.py`)
against the benchmark's plain reference, which is loaded by path from
`benchmarks/reference/pangu_ultra_moe.py` and imports nothing of the
program. Tiny widths that keep every ratio of the served configuration:
low-rank query and key-value latents, a no-rope and a rope part of each
head, 3 of 16 experts held of which the top 4 are selected, a shared
expert, one dense and two expert layers. Weights are seeded here, in the
reference's layout, and laid into the program's tree by name."""
import importlib.util
import json
import os
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.latent_moe import latent_moe_lm
from deeplearning4j_tpu.models.transformer import transformer_lm
from deeplearning4j_tpu.nn.decode import CacheStep
from deeplearning4j_tpu.nn.layers.latent_attention import (
    LatentAttentionImpl,
    latent_attention,
)
from deeplearning4j_tpu.nn.layers.moe import (
    DroplessMoEImpl,
    DroplessMoELayer,
    round_rows,
)
from deeplearning4j_tpu.serving.buckets import BucketLattice
from deeplearning4j_tpu.serving.engine import GenerationEngine
from deeplearning4j_tpu.serving.kvcache import CachePlan, bytes_per_slot
from deeplearning4j_tpu.serving.server import ServingServer
from deeplearning4j_tpu.telemetry import Recorder
from deeplearning4j_tpu.telemetry.memstat import tree_bytes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "benchmarks", "reference", "pangu_ultra_moe.py")
    spec = importlib.util.spec_from_file_location("ref_pangu_ultra_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

DIMS = {"d": 64, "H": 4, "L": 3, "n_dense": 1, "q_rank": 24, "kv_rank": 16,
        "nope": 16, "rope": 8, "v": 16, "F": 160, "Fe": 32, "E": 16,
        "held": 3, "first_expert": 0, "top_k": 4, "n_shared": 1,
        "scaling": 2.5, "theta": 25.6e6, "eps": 1e-5, "V": 128}
_ATTN = ("Wqa", "q_norm", "Wqb", "Wkva", "kv_norm", "Wkvb", "Wo")


def seeded_weights(seed, dims=DIMS):
    """The reference's weights: matrices N(0, gain^2 / fan_in) (query
    x 2, head x 2, so that attention picks rows and logits spread), norm
    gains 1 + N(0, 0.02), a unit embedding."""
    rng = np.random.default_rng(seed)

    def mat(*shape, fan, gain=1.0):
        return jnp.asarray(rng.normal(0, gain / fan ** 0.5, shape), jnp.float32)

    def vec(n):
        return jnp.asarray(1 + 0.02 * rng.normal(size=n), jnp.float32)

    d, H, qr, c = dims["d"], dims["H"], dims["q_rank"], dims["kv_rank"]
    n, r, v, Fe = dims["nope"], dims["rope"], dims["v"], dims["Fe"]
    layers = []
    for i in range(dims["L"]):
        w = {"n1": vec(d), "n2": vec(d), "n3": vec(d), "n4": vec(d),
             "Wqa": mat(d, qr, fan=d), "q_norm": vec(qr),
             "Wqb": mat(qr, H * (n + r), fan=qr, gain=2.0),
             "Wkva": mat(d, c + r, fan=d), "kv_norm": vec(c),
             "Wkvb": mat(c, H * (n + v), fan=c),
             "Wo": mat(H * v, d, fan=H * v)}
        if i < dims["n_dense"]:
            w.update(Wgate=mat(d, dims["F"], fan=d), Wup=mat(d, dims["F"], fan=d),
                     Wdown=mat(dims["F"], d, fan=dims["F"]))
        else:
            w.update(_expert_weights(mat, dims, dims["held"]))
        layers.append(w)
    return {"embed": mat(dims["V"], d, fan=1.0), "norm_f": vec(d),
            "Wout": mat(d, dims["V"], fan=d, gain=2.0), "layers": layers}


def _expert_weights(mat, dims, held):
    d, Fe, Fs = dims["d"], dims["Fe"], dims["n_shared"] * dims["Fe"]
    return {"Wg": mat(d, dims["E"], fan=d),
            "We_gate": mat(held, d, Fe, fan=d), "We_up": mat(held, d, Fe, fan=d),
            "We_down": mat(held, Fe, d, fan=Fe),
            "Ws_gate": mat(d, Fs, fan=d), "Ws_up": mat(d, Fs, fan=d),
            "Ws_down": mat(Fs, d, fan=Fs)}


def program_attention(w, dims=DIMS):
    """The program keeps the two up-projections split by what their
    columns make (the query's two output-major); the reference one
    matrix each, per head [q_nope | q_pe] and [k_nope | v]."""
    H, n, r, v = dims["H"], dims["nope"], dims["rope"], dims["v"]
    Wqb = w["Wqb"].reshape(-1, H, n + r)
    Wkvb = w["Wkvb"].reshape(-1, H, n + v)
    return {"Wqa": w["Wqa"], "q_norm": w["q_norm"],
            "Wqb_nope": Wqb[..., :n].reshape(-1, H * n).T,
            "Wqb_rope": Wqb[..., n:].reshape(-1, H * r).T,
            "Wkva": w["Wkva"], "kv_norm": w["kv_norm"],
            "Wkvb_k": Wkvb[..., :n].reshape(-1, H * n),
            "Wkvb_v": Wkvb[..., n:].reshape(-1, H * v), "Wo": w["Wo"]}


def program_params(W, dtype=jnp.float32):
    out = {"embed": {"W": W["embed"]}, "norm_f": {"gamma": W["norm_f"]},
           "out": {"W": W["Wout"]}}
    for i, w in enumerate(W["layers"]):
        p = f"blk{i}"
        out.update({f"{p}_n{j}": {"gamma": w[f"n{j}"]} for j in (1, 2, 3, 4)})
        out[f"{p}_attn"] = program_attention(w)
        out[f"{p}_ff"] = {k: x for k, x in w.items()
                          if k not in _ATTN and not k.startswith("n")}
    return jax.tree.map(lambda x: x.astype(dtype), out)


def tiny_net(W, dtype="float32", dims=DIMS):
    net = latent_moe_lm(
        vocab_size=dims["V"], d_model=dims["d"], n_heads=dims["H"],
        n_layers=dims["L"], q_rank=dims["q_rank"], kv_rank=dims["kv_rank"],
        nope_dim=dims["nope"], rope_dim=dims["rope"], v_dim=dims["v"],
        d_ff=dims["F"], n_dense_layers=dims["n_dense"], n_experts=dims["E"],
        top_k=dims["top_k"], d_expert=dims["Fe"], n_shared=dims["n_shared"],
        first_expert=dims["first_expert"], n_held=dims["held"],
        routed_scaling=dims["scaling"], rope_theta=dims["theta"],
        eps=dims["eps"], dtype=dtype, param_dtype=dtype)
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
# order of float32 sums alone (the blockwise softmax, the folded key
# product), 1e-5 in a log-probability here; 2e-4 leaves that twenty times
# of room and is a fiftieth of what bfloat16 anywhere reads (>= 1e-2,
# `test_bfloat16_where_float32_is_stated_fails`)
TOL = 2e-4


def test_full_forward_matches_the_reference(W, tokens):
    net = tiny_net(W)
    with jax.default_matmul_precision("highest"):
        probs = net.output(tokens[None, :])
    assert np.abs(logp(probs[0]) - log_probs_ref(W, tokens)).max() < TOL


def _through_the_cache(net, tokens, rows=range(16), capacity=48, slot=1,
                       slots=3):
    """{position: log-probabilities}: the prompt's 32 tokens in two
    chunks of 16 (the rows `rows` of each chunk read by `last_idx`, the
    cache rebuilt each time: EVERY position by default), then 8 decode
    steps through the latent cache."""
    prefill = jax.jit(net.prefill_fn())
    step = jax.jit(net.incremental_decode_fn())
    ones, row = np.ones((1, 16), np.float32), np.array([slot], np.int32)
    out = {}
    with jax.default_matmul_precision("highest"):
        for j in rows:
            cache = net.init_kv_cache(slots, capacity)
            for c in (0, 1):
                probs, cache, _ = prefill(
                    net.params, net.state, cache, tokens[None, 16 * c:16 * c + 16],
                    ones, row, np.array([16 * c], np.int32),
                    np.array([j], np.int32))
                out[16 * c + j] = logp(probs[0])
        for t in range(32, len(tokens)):
            tok = np.zeros(slots, np.int32)
            pos = np.full(slots, capacity - 1, np.int32)
            tok[slot], pos[slot] = tokens[t], t
            probs, cache, _ = step(net.params, net.state, cache, tok, pos)
            out[t] = logp(probs[slot])
    return out


def test_prefill_in_two_chunks_then_decode_matches_the_reference(W, tokens):
    got = _through_the_cache(tiny_net(W), tokens)
    want = log_probs_ref(W, tokens)
    assert sorted(got) == list(range(40))
    assert max(np.abs(got[t] - want[t]).max() for t in got) < TOL


def test_bfloat16_where_float32_is_stated_fails(W, tokens):
    got = _through_the_cache(tiny_net(W, "bfloat16"), tokens, rows=(15,))
    want = log_probs_ref(W, tokens)
    assert max(np.abs(got[t] - want[t]).max() for t in got) > 50 * TOL


def test_latent_space_decode_equals_the_expanded_form(W):
    """One decode step of four slots at different depths over a cache of
    random rows: the query folded through `Wkvb`'s key half against the
    rows as they lie, and keys and values rebuilt from them."""
    conf = tiny_net(W).layer_vertices["blk1_attn"].layer
    p = program_attention(W["layers"][1])
    rng = np.random.default_rng(3)
    B, S = 4, 40
    cache = {"ckv": jnp.asarray(rng.normal(size=(B, S, 16)), jnp.float32),
             "kpe": jnp.asarray(rng.normal(size=(B, S, 8)), jnp.float32)}
    x = jnp.asarray(rng.normal(size=(B, 1, 64)), jnp.float32)
    pos = jnp.asarray([[0], [7], [23], [39]], jnp.int32)
    with jax.default_matmul_precision("highest"):
        y_lat, c_lat = latent_attention(conf, p, x, pos, cache=cache,
                                        latent=True)
        y_exp, c_exp = latent_attention(conf, p, x, pos, cache=cache,
                                        latent=False)
        step = LatentAttentionImpl().apply_cached(conf, p, x, cache,
                                                  CacheStep(None, pos))
    assert np.abs(np.asarray(y_lat - y_exp)).max() < 1e-5
    assert all(np.array_equal(c_lat[k], c_exp[k]) for k in ("ckv", "kpe"))
    assert np.array_equal(step[0], y_lat)       # a decode step is latent
    assert np.abs(np.asarray(y_lat)).max() > 0.1


def _share_layer(first, held, full):
    conf = DroplessMoELayer(n_in=64, n_out=64, n_experts=16, top_k=4,
                            d_hidden=32, n_shared=1, first_expert=first,
                            n_held=held, routed_scaling=2.5, activation="silu")
    cut = slice(first, first + held)
    p = dict(full, We_gate=full["We_gate"][cut], We_up=full["We_up"][cut],
             We_down=full["We_down"][cut])
    return conf, p


def test_the_shares_add_up_to_the_uncut_layer():
    """Six chips hold experts 0-2, 3-5, ..., 15 of one layer. The routed
    parts of all shares, with the shared expert counted once, are the
    reference's layer holding all 16."""
    rng = np.random.default_rng(9)

    def mat(*shape, fan, gain=1.0):
        return jnp.asarray(rng.normal(0, gain / fan ** 0.5, shape), jnp.float32)

    full = _expert_weights(mat, DIMS, 16)
    x = jnp.asarray(rng.normal(size=(37, 64)), jnp.float32)
    impl = DroplessMoEImpl()
    with jax.default_matmul_precision("highest"):
        whole = ref.experts(x, full, dict(DIMS, first_expert=0), ref.mm_highest)
        shared = ref.gated(x, full["Ws_gate"], full["Ws_up"], full["Ws_down"],
                           ref.mm_highest)
        total, pairs = 0.0, 0
        shares = [(f, min(3, 16 - f)) for f in range(0, 16, 3)]
        for first, held in shares:
            y, counts = impl.apply_counted(*_share_layer(first, held, full), x)
            total = total + (y - shared)
            pairs += int(counts["moe_pairs"])
            # and each share is what the reference gives for that share
            mine = ref.experts(x, _share_layer(first, held, full)[1],
                               dict(DIMS, first_expert=first), ref.mm_highest)
            assert np.abs(np.asarray(y - mine)).max() < 1e-5
    assert np.abs(np.asarray(total + shared - whole)).max() < 2e-5
    assert pairs == 37 * 4          # every selected pair is some share's
    assert np.abs(np.asarray(whole - shared)).max() > 0.1


def test_no_pair_is_dropped_when_every_token_picks_one_held_expert():
    """A router of 64 experts that sends all 100 tokens to experts 1, 5,
    9 and 13: of the three held (0-2) expert 1 gets every token, four
    times what a round holds. Nothing is dropped, and the counters read
    the test's own count."""
    rng = np.random.default_rng(11)

    def mat(*shape, fan, gain=1.0):
        return jnp.asarray(rng.normal(0, gain / fan ** 0.5, shape), jnp.float32)

    w = _expert_weights(mat, DIMS, 3)
    Wg = np.zeros((64, 64), np.float32)
    Wg[0, [1, 5, 9, 13]] = 1.0
    w["Wg"] = jnp.asarray(Wg)
    x = np.asarray(rng.normal(size=(100, 64)), np.float32)
    x[:, 0] = 3.0 + rng.random(100)     # feature 0 drives the four scores
    valid = np.ones(100, bool)
    valid[[4, 17]] = False              # two pad rows select nothing
    conf, p = _share_layer(0, 3, w)
    conf.n_experts = 64
    with jax.default_matmul_precision("highest"):
        y, counts = DroplessMoEImpl().apply_counted(
            conf, p, jnp.asarray(x), jnp.asarray(valid))
        want = ref.experts(jnp.asarray(x), w, dict(DIMS, first_expert=0),
                           ref.mm_highest)
        shared = ref.gated(jnp.asarray(x), w["Ws_gate"], w["Ws_up"],
                           w["Ws_down"], ref.mm_highest)
    y, want, shared = (np.asarray(a) for a in (y, want, shared))
    assert np.abs(y[valid] - want[valid]).max() < 2e-5
    assert np.abs(y[~valid] - shared[~valid]).max() < 2e-5
    assert np.abs(want - shared)[valid].min(axis=0).max() > 0.01
    rows_a_round = round_rows(100, 4, 64)
    rounds = -(-98 // rows_a_round)
    assert rows_a_round == 32 and rounds == 4   # the fourth holds 2
    assert {k: int(v) for k, v in counts.items()} == {
        "moe_pairs": 98, "moe_rows": rounds * 3 * rows_a_round,
        "moe_max_load": 98}


def test_a_step_counts_the_rows_its_caller_calls_live_and_no_position(W):
    """`live` is the caller's word on which rows hold a request: a
    decode step counts, and routes, those rows and no others, wherever
    their tokens lie. Without it every row is real, a token at the
    cache's last position too: its logits are what the same token reads
    in a step that calls its row live."""
    net, slots, capacity = tiny_net(W), 4, 16
    step = jax.jit(net.incremental_decode_fn())
    tok = np.array([3, 7, 11, 5], np.int32)
    pos = np.array([0, capacity - 1, 0, 0], np.int32)

    def run(*live):
        with jax.default_matmul_precision("highest"):
            probs, _, counts = step(net.params, net.state,
                                    net.init_kv_cache(slots, capacity),
                                    tok, pos, *live)
        return np.asarray(probs), dict(zip(step.counters, map(int, counts)))

    layers = DIMS["L"] - DIMS["n_dense"]
    rows = layers * DIMS["held"] * round_rows(slots, DIMS["top_k"], DIMS["E"])
    all_probs, all_counts = run()
    two_probs, two_counts = run(np.array([False, True, True, False]))
    none_probs, none_counts = run(np.zeros(slots, bool))
    assert none_counts == {"moe_pairs": 0, "moe_rows": 0, "moe_max_load": 0}
    assert 0 < two_counts["moe_pairs"] < all_counts["moe_pairs"]
    assert two_counts["moe_rows"] == all_counts["moe_rows"] == rows
    # rows 1 and 2 are routed alike with and without the mask, the last
    # position among them; rows 0 and 3 lose their routed experts
    assert np.abs(two_probs[[1, 2]] - all_probs[[1, 2]]).max() < 1e-6
    assert np.abs(none_probs[1] - all_probs[1]).max() > 1e-4
    assert np.abs(two_probs[[0, 3]] - none_probs[[0, 3]]).max() < 1e-6


def _spans(rec, name):
    return [e for e in rec.events
            if e.get("event") == "span" and e.get("name") == name]


def test_engine_serves_the_block_over_http_in_bfloat16(W):
    """`POST /generate` through `ServingServer` and `GenerationEngine`:
    no step retraces after the warm-up, every warmed step aliases the
    whole latent cache, the weights are held in bfloat16, the spans carry
    the expert layer's counters and the `meta` event the cache row's
    kind."""
    net = tiny_net(W, "bfloat16")
    rec = Recorder(path=None)
    engine = GenerationEngine(
        net, BucketLattice(batch_sizes=(1,), seq_lens=(8, 16)), slots=3,
        max_new_tokens=8, page_size=8, prefill_chunk=8, recorder=rec)
    assert engine.warmup() == 2      # the 8-token chunk and the decode step
    worker = engine.fleet_workers()[0]
    assert {a.dtype for a in jax.tree.leaves(engine.weights.current.params)} \
        == {jnp.dtype("bfloat16")}
    cache_bytes = tree_bytes(worker.cache)
    assert cache_bytes == 3 * 24 * 3 * (16 + 8) * 2     # slots, rows, layers
    costs = [e for e in rec.events if e.get("event") == "cost"]
    assert len(costs) == 2 and all(
        e["alias_bytes"] == cache_bytes for e in costs), costs
    meta = [e for e in rec.events if e.get("event") == "meta"
            and e.get("role") == "generation-engine"][0]
    assert meta["cache"]["rows"] == {"ckv": 3 * 16 * 2, "kpe": 3 * 8 * 2}
    assert meta["cache"]["bytes_per_token"] == 144
    server = ServingServer(engine, port=0).start()
    try:
        rng = np.random.default_rng(2)
        for plen, new in ((5, 8), (13, 6), (16, 3)):
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
    steps = _spans(rec, "decode_step") + _spans(rec, "prefill_chunk")
    assert len(_spans(rec, "prefill_chunk")) == 5   # 1 + 2 + 2 chunks of 8
    # a program's counters come home with its tokens, under the span that
    # says it `fetched` them: the step dispatched after it, or a lone fetch
    home = {e["fetched"]: e for e in rec.events
            if e.get("event") == "span" and e.get("fetched") is not None}
    assert sorted(home) == sorted(e["program"] for e in steps)
    counted = {e["program"]: home[e["program"]] for e in steps}
    for c in counted.values():  # a lone token may select no held expert
        assert 0 <= c["moe_max_load"] <= c["moe_pairs"] <= c["moe_rows"], c
        assert (c["moe_pairs"] == 0) == (c["moe_rows"] == 0), c
    assert all(counted[e["program"]]["moe_pairs"] > 0
               for e in _spans(rec, "prefill_chunk"))
    # a decode step of one live slot: 2 expert layers, at most 3 held
    # experts selected in each, one round of 3 x 8 rows where any is
    assert all(counted[e["program"]]["moe_pairs"] <= 6
               and counted[e["program"]]["moe_rows"] in (0, 24, 48)
               for e in _spans(rec, "decode_step") if e["n_active"] == 1)


@pytest.mark.parametrize("kind,kv_dtype", [
    ("keys and values", "f32"), ("keys and values", "int8"),
    ("latent rows", "f32")])
def test_bytes_per_slot_is_the_cache_trees_bytes_a_slot(W, kind, kv_dtype):
    net = (tiny_net(W, "bfloat16") if kind == "latent rows" else
           transformer_lm(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                          d_ff=64, max_length=64, dtype="bfloat16"))
    plan = CachePlan(24, 8, n_slots=5, page_size=8, kv_dtype=kv_dtype)
    cache = net.init_kv_cache(5, plan.capacity, kv_dtype, 8)
    assert plan.bytes_per_slot(net) * 5 == tree_bytes(cache)
    assert bytes_per_slot(plan.cache_specs(net)) == plan.bytes_per_slot(net)
    per_token = plan.describe(net)["bytes_per_token"]
    assert per_token * plan.capacity == plan.bytes_per_slot(net)
    if kind == "latent rows":
        assert per_token == 3 * (16 + 8) * 2
        with pytest.raises(ValueError, match="int8"):
            net.init_kv_cache(5, plan.capacity, "int8", 8)


def test_transformer_lm_steps_are_what_they_were():
    """A net without latent attention or experts keeps its programs: the
    same cache tree ([B, S, H, D] keys and values in the compute dtype),
    two values from every step and no counter, and the tokens of the
    full forward."""
    net = transformer_lm(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                         d_ff=64, max_length=64).init(seed=3)
    cache = net.init_kv_cache(3, 32)
    assert {n: {a: (x.shape, x.dtype.name) for a, x in e.items()}
            for n, e in cache.items()} == {
        f"blk{i}_attn": {"k": ((3, 32, 2, 16), "float32"),
                         "v": ((3, 32, 2, 16), "float32")} for i in (0, 1)}
    fns = (net.prefill_fn(), net.incremental_decode_fn(), net.verify_decode_fn())
    assert [f.counters for f in fns] == [(), (), ()]
    tokens = np.random.default_rng(4).integers(0, 64, 12).astype(np.int32)
    out = fns[0](net.params, net.state, cache, tokens[None, :8],
                 np.ones((1, 8), np.float32), np.array([2], np.int32),
                 np.array([0], np.int32), np.array([7], np.int32))
    assert len(out) == 2
    got, cache = [np.asarray(out[0][0])], out[1]
    for t in range(8, 12):
        tok, pos = np.zeros(3, np.int32), np.full(3, 31, np.int32)
        tok[2], pos[2] = tokens[t], t
        out = fns[1](net.params, net.state, cache, tok, pos)
        assert len(out) == 2
        got.append(np.asarray(out[0][2]))
        cache = out[1]
    full = np.asarray(net.output(tokens[None, :]))[0, 7:]
    assert np.abs(np.stack(got) - full).max() < 1e-5
    lowered = jax.jit(fns[1]).lower(net.params, net.state, cache,
                                    np.zeros(3, np.int32), np.zeros(3, np.int32))
    assert len(jax.tree.leaves(lowered.out_info)) == 1 + 4
