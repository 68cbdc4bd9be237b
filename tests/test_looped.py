"""A graph whose span of blocks runs several times a token with one set of
weights (`GraphBuilder.loop`, `models.looped.looped_lm`), against the
plain reference of the `ouro` family, which is loaded by path from
`benchmarks/reference/ouro.py` and imports nothing of the program: the
containers' forward and its gradient, the serving walk (prefill chunks
and decode steps with the pass axis of every cached layer), a loop of
one against the unlooped net, the grouped kernels told a pass, and what
the cache bills.

Tiny size: d 64, 4 heads of 16, 3 blocks, 3 passes, a vocabulary of 97,
seeded random weights, float32 throughout. Tolerances: the program and
the reference differ only in the order of float32 sums (the program
fuses and reorders products and norms; the reference's products are
HIGHEST precision): over 9 block applications the log-probabilities
(logits of standard deviation about 2) differ by 2.2e-5 at the most
measured, so 2e-4 against them, 1e-5 against the probabilities, and 1e-4
relative against a gradient leaf (a leaf's gradient sums many more
products). Each fault of the reference (one pass, a cache shared by the
passes, the final norm after the last pass only) moves the logits by
6.3-8.7 measured; the test asks for more than 0.5.
"""

import copy
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.latent_moe import sandwich_moe_lm
from deeplearning4j_tpu.models.looped import looped_lm
from deeplearning4j_tpu.nn.conf import GroupedAttentionLayer, LoopConf
from deeplearning4j_tpu.nn.conf.graph_conf import (
    ComputationGraphConfiguration,
)
from deeplearning4j_tpu.nn.decode import (
    cache_specs,
    init_cache,
    make_decode_fn,
    make_prefill_fn,
)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers import grouped_attention as ga
from deeplearning4j_tpu.ops import decode_attention as da
from deeplearning4j_tpu.ops import prefill_attention as pa
from deeplearning4j_tpu.serving.kvcache import CachePlan, bytes_per_slot

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "benchmarks", "reference", "ouro.py")
    spec = importlib.util.spec_from_file_location("ref_ouro", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

DIMS = {"hidden": 64, "Hq": 4, "Hk": 4, "d": 16, "L": 3, "times": 3,
        "F": 96, "theta": 1e6, "eps": 1e-6, "V": 97}
CAPACITY = 32
LOGIT_TOL = 2e-4
PROB_TOL = 1e-5


def seeded_weights(seed, dims=DIMS):
    """The reference's weights: matrices N(0, gain^2 / fan_in) (the
    query's gain 2: scores that pick keys; the head's 2), norm gains
    1 + N(0, 0.02), a unit embedding."""
    rng = np.random.default_rng(seed)
    h, H, d, F = dims["hidden"], dims["Hq"], dims["d"], dims["F"]

    def mat(*shape, gain=1.0):
        return jnp.asarray(rng.normal(0, gain / shape[0] ** 0.5, shape),
                           jnp.float32)

    def vec(n):
        return jnp.asarray(1 + 0.02 * rng.normal(size=n), jnp.float32)

    layers = [{"n1": vec(h), "n2": vec(h), "n3": vec(h), "n4": vec(h),
               "Wq": mat(h, H * d, gain=2.0), "Wk": mat(h, H * d),
               "Wv": mat(h, H * d), "Wo": mat(H * d, h),
               "Wgate": mat(h, F), "Wup": mat(h, F), "Wdown": mat(F, h)}
              for _ in range(dims["L"])]
    return {"embed": jnp.asarray(rng.normal(size=(dims["V"], h)), jnp.float32),
            "norm_f": vec(h), "Wout": mat(h, dims["V"], gain=2.0),
            "layers": layers}


def program_params(W):
    """The reference's weights under the names `looped_lm` gives them."""
    p = {"embed": {"W": W["embed"]}, "norm_f": {"gamma": W["norm_f"]},
         "out": {"W": W["Wout"]}}
    for i, w in enumerate(W["layers"]):
        b = f"blk{i}"
        p.update({f"{b}_n{j}": {"gamma": w[f"n{j}"]} for j in (1, 2, 3, 4)})
        p[f"{b}_attn"] = {n: w[n] for n in ("Wq", "Wk", "Wv", "Wo")}
        p[f"{b}_ff"] = {n: w[n] for n in ("Wgate", "Wup", "Wdown")}
    return p


def looped_net(W, times=DIMS["times"], dims=DIMS):
    net = looped_lm(dims["V"], dims["hidden"], dims["Hq"], dims["L"], times,
                    d_ff=dims["F"], n_kv_heads=dims["Hk"], head_dim=dims["d"],
                    rope_theta=dims["theta"], eps=dims["eps"]).init()
    net.params = program_params(W)
    return net


def tokens(seed, n, b=1):
    return jnp.asarray(np.random.default_rng(seed).integers(
        0, DIMS["V"], (b, n)), jnp.int32)


def ref_logits(W, toks, **kw):
    return jnp.stack([ref.forward(W, t, DIMS, **kw) for t in toks])


def output(net, toks):
    out = net.output(toks)
    return out[0] if isinstance(out, list) else out


# ----------------------------------------------------------- the forward

@pytest.mark.parametrize("seed", [0, 1])
def test_output_equals_the_reference(seed):
    W = seeded_weights(seed)
    toks = tokens(seed + 10, 12, b=2)
    got = np.log(np.asarray(output(looped_net(W), toks)))
    want = np.asarray(jax.nn.log_softmax(ref_logits(W, toks), -1))
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL)


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_each_fault_of_the_reference_moves_the_logits(fault):
    """What the limits' faults read on the chip reads here too: each
    wrong model is far from the right one, at a margin the program's
    own error does not approach."""
    W = seeded_weights(3)
    toks = tokens(13, 12, b=1)
    gap = jnp.max(jnp.abs(ref_logits(W, toks, fault=fault)
                          - ref_logits(W, toks)))
    assert gap > 0.5, (fault, float(gap))


def test_a_loop_of_one_is_the_unlooped_net_bit_for_bit():
    W = seeded_weights(4)
    toks = tokens(14, 12, b=2)
    plain = sandwich_moe_lm(
        lambda i: GroupedAttentionLayer(
            n_in=64, n_out=64, n_heads=4, n_kv_heads=4, head_dim=16,
            rope_theta=1e6, eps=1e-6, qk_norm=False, gate=False,
            activation="identity"),
        97, 64, 3, d_ff=96, n_dense_layers=3, eps=1e-6, seed=12345,
        learning_rate=3e-4, dtype="float32", param_dtype="float32").init()
    plain.params = program_params(W)
    once = looped_net(W, times=1)
    assert once.conf.loop.times == 1 and plain.conf.loop is None
    np.testing.assert_array_equal(np.asarray(output(once, toks)),
                                  np.asarray(output(plain, toks)))
    assert cache_specs(once, CAPACITY) == cache_specs(plain, CAPACITY)
    served = []
    for net in (once, plain):
        probs, _cache, _counts = make_prefill_fn(net)(
            net.params, net.state, init_cache(net, 2, CAPACITY), toks,
            jnp.ones((2, 12), jnp.int32), jnp.arange(2),
            jnp.zeros(2, jnp.int32), jnp.full(2, 11))
        served.append(np.asarray(probs))
    np.testing.assert_array_equal(*served)


def test_the_gradient_through_the_loop_equals_the_reference():
    """Every pass's use of a weight adds to its gradient: reverse mode
    through the loop against the reference's, leaf by leaf."""
    W = seeded_weights(5)
    toks = tokens(15, 10, b=2)
    net = looped_net(W)
    proj = jnp.asarray(np.random.default_rng(6).normal(
        size=(2, 10, DIMS["V"])), jnp.float32)

    def prog(params):
        out, _s, _c = net._forward(params, net.state, {"tokens": toks},
                                   train=False, rng=None, collect=True)
        x = out["norm_f"]
        return jnp.sum((x @ params["out"]["W"]) * proj)

    def want(W):
        return jnp.sum(ref_logits(W, toks) * proj)

    g = jax.grad(prog)(net.params)
    g_ref = program_params(jax.grad(want)(W))
    for name, leaves in g_ref.items():
        for leaf, x in leaves.items():
            scale = float(jnp.max(jnp.abs(x)))
            err = float(jnp.max(jnp.abs(g[name][leaf] - x)))
            assert err <= 1e-4 * scale, (name, leaf, err, scale)
    # a weight used in every pass: its gradient is no one pass's
    assert float(jnp.max(jnp.abs(g["blk0_attn"]["Wq"]))) > 0


def test_pipeline_stages_refuse_a_looped_graph():
    """Stages cut a graph once; a looped span would run on one stage again
    and again."""
    from deeplearning4j_tpu.parallel.pipeline import PipelinePlan

    with pytest.raises(ValueError, match="looped graph"):
        PipelinePlan(looped_net(seeded_weights(11)), 3)


def test_the_loop_is_serialised_and_checked():
    net = looped_net(seeded_weights(7))
    again = ComputationGraphConfiguration.from_json(net.conf.to_json())
    assert again.loop == net.conf.loop
    assert ComputationGraph(again).loop == net.loop
    assert net.loop[0] == "embed" and net.loop[1][0] == "blk0_n1" \
        and net.loop[1][-1] == "norm_f" and net.loop[2] == 3
    for first, last, said in (
            # blk1_res2 reads blk1_res1, inside the span and not its last
            ("blk1_n1", "blk1_n3", "only the loop's last vertex"),
            # blk0_attn reads blk0_n1 and blk0_res1 reads embed
            ("blk0_attn", "blk0_res1", "reads one vertex outside it")):
        conf = json.loads(net.conf.to_json())
        conf["loop"].update(first=first, last=last)
        with pytest.raises(ValueError, match=said):
            ComputationGraph(ComputationGraphConfiguration.from_json(
                json.dumps(conf)))


# ---------------------------------------------------------- the serving walk

def test_prefill_in_two_chunks_then_decode_equals_the_reference():
    """Two rows: prompts of 16 and 9 in two chunks of 8 (the second row's
    second chunk is padded), then four decode steps, the second row not
    live in the third; every step's probabilities against the reference's
    full forward at that position, and the idle step leaves its rows
    untouched."""
    W = seeded_weights(8)
    net = looped_net(W)
    seq = tokens(18, 20, b=2)
    want = jax.nn.softmax(ref_logits(W, seq), -1)
    prefill, decode = make_prefill_fn(net), make_decode_fn(net)
    assert prefill.counters[-1] == "loop_passes"
    cache = init_cache(net, 2, CAPACITY)
    lens = [16, 9]
    for c in range(2):
        toks = seq[:, 8 * c:8 * c + 8]
        kmask = jnp.asarray([[1] * 8, [1] * min(8, max(0, 9 - 8 * c))
                             + [0] * (8 - min(8, max(0, 9 - 8 * c)))],
                            jnp.int32)
        last = jnp.asarray([7, (lens[1] - 1) - 8 * c if c else 7])
        probs, cache, counts = prefill(
            net.params, net.state, cache, toks, kmask, jnp.arange(2),
            jnp.full(2, 8 * c, jnp.int32), last)
        np.testing.assert_allclose(probs[0], want[0, 8 * c + 7],
                                   atol=PROB_TOL)
        assert int(counts[-1]) == 3 * 2          # passes x rows with tokens
        # the rows some query could see, over 3 layers x 3 passes: 8 + 8,
        # then 8 + 8 and 8 + 1 before and in the second chunk
        assert prefill.counters[0] == "attn_rows_seen"
        assert int(counts[0]) == 9 * (16, 25)[c]
    np.testing.assert_allclose(probs[1], want[1, lens[1] - 1], atol=PROB_TOL)
    pos = np.asarray(lens)
    for step in range(4):
        live = np.array([True, step != 2])
        before = jax.tree.map(lambda a: a[1], cache)
        probs, cache, counts = decode(
            net.params, net.state, cache, seq[jnp.arange(2), pos],
            jnp.asarray(pos, jnp.int32), jnp.asarray(live))
        for r in range(2):
            if live[r]:
                np.testing.assert_allclose(probs[r], want[r, pos[r]],
                                           atol=PROB_TOL)
        assert int(counts[-1]) == 3 * int(live.sum())
        if not live[1]:
            jax.tree.map(np.testing.assert_array_equal, before,
                         jax.tree.map(lambda a: a[1], cache))
        pos = pos + live


def test_each_pass_writes_its_own_rows():
    """After a prefill, pass t's block of a layer's entry holds the keys
    of pass t's input: the passes' rows differ, and a cache shared by
    the passes would be another model (the reference's fault reads so)."""
    W = seeded_weights(9)
    net = looped_net(W)
    cache = init_cache(net, 1, CAPACITY)
    _p, cache, _c = make_prefill_fn(net)(
        net.params, net.state, cache, tokens(19, 8), jnp.ones((1, 8),
                                                              jnp.int32),
        jnp.arange(1), jnp.zeros(1, jnp.int32), jnp.full(1, 7))
    k = np.asarray(cache["blk1_attn"]["k"])              # [1, P, H, R, d]
    assert k.shape == (1, 3, 4, CAPACITY, 16)
    assert np.all(np.abs(k[0, :, :, :8]).sum(-1) > 0)
    assert np.all(k[0, :, :, 8:] == 0)
    for t in range(1, 3):
        assert np.max(np.abs(k[0, t, :, :8] - k[0, 0, :, :8])) > 0.1


def test_the_cache_bills_passes_times_rows():
    net = looped_net(seeded_weights(10))
    specs = cache_specs(net, CAPACITY)
    assert set(specs) == {f"blk{i}_attn" for i in range(3)}
    for arrays in specs.values():
        assert arrays == {n: ((3, 4, CAPACITY, 16), "float32")
                          for n in ("k", "v")}
    per_slot = 3 * 3 * 2 * 4 * 16 * 4 * CAPACITY  # passes x layers x k,v
    assert bytes_per_slot(specs) == per_slot
    plan = CachePlan(16, 16, n_slots=2)
    said = plan.describe(net)
    assert said["passes"] == 3
    assert said["bytes_per_token"] == per_slot / CAPACITY
    assert said["bytes_per_slot"] == per_slot
    cache = init_cache(net, 2, CAPACITY)
    assert sum(x.nbytes for x in jax.tree.leaves(cache)) == 2 * per_slot
    assert CachePlan(16, 16, n_slots=2).describe(
        looped_net(seeded_weights(10), times=1))["passes"] == 1


def test_a_layer_with_no_pass_axis_is_refused_inside_a_loop():
    from deeplearning4j_tpu.models.latent_moe import latent_moe_lm

    net = latent_moe_lm(64, 32, 2, 2, q_rank=16, kv_rank=16, nope_dim=8,
                        rope_dim=8, v_dim=8, d_ff=48, n_dense_layers=2)
    conf = copy.deepcopy(net.conf)
    conf.loop = LoopConf(first="blk0_n1", last="norm_f", times=2)
    looped = ComputationGraph(conf)
    with pytest.raises(ValueError, match="no pass axis"):
        make_decode_fn(looped)


# ------------------------------------------------------------ the kernels

@pytest.mark.parametrize("p", [0, 2])
def test_gqa_decode_kernel_reads_the_pass_it_is_told(p):
    """`gqa_decode_kernel` in interpret mode over an entry with a pass
    axis: the pass's rows, one query a key-value head (the looped
    model's shape), against the `jnp` twin on that pass's rows alone;
    the other passes hold NaN, which no read of them could hide."""
    rng = np.random.default_rng(p)
    B, P, H, R, D = 3, 3, 4, 64, 128
    k = np.full((B, P, H, R, D), np.nan, np.float32)
    v = np.full((B, P, H, R, D), np.nan, np.float32)
    k[:, p] = rng.normal(size=(B, H, R, D))
    v[:, p] = rng.normal(size=(B, H, R, D))
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    pos = jnp.asarray([5, 40, 63], jnp.int32)
    live = jnp.asarray([True, True, False])
    got = da.gqa_decode_kernel(q, jnp.asarray(k), jnp.asarray(v), pos, live,
                               jnp.int32(p), interpret=True, block_k=16)
    want = da.gqa_decode_jnp(q, jnp.asarray(k[:, p]), jnp.asarray(v[:, p]),
                             pos, live)
    np.testing.assert_allclose(got, want, atol=2e-5)
    assert np.all(np.asarray(got)[2] == 0)
    np.testing.assert_allclose(
        da.gqa_decode_jnp(q, jnp.asarray(k), jnp.asarray(v), pos, live,
                          jnp.int32(p)), want, atol=0)


@pytest.mark.parametrize("p", [0, 3])
def test_gqa_prefill_kernel_reads_the_pass_it_is_told(p):
    """`gqa_prefill` in interpret mode over an entry with a pass axis, a
    chunk of 128 queries one a key-value head at start 96 of a full
    entry of 256 rows, against `chunk_walk` on that pass's rows; the
    other passes hold NaN."""
    rng = np.random.default_rng(10 + p)
    b, P, H, R, D, T, start = 1, 4, 2, 256, 128, 128, 96
    conf = GroupedAttentionLayer(n_in=8, n_out=8, n_heads=H, n_kv_heads=H,
                                 head_dim=D)
    k_e = np.full((2, P, H, R, D), np.nan, np.float32)
    v_e = np.full((2, P, H, R, D), np.nan, np.float32)
    k_e[:, p] = rng.normal(size=(2, H, R, D))
    v_e[:, p] = rng.normal(size=(2, H, R, D))
    q = jnp.asarray(rng.normal(size=(b, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, T, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, T, H, D)), jnp.float32)
    keep = jnp.asarray(np.arange(T)[None] < 100)
    rows, starts = jnp.asarray([1]), jnp.asarray([start])
    got = pa.gqa_prefill(
        da.group_queries(q.transpose(0, 2, 1, 3), H), jnp.asarray(k_e),
        jnp.asarray(v_e), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        keep, rows, starts, window=0, pass_index=jnp.int32(p),
        interpret=True)
    pos = start + jnp.arange(T)[None]
    want = ga.chunk_walk(conf, q, k, v, jnp.asarray(k_e[:, p]),
                         jnp.asarray(v_e[:, p]), pos, keep, rows)
    np.testing.assert_allclose(got, want, atol=2e-5)


# --------------------------------------------- the layer's two switches

def test_norms_and_gate_are_on_by_default_and_off_drops_their_weights():
    """Grouped attention keeps its per-head norms and its gate by default
    (the nets of the window-and-full and hybrid models are built so),
    and with both off it is plain multi-head attention with neither
    weight made: its output is the reference's attention."""
    on = GroupedAttentionLayer(n_in=64, n_out=64, n_heads=4, head_dim=16,
                               rope_theta=1e6, weight_init="xavier",
                               activation="identity")
    off = GroupedAttentionLayer(n_in=64, n_out=64, n_heads=4, head_dim=16,
                                rope_theta=1e6, weight_init="xavier",
                                activation="identity", qk_norm=False,
                                gate=False)
    impl = ga.GroupedAttentionImpl()
    p_on, _ = impl.init(on, jax.random.PRNGKey(0), jnp.float32)
    p_off, _ = impl.init(off, jax.random.PRNGKey(0), jnp.float32)
    assert sorted(p_on) == ["Wg", "Wk", "Wo", "Wq", "Wv", "k_norm", "q_norm"]
    assert sorted(p_off) == ["Wk", "Wo", "Wq", "Wv"]
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 9, 64)),
                    jnp.float32)
    y_off, _ = impl.apply(off, p_off, {}, x)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.attention(r, p_off, DIMS, ref.mm_highest)
                          for r in x])
    np.testing.assert_allclose(y_off, want, atol=1e-5)
    y_on, _ = impl.apply(on, dict(p_on, **{k: p_off[k] for k in p_off}), {},
                         x)
    assert float(jnp.max(jnp.abs(y_on - y_off))) > 1e-3


# log-probabilities of two tiny nets that use grouped attention with its
# norms and gate (the window-and-full and the hybrid delta-rule models),
# recorded before the two switches existed: [0, 23, 5], [1, 7, 100] and
# the sum over every row. float32 on the CPU; 1e-6 relative covers a
# reordered sum, far under what a dropped norm or gate moves
BEFORE_SWITCHES = {"window_full": (-5.318713282586826, -5.724608566480803,
                                   -31889.88128607312),
                   "hybrid": (-4.497034814870347, -4.107015972827841,
                              -31885.9920291074)}


@pytest.mark.parametrize("name", sorted(BEFORE_SWITCHES))
def test_the_switches_defaults_keep_the_grouped_nets_outputs(name):
    from deeplearning4j_tpu.models.grouped_moe import grouped_moe_lm
    from deeplearning4j_tpu.models.hybrid_moe import hybrid_moe_lm

    net = (grouped_moe_lm(128, 64, 4, 2, 16, ["sliding_attention",
                                              "full_attention"], 8, 1, 96,
                          8, 2, 32, 0, 4)
           if name == "window_full" else
           hybrid_moe_lm(128, 64, ["linear_attention", "full_attention"],
                         n_k_heads=2, n_v_heads=4, k_head_dim=16,
                         v_head_dim=16, conv_kernel=4, n_heads=4,
                         n_kv_heads=2, head_dim=16, rotary_dim=8,
                         rope_theta=1e6, n_experts=8, top_k=2, d_expert=32,
                         n_held=4)).init(seed=7)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 128, (2, 24)),
                       jnp.int32)
    lp = np.log(np.asarray(output(net, toks), np.float64))
    np.testing.assert_allclose(
        (lp[0, 23, 5], lp[1, 7, 100], lp.sum()), BEFORE_SWITCHES[name],
        rtol=1e-6)
