"""The grouped-attention block with window and full layers side by side
(`models/grouped_moe.py`) against the benchmark's plain reference, which
is loaded by path from `benchmarks/reference/afmoe.py` and imports
nothing of the program. Tiny widths that keep the ratios of the served
configuration: 12 query heads on 2 key-value heads of 16 (six queries a
head), a window of 24, layer types S S S F, one dense and three expert
layers, 16 experts of which 4 are held, 2 a token. Weights are seeded
here, in the reference's layout, and laid into the program's tree by
name. The reference masks whole sequences; the program keeps a ring of
24 rows a slot in its window layers and 96 rows in its full one."""
import importlib.util
import json
import os
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.grouped_moe import grouped_moe_lm
from deeplearning4j_tpu.models.latent_moe import latent_moe_lm
from deeplearning4j_tpu.models.retention import retention_lm
from deeplearning4j_tpu.models.transformer import transformer_lm
from deeplearning4j_tpu.nn.layers import moe
from deeplearning4j_tpu.ops import decode_attention as op
from deeplearning4j_tpu.serving.buckets import BucketLattice
from deeplearning4j_tpu.serving.engine import GenerationEngine
from deeplearning4j_tpu.serving.kvcache import CachePlan, bytes_per_slot
from deeplearning4j_tpu.serving.server import ServingServer
from deeplearning4j_tpu.telemetry import Recorder
from deeplearning4j_tpu.telemetry.memstat import tree_bytes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(ROOT, "benchmarks", "reference", "afmoe.py")
    spec = importlib.util.spec_from_file_location("ref_afmoe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

TYPES = ("sliding_attention",) * 3 + ("full_attention",)
DIMS = {"hidden": 48, "Hq": 12, "Hk": 2, "d": 16, "window": 24, "L": 4,
        "sliding": tuple(t == "sliding_attention" for t in TYPES),
        "n_dense": 1, "F": 96, "Fe": 32, "E": 16, "held": 4,
        "first_expert": 0, "top_k": 2, "scaling": 2.448, "theta": 10000.0,
        "eps": 1e-5, "V": 128}
CAPACITY = 96       # the full layer's rows a slot: four rings long


def seeded_weights(seed, dims=DIMS, held=None, first=None):
    """The reference's weights: matrices N(0, gain^2 / fan_in), norm
    gains 1 + N(0, 0.02) (the query's x 2: scores that pick rows), an
    embedding of N(0, 1 / hidden) rows (times sqrt(hidden): unit), a
    selection bias of N(0, 0.3) that reorders most tokens' experts."""
    rng = np.random.default_rng(seed)
    held = dims["held"] if held is None else held

    def mat(*shape, fan, gain=1.0):
        return jnp.asarray(rng.normal(0, gain / fan ** 0.5, shape), jnp.float32)

    def vec(n, gain=1.0):
        return jnp.asarray(gain * (1 + 0.02 * rng.normal(size=n)), jnp.float32)

    h, Hq, Hk, d = (dims[k] for k in ("hidden", "Hq", "Hk", "d"))
    layers = []
    for i in range(dims["L"]):
        w = {"n1": vec(h), "n2": vec(h), "n3": vec(h), "n4": vec(h),
             "Wq": mat(h, Hq * d, fan=h), "Wk": mat(h, Hk * d, fan=h),
             "Wv": mat(h, Hk * d, fan=h), "Wg": mat(h, Hq * d, fan=h),
             "q_norm": vec(d, 2.0), "k_norm": vec(d),
             "Wo": mat(Hq * d, h, fan=Hq * d)}
        if i < dims["n_dense"]:
            F = dims["F"]
            w.update(Wgate=mat(h, F, fan=h), Wup=mat(h, F, fan=h),
                     Wdown=mat(F, h, fan=F))
        else:
            Fe, E = dims["Fe"], dims["E"]
            # every expert's weights are drawn, the held ones kept: a
            # share holds the same numbers the whole layer would
            gate, up, down = (mat(E, h, Fe, fan=h), mat(E, h, Fe, fan=h),
                              mat(E, Fe, h, fan=Fe))
            lo = dims["first_expert"] if first is None else first
            w.update(Wr=mat(h, E, fan=h),
                     bsel=jnp.asarray(rng.normal(0, 0.3, E), jnp.float32),
                     We_gate=gate[lo:lo + held], We_up=up[lo:lo + held],
                     We_down=down[lo:lo + held],
                     Ws_gate=mat(h, Fe, fan=h), Ws_up=mat(h, Fe, fan=h),
                     Ws_down=mat(Fe, h, fan=Fe))
        layers.append(w)
    return {"embed": mat(dims["V"], h, fan=h), "norm_f": vec(h),
            "Wout": mat(h, dims["V"], fan=h, gain=2.0), "layers": layers}


_ATTN = ("Wq", "Wk", "Wv", "Wg", "q_norm", "k_norm", "Wo")


def program_params(W, dtype=jnp.float32):
    """The reference's weights under the names `grouped_moe_lm` gives
    them; the router is `Wg` of the expert layer there, and the
    selection bias stays float32 whatever the rest is held in."""
    out = {"embed": {"W": W["embed"]}, "norm_f": {"gamma": W["norm_f"]},
           "out": {"W": W["Wout"]}}
    for i, w in enumerate(W["layers"]):
        p = f"blk{i}"
        for j in (1, 2, 3, 4):
            out[f"{p}_n{j}"] = {"gamma": w[f"n{j}"]}
        out[f"{p}_attn"] = {k: w[k] for k in _ATTN}
        ff = {k: x for k, x in w.items()
              if k not in _ATTN and not k.startswith("n")}
        if "Wr" in ff:
            ff["Wg"] = ff.pop("Wr")
        out[f"{p}_ff"] = ff
    out = jax.tree.map(lambda x: x.astype(dtype), out)
    for i, w in enumerate(W["layers"]):
        if "bsel" in w:
            out[f"blk{i}_ff"]["bsel"] = w["bsel"]
    return out


def tiny_net(W, dtype="float32", dims=DIMS, window=None, **conf_changes):
    net = grouped_moe_lm(
        dims["V"], dims["hidden"], dims["Hq"], dims["Hk"], dims["d"], TYPES,
        dims["window"] if window is None else window, dims["n_dense"],
        dims["F"], dims["E"], dims["top_k"], dims["Fe"],
        dims["first_expert"], dims["held"], routed_scaling=dims["scaling"],
        rope_theta=dims["theta"], eps=dims["eps"], dtype=dtype,
        param_dtype=dtype)
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
    return seeded_weights(37)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(5).integers(0, DIMS["V"], 80).astype(np.int32)


@pytest.fixture(scope="module")
def want(W, tokens):
    return log_probs_ref(W, tokens)


# float32 program against the float32 reference: the two differ by the
# order of float32 sums alone (the reference takes one softmax over a
# masked row, the program a running softmax over blocks of the ring and
# a merge with the chunk's own half by their log-sum-exps), 1.1e-5 in a
# log-probability here (8e-6 by the full forward); 2e-4 leaves that
# eighteen times of room and is a thousandth and less of what the faults
# below read (a bfloat16 program 0.5; a window off by one key 4.2 and
# 7.2, rotary in the full layer 3.0, no gate 2.9, no bias 5.4: an expert
# or a key swapped moves a small log-probability by whole units)
TOL = 2e-4


def test_full_forward_matches_the_reference(W, tokens, want):
    net = tiny_net(W)
    with jax.default_matmul_precision("highest"):
        probs = net.output(tokens[None, :])
    assert np.abs(logp(probs[0]) - want).max() < TOL


# the prompt's 48 tokens in four chunks of a 16-token bucket with unequal
# padding: the third crosses the window's 24 keys and wraps the ring, the
# fourth's first query (position 38) sees back to position 15, rows its
# own last tokens overwrite
CHUNKS = ((0, 13), (13, 9), (22, 16), (38, 10))


def _through_the_ring(net, tokens, slot=1, slots=3, dirty=None, chunks=CHUNKS,
                      bucket=16):
    """{position: log-probabilities}: the prompt in `chunks` (start, real
    tokens) of a `bucket`-token bucket, the last real row of each read,
    then one decode step a token to the end (80 tokens: the ring of 24
    rows is overwritten a third time from position 72 on). `dirty`: a
    cache to start from in place of a zeroed one."""
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


def test_prefill_across_the_window_then_decode_through_two_wraps(
        W, tokens, want):
    got, cache, counted = _through_the_ring(tiny_net(W), tokens)
    assert sorted(got) == [12, 21, 37, 47] + list(range(48, 80))
    assert _worst(got, want) < TOL
    assert {a.shape for e in cache.values() for a in e.values()} \
        == {(3, 2, 24, 16), (3, 2, 96, 16)}
    # rows some query of a step could see, over the four layers: a window
    # layer's are the 23 before the step's first query at the most and
    # the step's own, the full layer's every earlier row
    assert [c["attn_rows_seen"] for c in counted[:4]] \
        == [4 * 13, 4 * 22, 3 * (22 + 16) + 38, 3 * (23 + 10) + 48]
    assert [c["attn_wrapped"] for c in counted[:4]] == [0, 0, 1, 1]
    assert counted[4]["attn_rows_seen"] == 3 * 24 + 49
    assert all(c["attn_wrapped"] == 1 for c in counted[4:])
    assert all(c["moe_pairs"] <= c["moe_rows"] for c in counted)


def test_a_bfloat16_program_where_float32_is_stated_fails(W, tokens, want):
    got, _, _ = _through_the_ring(tiny_net(W, "bfloat16"), tokens)
    assert _worst(got, want) > 100 * TOL


@pytest.mark.parametrize("fault", ["window_plus_1", "window_minus_1",
                                   "rotary_in_the_full_layer",
                                   "gate_left_out", "bsel_ignored"])
def test_a_fault_in_the_layer_fails(W, tokens, want, fault):
    """Each departs from the equations in one place; through the ring and
    by the full forward alike it reads a hundred times the tolerance."""
    net = {
        "window_plus_1": lambda: tiny_net(W, window=25),
        "window_minus_1": lambda: tiny_net(W, window=23),
        "rotary_in_the_full_layer": lambda: tiny_net(
            W, blk3_attn={"rope_theta": 10000.0}),
        "gate_left_out": lambda: tiny_net(W),
        "bsel_ignored": lambda: tiny_net(W)}[fault]()
    if fault == "gate_left_out":    # sigmoid(0) * 2 Wo: no gate, exactly
        attn = net.params["blk1_attn"]
        attn.update(Wg=jnp.zeros_like(attn["Wg"]), Wo=2 * attn["Wo"])
    if fault == "bsel_ignored":
        for i in range(1, 4):
            del net.params[f"blk{i}_ff"]["bsel"]
    got, _, _ = _through_the_ring(net, tokens)
    assert _worst(got, want) > 100 * TOL
    with jax.default_matmul_precision("highest"):
        probs = net.output(tokens[None, :])
    assert np.abs(logp(probs[0]) - want).max() > 100 * TOL


def test_a_chunk_longer_than_the_ring_keeps_its_last_rows(W, tokens, want):
    """A bucket of 32 over a ring of 24: the chunk attends itself under
    the window's mask and only its last 24 tokens are written."""
    got, _, _ = _through_the_ring(tiny_net(W), tokens, bucket=32,
                                  chunks=((0, 30), (30, 18)))
    assert _worst(got, want) < TOL


def test_a_slots_second_shorter_tenant_is_served_as_a_fresh_cache_would(
        W, tokens):
    """Slot 1 serves the 80 tokens (its rings wrapped twice), then a
    prompt of 30 from position 0: no reset, the rows the first tenant
    left hold positions that are negative or beyond the query by the
    ring's arithmetic, and the log-probabilities are those of a zeroed
    cache, bit for bit."""
    net = tiny_net(W)
    second = np.random.default_rng(9).integers(0, DIMS["V"], 30).astype(np.int32)
    short = ((0, 13), (13, 9))
    fresh, _, _ = _through_the_ring(net, second, chunks=short)
    _, used, _ = _through_the_ring(net, tokens)
    assert all(np.abs(np.asarray(a)[1]).max() > 0
               for e in used.values() for a in e.values())
    again, _, _ = _through_the_ring(net, second, dirty=used, chunks=short)
    assert sorted(again) == [12, 21] + list(range(22, 30))
    assert all(np.array_equal(fresh[t], again[t]) for t in fresh)


def test_an_idle_rows_ring_is_bit_identical_after_a_step(W, tokens):
    """The engine feeds an idle slot the scratch position capacity - 1:
    in a ring of 24 that is row 23, which slot 1's tenant needs."""
    net = tiny_net(W)
    _, cache, _ = _through_the_ring(net, tokens, slot=1)
    _, cache, _ = _through_the_ring(net, tokens[::-1].copy(), slot=2,
                                    dirty=cache)
    before = jax.tree.map(np.asarray, cache)
    step = jax.jit(net.incremental_decode_fn())
    tok = np.array([5, 0, 0], np.int32)
    pos = np.array([0, CAPACITY - 1, CAPACITY - 1], np.int32)
    _, after, _ = step(net.params, net.state, cache, tok, pos,
                       np.array([True, False, False]))
    for name, arrays in before.items():
        for arr, old in arrays.items():
            new = np.asarray(after[name][arr])
            assert np.array_equal(new[1:], old[1:]), (name, arr)
            assert np.abs(old[1:]).max() > 0
            assert not np.array_equal(new[0], old[0])


# a chunk's write as one run a row against the scatter it replaced: a
# layer of 4 queries on 2 key-value heads of 8 over 3 cache rows, a ring
# of 24 rows (window 24) or a full entry of 40; per case the window, the
# bucket T, and per batch row its cache row, start, real tokens and live
RUN_CASES = {
    "start_0": (24, 16, [(1, 0, 16, True)]),
    "ends_at_the_ring_end": (24, 16, [(1, 8, 16, True)]),
    "wraps": (24, 16, [(2, 20, 16, True)]),
    "pad_tail_crosses_the_wrap": (24, 16, [(0, 14, 7, True)]),
    "a_row_not_live": (24, 16, [(0, 20, 16, True), (2, 5, 16, False)]),
    "full_layer": (0, 16, [(1, 20, 12, True)]),
    "two_rows": (24, 8, [(2, 19, 8, True), (0, 3, 5, True)]),
    "longer_than_the_ring": (24, 32, [(1, 10, 30, True)]),
    "longer_than_the_ring_kept_from_row_0": (24, 40, [(1, 8, 40, True)]),
}
# the batch rows whose written run passed the entry's end
RUN_WRAPS = {"start_0": 0, "ends_at_the_ring_end": 0, "wraps": 1,
             "pad_tail_crosses_the_wrap": 0, "a_row_not_live": 1,
             "full_layer": 0, "two_rows": 1, "longer_than_the_ring": 1,
             "longer_than_the_ring_kept_from_row_0": 0}


@pytest.mark.parametrize("case", sorted(RUN_CASES))
def test_a_chunks_run_write_is_the_scatter_bit_for_bit(case):
    """The layer's chunk on an entry of random rows against the scatter
    of the same projected rows (`scatter_write` at `written_rows`, called
    in the same program): every entry array equal bit for bit, so `keep`
    0 and a row not live write nothing, a position lands at p % R, and
    every old row outside the run stays. A chunk longer than its ring
    keeps the scatter. `attn_write_wraps` counts the rows that wrapped."""
    from deeplearning4j_tpu.nn.conf.layers import GroupedAttentionLayer
    from deeplearning4j_tpu.nn.decode import CacheStep
    from deeplearning4j_tpu.nn.layers import grouped_attention as ga

    W_, T, batch = RUN_CASES[case]
    conf = GroupedAttentionLayer(n_in=16, n_out=16, n_heads=4, n_kv_heads=2,
                                 head_dim=8, window=W_, rope_theta=10000.0,
                                 weight_init="xavier")
    impl = ga.GroupedAttentionImpl()
    params, _ = impl.init(conf, jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(len(case))
    entry = {n: jnp.asarray(rng.normal(size=(3,) + a[0]), jnp.float32)
             for n, a in impl.cache_arrays(conf, 40, "f32", 8,
                                           jnp.float32).items()}
    R = next(iter(entry.values())).shape[2]
    rows, starts, n_real, live = (np.array(c) for c in zip(*batch))
    assert (T <= R) == (not case.startswith("longer_than_the_ring"))
    x = jnp.asarray(rng.normal(size=(len(batch), T, 16)), jnp.float32)
    pos = jnp.asarray(starts[:, None] + np.arange(T)[None, :], jnp.int32)
    keep = jnp.asarray(np.arange(T)[None, :] < n_real[:, None], jnp.float32)

    @jax.jit
    def both(entry):
        step = CacheStep(jnp.asarray(rows, jnp.int32), pos, keep=keep,
                         chunk=True, live=jnp.asarray(live))
        _, got, counts = impl.apply_cached(conf, params, x, entry, step)
        _, k, v = ga._project(conf, params, x, pos)
        kept = (keep > 0) & jnp.asarray(live)[:, None]
        want = ga.scatter_write(entry, dict(zip(ga.entry_names(conf), (k, v))),
                                jnp.asarray(rows, jnp.int32),
                                ga.written_rows(pos, kept, R))
        return got, want, counts

    with jax.default_matmul_precision("highest"):
        got, want, counts = both(entry)
    for n in entry:
        assert np.array_equal(np.asarray(got[n]), np.asarray(want[n])), n
        changed = np.asarray(got[n]) != np.asarray(entry[n])
        assert changed.any() == bool((n_real * live).any()), n
    assert int(counts["attn_write_wraps"]) == RUN_WRAPS[case]


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_a_chunk_writes_each_entry_as_a_run_and_a_step_by_scatter(W, kind):
    """The tiny block's prefill program scatters into no whole entry and
    writes each by two dynamic_update_slices (the run's two blocks, one
    chunk row); its decode step keeps one scatter an entry and writes
    none by dynamic_update_slice."""
    net = tiny_net(W)
    cache = net.init_kv_cache(3, CAPACITY)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)   # noqa: E731
    if kind == "prefill":
        jaxpr = jax.make_jaxpr(net.prefill_fn())(
            net.params, net.state, cache, i32(1, 16),
            jnp.ones((1, 16), jnp.float32), i32(1), i32(1), i32(1))
    else:
        jaxpr = jax.make_jaxpr(net.incremental_decode_fn())(
            net.params, net.state, cache, i32(3), i32(3), jnp.ones(3, bool))
    shapes = {a.shape for e in cache.values() for a in e.values()}
    assert shapes == {(3, 2, 24, 16), (3, 2, 96, 16)}
    takes_entry = [eqn.primitive.name for eqn in _eqns(jaxpr.jaxpr)
                   if eqn.invars and hasattr(eqn.invars[0], "aval")
                   and getattr(eqn.invars[0].aval, "shape", None) in shapes]
    n_entries = sum(len(e) for e in cache.values())
    if kind == "prefill":
        assert "scatter" not in takes_entry
        assert takes_entry.count("dynamic_update_slice") == 2 * n_entries
    else:
        assert takes_entry.count("scatter") == n_entries
        assert "dynamic_update_slice" not in takes_entry


def test_speculative_decoding_and_int8_are_refused_with_the_layer_named(W):
    net = tiny_net(W)
    with pytest.raises(ValueError,
                       match=r"blk0_attn \(GroupedAttentionLayer\)"):
        net.verify_decode_fn()
    with pytest.raises(ValueError,
                       match=r"blk2_attn \(GroupedAttentionLayer\)"):
        GenerationEngine(net, BucketLattice(batch_sizes=(1,), seq_lens=(8,)),
                         slots=2, max_new_tokens=8, page_size=8,
                         speculative_k=2)
    with pytest.raises(ValueError,
                       match=r"blk0_attn \(GroupedAttentionLayer\).*int8"):
        GenerationEngine(net, BucketLattice(batch_sizes=(1,), seq_lens=(8,)),
                         slots=2, max_new_tokens=8, page_size=8,
                         kv_dtype="int8")
    # a window of tokens handed to the layer itself is refused too: it
    # has no window-of-drafts step, for a ring or for rows
    from deeplearning4j_tpu.nn.decode import CacheStep
    from deeplearning4j_tpu.nn.layers.grouped_attention import (
        GroupedAttentionImpl,
    )

    cache = net.init_kv_cache(2, 32)
    x, step = jnp.zeros((2, 3, 48)), CacheStep(None, jnp.zeros((2, 3),
                                                               jnp.int32))
    for name in ("blk0_attn", "blk3_attn"):
        with pytest.raises(ValueError, match="one token a row"):
            GroupedAttentionImpl().apply_cached(
                net.conf.vertices[name].layer, net.params[name], x,
                cache[name], step)
    assert not GroupedAttentionImpl.rewindable(
        net.conf.vertices["blk0_attn"].layer)
    assert GroupedAttentionImpl.rewindable(net.conf.vertices["blk3_attn"].layer)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernel_in_interpret_mode_equals_its_jnp_twin(dtype):
    """Rows of unequal length, one of them not live, one whose ring has
    wrapped (position 70 in 32 rows) and one at the ring's last row."""
    rng = np.random.default_rng(2)
    B, Hq, Hk, d, R = 5, 12, 2, 16, 32
    q = jnp.asarray(rng.normal(size=(B, Hq, d)), dtype)
    k, v = (jnp.asarray(rng.normal(size=(B, Hk, R, d)), dtype) for _ in "kv")
    pos = jnp.asarray([5, 31, 70, 40, 0], jnp.int32)
    live = jnp.asarray([True, True, True, False, True])
    twin = np.asarray(op.gqa_decode_jnp(q, k, v, pos, live), np.float32)
    for block_k in (16, 32):
        kern = op.gqa_decode_kernel(q, k, v, pos, live, interpret=True,
                                    block_k=block_k)
        assert kern.shape == (B, Hq, d) and kern.dtype == q.dtype
        kern = np.asarray(kern, np.float32)
        # float32: the same sums in another order; bfloat16: the kernel
        # rounds the softmax's weights to the values' type before their
        # product, the twin multiplies in float32 (2^-9 of values near 1)
        assert np.abs(kern - twin).max() < (1e-5 if dtype == "float32"
                                            else 2e-2)
        assert np.all(kern[3] == 0)         # not live: nothing seen
    # against the sum written out: row 2 sees all 32 rows, row 0 six
    kf, vf, qf = (np.asarray(a, np.float64) for a in (k, v, q))
    for i, n in ((0, 6), (2, 32), (4, 1)):
        for h in range(Hq):
            s = qf[i, h] @ kf[i, h // 6, :n].T / 4.0
            w = np.exp(s - s.max())
            assert np.abs(w @ vf[i, h // 6, :n] / w.sum() - twin[i, h]).max() \
                < (1e-5 if dtype == "float32" else 3e-2)


def _ring_of(seq, upto, R, junk):
    """The ring a sequence leaves after writing positions 0 .. upto - 1,
    position p at row p % R; a row never written holds `junk`."""
    ring = np.full((seq.shape[0], R, seq.shape[2]), junk, np.float32)
    for p in range(max(0, upto - R), upto):
        ring[:, p % R] = seq[:, p]
    return ring


def _windowed(q, kseq, vseq, lo, hi):
    """softmax(q . k_p / sqrt(d)) v_p over the positions lo <= p < hi of
    one key-value head's sequence, in float64."""
    s = q.astype(np.float64) @ kseq[lo:hi].astype(np.float64).T \
        / np.sqrt(q.shape[-1])
    w = np.exp(s - s.max(-1, keepdims=True))
    return (w @ vseq[lo:hi].astype(np.float64)) / w.sum(-1, keepdims=True)


# the served ring's own size: 4,096 rows walked in blocks of 512
SERVED_R, SERVED_BLOCK = 4096, 512
# a token's position: the ring's last row and its first wrap, a block's
# last row and the next block's first, twice round, and a lone token
EDGE_POSITIONS = (4094, 4095, 4096, 4097, 511, 512, 8191, 8192, 0)


def _sequence(rng, Hk, n, d):
    """Keys N(0, 1) and values whose first place is the row's POSITION
    (less n, to keep float32 sums small): under a zero query the output's
    first place is the mean position of the rows seen, which a row more
    or less at either end of a window of 4,096 moves by 0.5."""
    kseq = rng.normal(size=(Hk, n, d)).astype(np.float32)
    vseq = rng.normal(size=(Hk, n, d)).astype(np.float32)
    vseq[:, :, 0] = np.arange(n) - n
    return kseq, vseq


@pytest.mark.parametrize("pos", EDGE_POSITIONS)
def test_kernel_at_the_served_ring_size_sees_the_window_and_no_row_more(pos):
    """`gqa_decode_kernel` (interpret mode) and its twin over a ring of
    4,096 rows in blocks of 512, the ring built from a sequence's keys
    row by row, against the sum over the positions pos - 4095 .. pos
    written out in float64; a row the sequence has not written holds
    1e4 in every place. Then a zero query: the mean position seen is
    that window's, to 0.05 where a row more or less reads 0.5."""
    rng = np.random.default_rng(pos)
    Hq, Hk, d, R = 12, 2, 16, SERVED_R
    assert op.gqa_block(R) == SERVED_BLOCK
    kseq, vseq = _sequence(rng, Hk, pos + 1, d)
    q = (2 * rng.normal(size=(1, Hq, d))).astype(np.float32)
    k, v = (jnp.asarray(_ring_of(x, pos + 1, R, 1e4))[None]
            for x in (kseq, vseq))
    at = jnp.asarray([pos], jnp.int32)
    lo = max(0, pos - R + 1)
    want = np.stack([_windowed(q[0, h], kseq[h // 6], vseq[h // 6], lo,
                               pos + 1) for h in range(Hq)])
    mean = (lo + pos) / 2 - (pos + 1)
    for fn in (lambda *a: op.gqa_decode_kernel(*a, interpret=True),
               op.gqa_decode_jnp):
        got = np.asarray(fn(jnp.asarray(q), k, v, at), np.float64)[0]
        # float32 sums over 4,096 keys in another order; the first place
        # holds positions, up to 4,096 of them: 2e-5 of that
        assert np.abs(got[:, 1:] - want[:, 1:]).max() < 2e-5
        assert np.abs(got[:, 0] - want[:, 0]).max() < 0.05
        flat = np.asarray(fn(jnp.zeros_like(q), k, v, at), np.float64)[0]
        assert np.abs(flat[:, 0] - mean).max() < 0.05


@pytest.mark.parametrize("start", [4090, 4096, 5000, 8191, 12288])
def test_chunk_walk_at_the_served_ring_size_keeps_to_each_querys_window(start):
    """`ring_attention` as a prefill chunk uses it: 12 queries at
    positions start .. start + 11 against the ring as the chunk found it
    (positions below `start`), each seeing start + t - 4095 <= p < start
    there: against the sums written out, then under zero queries by the
    mean position each query sees."""
    rng = np.random.default_rng(start)
    Hq, Hk, d, R, T = 12, 2, 16, SERVED_R, 12
    G = Hq // Hk
    kseq, vseq = _sequence(rng, Hk, start, d)
    q = (2 * rng.normal(size=(1, Hq, T, d))).astype(np.float32)
    k, v = (jnp.asarray(_ring_of(x, start, R, 1e4))[None]
            for x in (kseq, vseq))
    pos = start + jnp.arange(T)[None, :]

    def walk(q):
        o, lse = op.ring_attention(
            op.group_queries(jnp.asarray(q), Hk), k, v,
            jnp.full((1, G * T), start), jnp.asarray([start]), None,
            jnp.tile(pos - R + 1, (1, G)))
        assert lse.shape == (1, Hk, G * T)
        return np.asarray(op.ungroup_queries(o, Hq), np.float64)[0]

    got, flat = walk(q), walk(np.zeros_like(q))
    for t in range(T):
        lo = max(0, start + t - R + 1)
        for h in range(Hq):
            want = _windowed(q[0, h, t], kseq[h // G], vseq[h // G], lo,
                             start)
            assert np.abs(got[h, t, 1:] - want[1:]).max() < 2e-5
            assert abs(got[h, t, 0] - want[0]) < 0.05
        assert np.abs(flat[:, t, 0] - ((lo + start - 1) / 2 - start)).max() \
            < 0.05


def test_the_four_expert_shares_add_up_to_the_uncut_layer():
    """One chip's share leaves out what the absent experts would add: the
    outputs of the four shares (experts 0-3, 4-7, 8-11, 12-15), with the
    shared expert counted once, are the uncut reference's layer."""
    from deeplearning4j_tpu.nn.layers.moe import DroplessMoELayer, dropless_moe

    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(size=(40, 48)), jnp.float32)
    whole = seeded_weights(41, held=16, first=0)["layers"][1]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.experts(x, whole, dict(DIMS, first_expert=0),
                                      ref.mm_highest))
        shared = np.asarray(ref.gated(x, whole["Ws_gate"], whole["Ws_up"],
                                      whole["Ws_down"], ref.mm_highest))
        total, pairs = -3.0 * shared, 0
        for first in (0, 4, 8, 12):
            w = seeded_weights(41, first=first)["layers"][1]
            assert np.array_equal(w["We_up"], whole["We_up"][first:first + 4])
            conf = DroplessMoELayer(
                n_in=48, n_out=48, n_experts=16, top_k=2, d_hidden=32,
                first_expert=first, n_held=4, n_shared=1,
                routed_scaling=2.448, selection_bias=True, activation="silu")
            params = dict(w, Wg=w["Wr"])
            y, counts = dropless_moe(conf, params, x)
            total, pairs = total + np.asarray(y), pairs + int(counts["moe_pairs"])
    assert pairs == 40 * 2          # every selected pair lies in one share
    assert np.abs(total - want).max() < 1e-4


def test_selection_without_a_bias_is_the_router_it_was():
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(50, 48)), jnp.float32)
    Wg = jnp.asarray(rng.normal(size=(48, 16)) / 7, jnp.float32)
    ids, w = moe.route_sigmoid_topk(x, Wg, 2, 2.5)
    s = jax.nn.sigmoid(jnp.matmul(x, Wg, precision=jax.lax.Precision.HIGHEST))
    top_s, top_i = jax.lax.top_k(s, 2)
    assert np.array_equal(ids, top_i)
    assert np.array_equal(w, top_s / (top_s.sum(-1, keepdims=True) + 1e-20) * 2.5)
    # a bias chooses and does not weigh
    bsel = jnp.asarray(rng.normal(0, 0.3, 16), jnp.float32)
    ids_b, w_b = moe.route_sigmoid_topk(x, Wg, 2, 2.5, bsel)
    assert np.array_equal(ids_b, jax.lax.top_k(s + bsel, 2)[1])
    assert (np.asarray(ids_b) != np.asarray(ids)).any(axis=1).mean() > 0.5
    picked = jnp.take_along_axis(s, ids_b, -1)
    assert np.allclose(w_b, picked / picked.sum(-1, keepdims=True) * 2.5)


def _spans(rec, name):
    return [e for e in rec.events
            if e.get("event") == "span" and e.get("name") == name]


def test_engine_serves_the_block_over_http_in_bfloat16(W):
    """`POST /generate` through `ServingServer` and `GenerationEngine`:
    no step retraces after the warm-up, every warmed step aliases the
    whole cache, the spans carry the attention layer's two counters
    beside the expert layer's three, and the `meta` event and /stats say
    what a slot's rows cost and which of them are rings."""
    net = tiny_net(W, "bfloat16")
    rec = Recorder(path=None)
    engine = GenerationEngine(
        net, BucketLattice(batch_sizes=(1,), seq_lens=(8, 16, 32)), slots=3,
        max_new_tokens=16, page_size=8, prefill_chunk=16, recorder=rec)
    assert engine.warmup() == 3     # chunks of 8 and 16, the decode step
    worker = engine.fleet_workers()[0]
    per_slot = (3 * 24 + 48) * 2 * 2 * 16 * 2   # rows x (k, v) x [2, 16] bf16
    assert tree_bytes(worker.cache) == 3 * per_slot
    costs = [e for e in rec.events if e.get("event") == "cost"]
    assert len(costs) == 3 and all(
        e["alias_bytes"] == 3 * per_slot for e in costs), costs
    meta = [e for e in rec.events if e.get("event") == "meta"
            and e.get("role") == "generation-engine"][0]
    for described in (meta["cache"], engine.stats()["cache"]):
        assert described["capacity"] == 48
        assert described["rows"] == {"k_win": 192.0, "v_win": 192.0,
                                     "k": 64.0, "v": 64.0}
        assert described["windows"] == {"k_win": 24, "v_win": 24}
        assert described["bytes_per_slot"] == per_slot
        assert described["states"] == {}
    # each warmed program's instructions by region (telemetry/costbook.py)
    regions = {e["entry"]: set(e["ops"].values()) for e in rec.events
               if e.get("event") == "regions"}
    assert {"attention", "attention/cache_write", "moe/router",
            "moe/experts", "moe/shared_expert", "norm", "embed", "ffn",
            "head"} <= regions["decode"] & regions["prefill"]
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
    # a program's counters come home with its tokens, under the span that
    # says it `fetched` them
    home = {e["fetched"]: e for e in rec.events
            if e.get("event") == "span" and e.get("fetched") is not None}
    names = ("attn_rows_seen", "attn_wrapped", "moe_pairs", "moe_rows",
             "moe_max_load")
    chunks, steps = _spans(rec, "prefill_chunk"), _spans(rec, "decode_step")
    assert len(chunks) == 1 + 2 + 1 + 2
    for e in chunks + steps:
        assert all(isinstance(home[e["program"]][n], int) for n in names)
    # a chunk's rows: the 23 before its first query at the most in the
    # three window layers, every earlier row in the full one, and its own
    assert [home[e["program"]]["attn_rows_seen"] for e in chunks] \
        == [3 * (min(e["start"], 23) + e["n_real"]) + e["start"] + e["n_real"]
            for e in chunks]
    wrapped = [home[e["program"]]["attn_wrapped"] for e in steps]
    assert set(wrapped) == {0, 1} and wrapped[0] == 0
    # the 30-token prompt passes the window in its second chunk
    assert [home[e["program"]]["attn_wrapped"] for e in chunks] \
        == [0, 0, 1, 0, 0, 1]


def test_a_second_request_in_a_slot_gets_the_tokens_a_fresh_engine_gives(W):
    """One slot, so the second, shorter request takes the first one's
    place in rings that have wrapped."""
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


def test_bytes_per_slot_is_the_cache_trees_bytes_a_slot_and_describe_gives_the_rings(W):
    net = tiny_net(W, "bfloat16")
    row = 2 * 16 * 2                # [2, 16] bfloat16
    for max_seq, rings in ((8, 16), (24, 24), (120, 24)):
        plan = CachePlan(max_seq, 8, n_slots=5, page_size=8)
        cache = net.init_kv_cache(5, plan.capacity, "f32", 8)
        assert plan.bytes_per_slot(net) * 5 == tree_bytes(cache)
        assert bytes_per_slot(plan.cache_specs(net)) == plan.bytes_per_slot(net)
        said = plan.describe(net)
        # a ring never outgrows its window; a capacity under the window
        # is a ring that never wraps
        assert said["windows"] == {"k_win": rings, "v_win": rings}
        assert said["rows"] == {"k_win": 3.0 * row, "v_win": 3.0 * row,
                                "k": 1.0 * row, "v": 1.0 * row}
        assert said["bytes_per_token"] == 8 * row
        assert said["bytes_per_slot"] == plan.bytes_per_slot(net) \
            == 2 * row * (3 * rings + plan.capacity)
        assert said["states"] == {} and said["state_bytes_per_slot"] == 0
    # a net of one kind of row: all of a slot is billed to the capacity
    rows = transformer_lm(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                          d_ff=64, max_length=64, dtype="bfloat16")
    plan = CachePlan(24, 8, n_slots=5, page_size=8)
    said = plan.describe(rows)
    assert said["windows"] == {} and said["states"] == {}
    assert said["bytes_per_token"] * plan.capacity == plan.bytes_per_slot(rows) \
        == said["bytes_per_slot"]


@pytest.mark.parametrize("model", ["transformer_lm", "latent_moe_lm",
                                   "retention_lm"])
def test_the_other_nets_keep_their_steps(model):
    """Counters of several kinds, a spec's third entry that may be a
    number and a `rewindable` that may be asked of a conf change nothing
    for the nets that were there: the same specs, the same counters in
    the same order, no selection bias held, the tokens of the full
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
        assert net.conf.vertices["blk1_ff"].layer.selection_bias is False
        assert "bsel" not in net.params["blk1_ff"]
        assert "embed_scaled" not in net.conf.vertices
    else:
        net = retention_lm(vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2,
                           n_layers=2, d_ff=48, head_dim=8).init(seed=3)
        counters, arrays, extra = ("state_resets",), {"s", "z"}, 1
    specs = net.kv_cache_specs(32)
    assert all(set(e) == arrays and all(
        len(s) == 2 + extra and s[2:] == ("slot",) * extra
        for s in e.values()) for e in specs.values())
    fns = [net.prefill_fn(), net.incremental_decode_fn()]
    if model == "retention_lm":
        with pytest.raises(ValueError, match=r"blk0_ret \(PowerRetentionLayer\)"):
            net.verify_decode_fn()
    else:
        fns.append(net.verify_decode_fn())
    assert [f.counters for f in fns] == [counters] * len(fns)
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
        live = np.zeros(3, bool)
        tok[2], pos[2], live[2] = tokens[t], t, True
        out = fns[1](net.params, net.state, cache, tok, pos, live)
        assert len(out) == n_out
        got.append(np.asarray(out[0][2]))
        cache = out[1]
    full = np.asarray(net.output(tokens[None, :]))[0]
    assert np.abs(np.stack(got) - full[7:]).max() < 1e-5
