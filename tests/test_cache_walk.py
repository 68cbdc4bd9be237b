"""The walk over the KV cache's key blocks (ISSUE 32) against the scan it
replaced: `ops/decode_attention._walk_live_blocks` takes each block out
of the cache where it lies and stops at the last block a query of the
call can see; the scan below relaid the whole cache and walked all of
it. Same dtype, same block length: the results are equal to the last
bit for every query that sees a key, a query that sees none reads zero,
and a block past the last live one is never read. Also here: the served
decode step holds no relaid copy of a cache entry, the engine's spans
say how far each step's walk went, and (ISSUE 33) the layers' own cached
forward, `apply_cached`, against their `apply` over the whole sequence;
the walk of nn/decode.py calls it and names no layer."""

import ast
import inspect
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import autotune
from deeplearning4j_tpu.ops import decode_attention as da

_NEG_INF = -1e30


def _scan_oracle(q, k, v, key_limit, block_k):
    """`_cache_attention_blocked` as it stood before ISSUE 32: the whole
    of k and v relaid to [blocks, B, H, block, D], a `lax.scan` over all
    of them."""
    B, S, H, D = k.shape
    Tq = q.shape[2]
    nb = S // block_k
    sm_scale = 1.0 / jnp.sqrt(jnp.float32(D))
    qf = q.astype(jnp.float32)
    kb = jnp.moveaxis(k.reshape(B, nb, block_k, H, D), 1, 0)
    kb = kb.transpose(0, 1, 3, 2, 4)
    vb = jnp.moveaxis(v.reshape(B, nb, block_k, H, D), 1, 0)
    vb = vb.transpose(0, 1, 3, 2, 4)

    m0 = jnp.full((B, H, Tq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)
    acc0 = jnp.zeros((B, H, Tq, D), jnp.float32)

    def body(carry, blk):
        m, l, acc, j0 = carry
        k_j, v_j = blk
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_j.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * sm_scale
        idx = j0 + jnp.arange(block_k)
        visible = idx[None, None, None, :] < key_limit[:, None, :, None]
        s = jnp.where(visible, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_j.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new, j0 + block_k), None

    (m, l, acc, _), _ = jax.lax.scan(
        body, (m0, l0, acc0, jnp.int32(0)), (kb, vb))
    out = jnp.where(l[..., None] > 0.0,
                    acc / jnp.maximum(l, 1e-30)[..., None], 0.0)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return out.astype(q.dtype), lse


B, S, H, D, BK, PAGE = 5, 64, 2, 16, 16, 8


def _limits(case):
    """(key_limit [b, Tq] int32, rows [b] or None) of a case."""
    if case == "single_query_ragged":           # the decode step
        return np.array([[1], [17], [40], [9], [33]]), None
    if case == "idle_row_among_live":           # `live` false: limit 0
        return np.array([[23], [0], [47], [0], [5]]), None
    if case == "no_row_sees_a_key":             # a prompt's first chunk
        return np.zeros((B, 8), np.int64), None
    if case == "verify_window":                 # limits pos + i + 1
        pos = np.array([3, 30, 0, 44, 15])
        return pos[:, None] + np.arange(4)[None, :] + 1, None
    if case == "row_subset":                    # a later prefill chunk
        start = np.array([24, 8])
        return np.broadcast_to(start[:, None], (2, 8)), np.array([3, 1])
    if case == "on_a_block_edge":
        return np.array([[16], [32], [48], [16], [32]]), None
    if case == "full_capacity":                 # and a tail past it
        return np.array([[S, S + 3], [S - 1, S], [1, 2], [S, S], [7, 8]]), \
            None
    raise AssertionError(case)


CASES = ("single_query_ragged", "idle_row_among_live", "no_row_sees_a_key",
         "verify_window", "row_subset", "on_a_block_edge", "full_capacity")


def _check(walk, oracle, arrays, poison, key_limit, rows):
    """`walk(*arrays)` equals `oracle(*arrays)` bit for bit where a query
    sees a key, reads zero (lse at the mask floor) where it sees none,
    and does not change by a bit when every block past the last live one
    is `poison`ed: those blocks are not read."""
    out, lse = walk(*arrays)
    want, want_lse = oracle(*arrays)
    assert out.dtype == want.dtype and out.shape == want.shape
    sees = np.broadcast_to((key_limit > 0)[:, None, :], lse.shape)
    out, want = np.asarray(out, np.float32), np.asarray(want, np.float32)
    assert np.array_equal(out[sees], want[sees])
    assert np.array_equal(np.asarray(lse)[sees], np.asarray(want_lse)[sees])
    assert not out[~sees].any()
    assert (np.asarray(lse)[~sees] == np.float32(_NEG_INF)).all()
    n_live = min(-(-int(key_limit.max()) // BK), S // BK)
    out2, lse2 = walk(*poison(arrays, n_live * BK))
    assert np.array_equal(np.asarray(out2, np.float32), out)
    assert np.array_equal(np.asarray(lse2), np.asarray(lse))
    if rows is not None and n_live:     # nor the rows nobody asked for
        other = np.setdiff1d(np.arange(B), rows)
        out3, _ = walk(*poison(arrays, 0, other))
        assert np.array_equal(np.asarray(out3, np.float32), out)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", CASES)
def test_walk_equals_the_scan_it_replaced(case, dtype):
    rng = np.random.default_rng(len(case))
    key_limit, rows = _limits(case)
    b, Tq = key_limit.shape
    q = jnp.asarray(rng.normal(size=(b, H, Tq, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), dtype)
    lim = jnp.asarray(key_limit, jnp.int32)

    def walk(q, k, v):
        return da._cache_attention_blocked(
            q, k, v, lim, BK, None if rows is None else jnp.asarray(rows))

    def oracle(q, k, v):
        if rows is not None:            # as nn/decode.py gathered them
            k, v = k[rows], v[rows]
        return _scan_oracle(q, k, v, lim, BK)

    def poison(arrays, first, which=slice(None)):
        q, k, v = arrays
        return (q, k.at[which, first:].set(jnp.nan),
                v.at[which, first:].set(jnp.nan))

    _check(walk, oracle, (q, k, v), poison, key_limit, rows)


@pytest.mark.parametrize("case", ["single_query_ragged",
                                  "idle_row_among_live", "verify_window",
                                  "row_subset", "full_capacity"])
def test_int8_walk_equals_the_scan_it_replaced(case):
    """The int8 twin is the same walk with a dequantising loader: against
    the scan over the dequantised cache (a code times its page's scale is
    the same product wherever it is taken)."""
    rng = np.random.default_rng(len(case) + 100)
    key_limit, rows = _limits(case)
    b, Tq = key_limit.shape
    q = jnp.asarray(rng.normal(size=(b, H, Tq, D)), jnp.float32)
    kc, ks = da.quantize_pages(
        jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32), PAGE)
    vc, vs = da.quantize_pages(
        jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32), PAGE)
    lim = jnp.asarray(key_limit, jnp.int32)

    def walk(q, kc, vc, ks, vs):
        return da._cache_attention_blocked_q8(
            q, kc, vc, ks, vs, lim, BK, PAGE,
            None if rows is None else jnp.asarray(rows))

    def oracle(q, kc, vc, ks, vs):
        k = da.dequantize_pages(kc, ks, PAGE)
        v = da.dequantize_pages(vc, vs, PAGE)
        if rows is not None:
            k, v = k[rows], v[rows]
        return _scan_oracle(q, k, v, lim, BK)

    def poison(arrays, first, which=slice(None)):
        q, kc, vc, ks, vs = arrays
        return (q, kc, vc, ks.at[which, first // PAGE:].set(jnp.nan),
                vs.at[which, first // PAGE:].set(jnp.nan))

    _check(walk, oracle, (q, kc, vc, ks, vs), poison, key_limit, rows)


# ------------------------------------------------- the served programs

def _tiny_lm():
    from deeplearning4j_tpu.models.transformer import transformer_lm

    net = transformer_lm(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                         d_ff=64, max_length=64)
    net.init()
    return net


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (tuple, list)) else (val,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("kind", ["decode", "verify", "prefill"])
def test_no_step_relays_or_gathers_a_whole_cache_entry(kind):
    """The copy is gone and stays gone: no transpose, reshape, copy or
    gather in a served step takes a whole cache entry as its operand;
    the entry is only written in place (scatter) and read a block at a
    time (dynamic_slice)."""
    net = _tiny_lm()
    n_slots, cap = 4, 48
    cache = net.init_kv_cache(n_slots, cap)
    entry = next(iter(cache.values()))["k"].shape
    i32 = lambda *s: jnp.zeros(s, jnp.int32)   # noqa: E731
    with autotune.override({"decode_attn": {"block_k": 16}}):
        if kind == "decode":
            jaxpr = jax.make_jaxpr(net.incremental_decode_fn())(
                net.params, net.state, cache, i32(n_slots), i32(n_slots),
                jnp.ones(n_slots, bool))
        elif kind == "verify":
            jaxpr = jax.make_jaxpr(net.verify_decode_fn())(
                net.params, net.state, cache, i32(n_slots, 3), i32(n_slots),
                jnp.ones(n_slots, bool))
        else:
            jaxpr = jax.make_jaxpr(net.prefill_fn())(
                net.params, net.state, cache, i32(1, 16),
                jnp.ones((1, 16), jnp.float32), i32(1), i32(1), i32(1))
    takes_entry = [eqn.primitive.name for eqn in _eqns(jaxpr.jaxpr)
                   if eqn.invars and hasattr(eqn.invars[0], "aval")
                   and getattr(eqn.invars[0].aval, "shape", None) == entry]
    assert set(takes_entry) <= {"scatter", "dynamic_slice", "pjit", "while"}, \
        takes_entry
    n_entries = 2 * len(cache)
    assert takes_entry.count("dynamic_slice") == n_entries
    assert takes_entry.count("scatter") == n_entries


def test_idle_rows_told_so_change_no_live_rows_output():
    """`live` gives an idle row key_limit 0: the live rows' outputs are
    the ones they get when every row is called live, bit for bit."""
    net = _tiny_lm()
    n_slots, cap = 4, 48
    step = jax.jit(net.incremental_decode_fn())
    rng = np.random.default_rng(3)
    cache = jax.tree.map(
        lambda x: jnp.asarray(rng.normal(size=x.shape), x.dtype),
        net.init_kv_cache(n_slots, cap))
    tok = jnp.asarray([5, 0, 9, 0], jnp.int32)
    pos = jnp.asarray([7, cap - 1, 19, cap - 1], jnp.int32)
    live = np.array([True, False, True, False])
    with autotune.override({"decode_attn": {"block_k": 8}}):
        told, _ = step(net.params, net.state, cache, tok, pos, live)
        untold, _ = step(net.params, net.state, cache, tok, pos)
    assert np.array_equal(np.asarray(told)[live], np.asarray(untold)[live])


# ------------------------------------------- the layers' cached forward

def _attention_layer():
    from deeplearning4j_tpu.nn.conf.layers import SelfAttentionLayer
    from deeplearning4j_tpu.nn.layers.attention import SelfAttentionImpl

    conf = SelfAttentionLayer(n_in=32, n_out=32, n_heads=2, causal=True,
                              activation="tanh", weight_init="xavier")
    impl = SelfAttentionImpl()
    params, _ = impl.init(conf, jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(1)      # biases that are not zero
    for name in ("bqkv", "bo"):
        params[name] = jnp.asarray(
            rng.normal(size=params[name].shape), jnp.float32)
    return conf, impl, params


@pytest.mark.parametrize("kv_dtype", ["f32", "int8"])
@pytest.mark.parametrize("kind", ["decode", "verify", "two_chunks"])
def test_attention_apply_cached_equals_apply(kind, kv_dtype):
    """`SelfAttentionImpl.apply_cached`, called as the walk calls it,
    against `apply` over the whole sequence: four rows with prompts of
    different lengths in one padded chunk, then one decode step, a
    verify window of 3 (two rows not `live` in both: they are fed the
    scratch position and attend nothing), or a second chunk into a
    permuted subset of the rows. Rows stored plain: atol 1e-5, the
    tolerance of tests/test_generation.py. int8: a code is within 1/254
    of its page's largest value, so 2 % of the output's largest value,
    set here before the run; and not the plain result (the codes were
    read)."""
    from deeplearning4j_tpu.nn.decode import CacheStep

    conf, impl, params = _attention_layer()
    n_rows, cap, page, L = 4, 32, 8, 20
    x = jnp.asarray(np.random.default_rng(2).normal(size=(n_rows, L, 32)),
                    jnp.float32)
    want = np.asarray(impl.apply(conf, params, {}, x)[0])
    atol = 1e-5 if kv_dtype == "f32" else 0.02 * np.abs(want).max()
    entry = {name: jnp.zeros((n_rows,) + shape, dt) for name, (shape, dt)
             in impl.cache_arrays(conf, cap, kv_dtype, page,
                                  jnp.float32).items()}

    def step(**kw):
        return CacheStep(kv_dtype=kv_dtype, page_size=page, **kw)

    def close(got, rows, positions, first_chunk=False):
        got, ref = np.asarray(got), want[rows, positions]
        # a first chunk attends its own fresh rows, whatever the format
        assert np.abs(got - ref).max() <= (1e-5 if first_chunk else atol)
        if kv_dtype == "int8" and not first_chunk:
            assert np.abs(got - ref).max() > 1e-5

    prompt = np.array([8, 5, 7, 3])
    local = np.arange(8)
    keep = (local[None, :] < prompt[:, None]).astype(np.float32)
    with autotune.override({"decode_attn": {"block_k": 8},
                            "decode_attn_q8": {"block_k": 8}}):
        y, entry = impl.apply_cached(
            conf, params, x[:, :8], entry, step(
                rows=jnp.arange(n_rows), keep=jnp.asarray(keep), chunk=True,
                positions=jnp.broadcast_to(local, (n_rows, 8))))
        for i, n in enumerate(prompt):
            close(y[i, :n], i, local[:n], first_chunk=True)
        if kind == "two_chunks":
            # rows 2 and 0 go on: 7 + 6 and 8 + 8 tokens
            rows, more = np.array([2, 0]), np.array([6, 8])
            positions = prompt[rows][:, None] + local[None, :]
            keep = (local[None, :] < more[:, None]).astype(np.float32)
            xs = jnp.stack([x[r, positions[j]] for j, r in enumerate(rows)])
            y, entry = impl.apply_cached(
                conf, params, xs, entry, step(
                    rows=jnp.asarray(rows), keep=jnp.asarray(keep),
                    chunk=True, positions=jnp.asarray(positions)))
            for j, r in enumerate(rows):
                close(y[j, :more[j]], r, positions[j, :more[j]])
            return
        T = 1 if kind == "decode" else 3
        live = np.array([True, False, True, False])
        positions = np.where(live[:, None],
                             prompt[:, None] + np.arange(T)[None, :], cap - 1)
        xs = jnp.stack([x[i, positions[i] % L] for i in range(n_rows)])
        y, after = impl.apply_cached(
            conf, params, xs, entry, step(
                rows=None, positions=jnp.asarray(positions),
                live=jnp.asarray(live)))
    for i in np.flatnonzero(live):
        close(y[i], i, positions[i])
    # a row not live attends no key: its output is the bias through the
    # activation, and it wrote only the scratch position
    assert np.allclose(np.asarray(y)[~live], np.tanh(params["bo"]),
                       atol=1e-6)
    for name in ("k", "v"):
        assert np.array_equal(np.asarray(after[name])[~live, :cap - 1],
                              np.asarray(entry[name])[~live, :cap - 1])


@pytest.mark.parametrize("learned", [True, False])
def test_positional_apply_cached_equals_apply(learned):
    """The positions said outright, arange(T) for every row, give what
    `apply` gives the whole sequence, to the bit (one sinusoid function,
    one table); a layer with no cache entry hands back the None it was
    given."""
    from deeplearning4j_tpu.nn.conf.layers import PositionalEncodingLayer
    from deeplearning4j_tpu.nn.decode import CacheStep
    from deeplearning4j_tpu.nn.layers.attention import PositionalEncodingImpl

    conf = PositionalEncodingLayer(learned=learned, max_length=16,
                                   n_features=12)
    impl = PositionalEncodingImpl()
    params, _ = impl.init(conf, jax.random.PRNGKey(3), jnp.float32)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(3, 10, 12)),
                    jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(10), (3, 10))
    y, entry = impl.apply_cached(conf, params, x, None,
                                 CacheStep(None, positions))
    assert entry is None
    assert np.array_equal(y, impl.apply(conf, params, {}, x)[0])
    assert np.abs(np.asarray(y - x)).max() > 1e-3
    # and a decode step's [b, 1] positions pick the same rows
    at = np.array([7, 0, 9])
    y1, _ = impl.apply_cached(conf, params, x[np.arange(3), at][:, None],
                              None, CacheStep(None, jnp.asarray(at)[:, None]))
    assert np.array_equal(y1[:, 0], y[np.arange(3), at])


def test_the_walk_names_no_layer_and_no_layer_imports_the_walk():
    """nn/decode.py asks the impls (`apply_cached`, `apply_counted`,
    `per_position`, `cache_arrays`, `region`): no identifier in it
    is a layer class of nn/conf/layers.py, it does not import the flash
    kernels, and nothing under nn/layers/ imports nn.decode (the step
    object is passed in)."""
    import deeplearning4j_tpu.nn as nn_pkg
    from deeplearning4j_tpu.nn.conf import layers as conf_layers

    root = pathlib.Path(nn_pkg.__file__).parent
    classes = {name for name, cls in inspect.getmembers(
        conf_layers, inspect.isclass) if cls.__module__ == conf_layers.__name__}
    assert {"SelfAttentionLayer", "PositionalEncodingLayer",
            "DenseLayer"} <= classes

    def identifiers(path):
        names, modules = set(), set()
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Name):
                names.add(node.id)
            elif isinstance(node, ast.Attribute):
                names.add(node.attr)
            elif isinstance(node, ast.ImportFrom):
                modules.add(node.module or "")
                names.update(a.name for a in node.names)
                modules.update(f"{node.module}.{a.name}" for a in node.names)
            elif isinstance(node, ast.Import):
                modules.update(a.name for a in node.names)
        return names, modules

    names, modules = identifiers(root / "decode.py")
    assert not names & classes
    assert not any("flash_attention" in m for m in modules)
    assert {"attn", "posenc"}.isdisjoint(names)
    for path in sorted((root / "layers").glob("*.py")):
        assert not any(m.startswith("deeplearning4j_tpu.nn.decode")
                       for m in identifiers(path)[1]), path.name
