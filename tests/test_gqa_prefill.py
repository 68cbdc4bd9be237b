"""Grouped attention's prefill-chunk kernel (`ops/prefill_attention.
gqa_prefill`) in interpret mode, at a head of 128 and six queries a
key-value head: against the layer's `jnp` walk (`grouped_attention.
chunk_walk`) and a dense float64 softmax written out here, over a ring of
4,096 rows in blocks of 512 (a window layer) and a full layer's rows, at
chunk starts on both sides of the ring's edges; a bucket's pads; two rows
of a batch at different starts; rows no query may see poisoned with NaN;
the off-by-ones the comparison catches; and the layer's routing: only a
cached chunk of a shape the kernel takes, on a TPU, takes it.

The keys are N(0, 1) and each value's first place is the key's POSITION
(less the sequence's length, to keep float32 sums small): under a zero
query a query's output there is the mean position it sees, which a key
more or less at either end of its window moves by about 0.5."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.layers import GroupedAttentionLayer
from deeplearning4j_tpu.nn.decode import CacheStep
from deeplearning4j_tpu.nn.layers import grouped_attention as ga
from deeplearning4j_tpu.ops import autotune
from deeplearning4j_tpu.ops import prefill_attention as pa
from deeplearning4j_tpu.ops.decode_attention import group_queries

Hq, Hk, D = 12, 2, 128
G = Hq // Hk
W = 4096                  # the served window, and its ring's rows
FULL = 12800              # a full layer's rows: starts up to 12,288 + 128
T = 128


@pytest.fixture(autouse=True)
def blocks(monkeypatch):
    """The served ring's blocks of 512 rows; 64 queries a block in
    sub-blocks of 32, so a chunk is two query blocks."""
    monkeypatch.setattr(autotune, "DEFAULT_GQA_PREFILL_BLOCK_Q", 64)
    monkeypatch.setattr(autotune, "DEFAULT_GQA_PREFILL_BLOCK_K", 512)
    monkeypatch.setattr(autotune, "DEFAULT_GQA_PREFILL_SUB_ROWS", 32)


def _sequence(seed, n):
    """Keys and values of positions 0 .. n - 1, [Hk, n, D] each; a value's
    first place is its position less n."""
    rng = np.random.default_rng(seed)
    k = rng.normal(size=(Hk, n, D)).astype(np.float32)
    v = rng.normal(size=(Hk, n, D)).astype(np.float32)
    v[:, :, 0] = np.arange(n) - n
    return k, v


def _entry(seq, start, R, junk=1e4):
    """What a layer's entry of R rows holds after positions 0 .. start - 1
    were written, position p at row p % R; a row never written holds
    `junk`."""
    out = np.full((Hk, R, D), junk, np.float32)
    for p in range(max(0, start - R), start):
        out[:, p % R] = seq[:, p]
    return out


def _case(seed, starts, window, R, n_real=T, rows=None, junk=1e4,
          zero_q=False):
    """The kernel's arguments for chunks at `starts` (one batch row each)
    of the cache rows `rows` of an entry of 3 rows, and the sequences
    they were cut from (`seqs`)."""
    rows = list(rows or [1, 2][:len(starts)])
    B = 3
    k_entry = np.full((B, Hk, R, D), junk, np.float32)
    v_entry = np.full((B, Hk, R, D), junk, np.float32)
    q, k_own, v_own, seqs = [], [], [], []
    rng = np.random.default_rng(seed + 1)
    for row, start in zip(rows, starts):
        ks, vs = _sequence(seed + start, start + T)
        seqs.append((ks, vs))
        k_entry[row] = _entry(ks, start, R, junk)
        v_entry[row] = _entry(vs, start, R, junk)
        k_own.append(ks[:, start:])
        v_own.append(vs[:, start:])
        q.append(np.zeros((Hq, T, D), np.float32) if zero_q
                 else (2 * rng.normal(size=(Hq, T, D))).astype(np.float32))
    keep = np.zeros((len(starts), T), bool)
    keep[:, :n_real] = True
    return {"q": np.stack(q), "k_entry": k_entry, "v_entry": v_entry,
            "k_own": np.stack(k_own), "v_own": np.stack(v_own),
            "keep": keep, "rows": np.asarray(rows, np.int32),
            "starts": np.asarray(starts, np.int32), "window": window,
            "seqs": seqs}


def _kernel(c, window=None, dtype=jnp.float32):
    """-> [b, Hq, T, D] float64."""
    qg = group_queries(jnp.asarray(c["q"], dtype), Hk)
    o = pa.gqa_prefill(
        qg, jnp.asarray(c["k_entry"], dtype), jnp.asarray(c["v_entry"], dtype),
        jnp.asarray(c["k_own"], dtype), jnp.asarray(c["v_own"], dtype),
        jnp.asarray(c["keep"]), jnp.asarray(c["rows"]),
        jnp.asarray(c["starts"]),
        window=c["window"] if window is None else window, interpret=True)
    assert o.dtype == dtype
    return np.asarray(o.astype(jnp.float32), np.float64).reshape(
        len(c["starts"]), Hq, T, D)


def _walk(c):
    """The layer's `jnp` walk on the same arguments -> [b, Hq, T, D]."""
    conf = GroupedAttentionLayer(n_in=8, n_out=8, n_heads=Hq, n_kv_heads=Hk,
                                 head_dim=D, window=c["window"])
    b = len(c["starts"])
    pos = jnp.asarray(c["starts"])[:, None] + jnp.arange(T)[None, :]
    o = ga.chunk_walk(
        conf, jnp.asarray(c["q"]).transpose(0, 2, 1, 3),
        jnp.asarray(c["k_own"]).transpose(0, 2, 1, 3),
        jnp.asarray(c["v_own"]).transpose(0, 2, 1, 3),
        jnp.asarray(c["k_entry"]), jnp.asarray(c["v_entry"]), pos,
        jnp.asarray(c["keep"]), jnp.asarray(c["rows"]))
    return np.asarray(o, np.float64).reshape(b, Hq, T, D)


def _dense(c, floor_shift=0, limit_shift=0):
    """softmax(q . k_p / sqrt(D)) v_p in float64 over the positions each
    query sees: p > pos - window (a window layer), p <= pos, the chunk's
    pads unseen. `floor_shift` / `limit_shift` 1 plant the off-by-ones:
    the floor `>=` for `>`, the limit `<=` for `<` (p < pos + 1)."""
    out = np.zeros((len(c["starts"]), Hq, T, D))
    for i, (start, (ks, vs)) in enumerate(zip(c["starts"], c["seqs"])):
        n = start + T
        seen_keys = np.ones(n, bool)
        seen_keys[start:] = c["keep"][i]
        for t in range(T):
            pos = start + t
            lo = max(0, pos - c["window"] + 1 - floor_shift) \
                if c["window"] else 0
            hi = min(n, pos + 1 + limit_shift)
            idx = np.arange(lo, hi)[seen_keys[lo:hi]]
            for h in range(Hq):
                s = ks[h // G, idx].astype(np.float64) @ \
                    c["q"][i, h, t].astype(np.float64) / np.sqrt(D)
                w = np.exp(s - s.max())
                out[i, h, t] = w @ vs[h // G, idx] / w.sum()
    return out


def _close(got, want, c):
    """float32 sums in another order: 2e-5 in the places of N(0, 1)
    values; in the first place, which holds positions (up to 12,416 of
    them), 0.05, where a key more or less at either end reads 0.5. Only
    the real queries of a bucket count."""
    n = int(c["keep"][0].sum())
    got, want = got[:, :, :n], want[:, :, :n]
    assert np.abs(got[..., 1:] - want[..., 1:]).max() < 2e-5
    assert np.abs(got[..., 0] - want[..., 0]).max() < 0.05


LAYERS = {"window": (W, W), "full": (0, FULL)}
STARTS = (0, 1000, 4090, 4096, 8192, 12288)


@pytest.mark.parametrize("start", STARTS)
@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_the_kernel_is_the_walk_and_the_dense_softmax(layer, start):
    window, R = LAYERS[layer]
    c = _case(start, [start], window, R)
    got = _kernel(c)
    _close(got, _walk(c), c)
    _close(got, _dense(c), c)
    # under a zero query: the mean position each query sees, written out
    z = _case(start, [start], window, R, zero_q=True)
    flat = _kernel(z)[0, :, :, 0]
    for t in range(T):
        lo = max(0, start + t - W + 1) if window else 0
        assert np.abs(flat[:, t] - ((lo + start + t) / 2 - (start + T))) \
            .max() < 0.05


def test_a_bucket_with_pads_and_two_rows_at_different_starts():
    """Rows 2 and 0 of the entry at 5,000 and 300 in one call, 100 real
    tokens of the 128-token bucket: a pad is seen by no query."""
    c = _case(7, [5000, 300], W, W, n_real=100, rows=[2, 0])
    got = _kernel(c)
    _close(got, _walk(c), c)
    _close(got, _dense(c), c)


@pytest.mark.parametrize("layer, start", [("window", 8192), ("window", 1000),
                                          ("full", 1000)])
def test_rows_no_query_sees_are_never_read(layer, start):
    """NaN in every row of the entry that no query of the chunk may see:
    in a ring that has wrapped, the row that holds the position just below
    the first query's window (its block is read for the rows above it);
    in an entry that has not, every row at or past `start` (half a block,
    then whole blocks); and every row of the entry's other cache rows.
    Had the kernel weighed one, the NaN would reach the output."""
    window, R = LAYERS[layer]
    clean = _case(3, [start], window, R, junk=0.0)
    poisoned = _case(3, [start], window, R, junk=np.nan)
    for name in ("k_entry", "v_entry"):
        if start >= R:
            poisoned[name][1, :, (start - W) % R] = np.nan
        poisoned[name][[0, 2]] = np.nan
    assert np.isnan(poisoned["v_entry"][1]).any()
    got = _kernel(poisoned)
    assert np.isfinite(got).all()
    assert np.array_equal(got, _kernel(clean))


def test_the_off_by_ones_fail_the_comparison():
    """The floor `>=` for `>` (the kernel handed a window one longer) and
    the limit `<=` for `<` (one key past each query) each read a key more
    at one end of a window of 4,096: the comparison above refuses both."""
    c = _case(11, [8192], W, W, zero_q=True)
    want = _dense(c)
    _close(_kernel(c), want, c)
    with pytest.raises(AssertionError):
        _close(_kernel(c, window=W + 1), want, c)
    with pytest.raises(AssertionError):
        _close(_dense(c, floor_shift=1), want, c)
    with pytest.raises(AssertionError):
        _close(_dense(c, limit_shift=1), want, c)


def test_in_bfloat16_the_kernel_is_the_walk_to_a_rounding():
    """The served dtype: queries, keys and values in bfloat16; the two
    differ by the output's rounding and the weights' (2^-6 of values of
    1-3, beside positions that bfloat16 holds to 2^-2 of a unit at most
    here, so the first place is left out)."""
    c = _case(5, [4500], W, W)
    for name in ("q", "k_entry", "v_entry", "k_own", "v_own"):
        c[name] = np.asarray(jnp.asarray(c[name], jnp.bfloat16), np.float32)
    got = _kernel(c, dtype=jnp.bfloat16)[..., 1:]
    walk = _walk(c)[..., 1:]
    assert np.abs(got - walk).max() <= 2 ** -6
    assert np.abs(walk).max() > 0.5


# ------------------------------------------------------- the layer's route

def _layer(monkeypatch, on_tpu, window, n_in=96):
    """The layer at a head of 128, the kernel in interpret mode where the
    layer would take it on a TPU; every call of it counted."""
    calls = []
    kernel = functools.partial(pa.gqa_prefill, interpret=True)
    monkeypatch.setattr(pa, "use_kernel", lambda: on_tpu)
    monkeypatch.setattr(pa, "gqa_prefill",
                        lambda *a, **k: calls.append(1) or kernel(*a, **k))
    conf = GroupedAttentionLayer(n_in=n_in, n_out=n_in, n_heads=Hq,
                                 n_kv_heads=Hk, head_dim=D, window=window,
                                 rope_theta=10000.0 if window else 0.0,
                                 weight_init="lecun")
    impl = ga.GroupedAttentionImpl()
    params, _ = impl.init(conf, jax.random.PRNGKey(40), jnp.float32)
    return conf, impl, params, calls


def _chunk(bucket, n_real, start, rows, capacity, impl, conf, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(1, bucket, conf.n_in)), jnp.float32)
    keep = jnp.asarray(np.arange(bucket) < n_real, jnp.float32)[None]
    cache = {n: jnp.asarray(rng.normal(size=(3,) + a[0]), jnp.float32)
             for n, a in impl.cache_arrays(conf, capacity, "f32", 16,
                                           jnp.float32).items()}
    step = CacheStep(jnp.asarray([rows], jnp.int32),
                     start + jnp.arange(bucket)[None], keep=keep, chunk=True)
    return x, cache, step


@pytest.mark.parametrize("window, capacity, start", [
    (256, 1024, 200),        # a ring of 256 rows, wrapped by the write
    (256, 1024, 700),        # a ring that had wrapped before the chunk
    (0, 1024, 300)])         # a full layer's rows
def test_a_served_chunk_with_pads_takes_the_kernel(monkeypatch, window,
                                                   capacity, start):
    """A prefill chunk through `apply_cached`, a 128-token bucket with 90
    real tokens: the kernel's route writes the same entry, counts the
    same and gives the real rows the walk's output."""
    out = {}
    for on_tpu in (True, False):
        conf, impl, params, calls = _layer(monkeypatch, on_tpu, window)
        x, cache, step = _chunk(128, 90, start, 1, capacity, impl, conf)
        out[on_tpu] = impl.apply_cached(conf, params, x, cache, step)
        assert len(calls) == on_tpu
    (y_k, c_k, n_k), (y_w, c_w, n_w) = out[True], out[False]
    assert all(np.array_equal(c_k[n], c_w[n]) for n in c_k)
    assert {n: int(v) for n, v in n_k.items()} == \
        {n: int(v) for n, v in n_w.items()}
    real = np.asarray(y_k[0, :90])
    assert np.abs(real - np.asarray(y_w[0, :90])).max() \
        <= 1e-5 * max(1.0, np.abs(real).max())


@pytest.mark.parametrize("window, capacity, bucket", [
    (24, 96, 32),            # a chunk longer than its ring of 24 rows
    (24, 96, 16),            # a ring of 24 rows: no block of 16 divides it
    (256, 1024, 64)])        # a chunk of no whole 128-key lane
def test_a_shape_the_kernel_does_not_take_keeps_the_walk(monkeypatch, window,
                                                         capacity, bucket):
    """On a TPU too: `gqa_prefill_fits` says no, the kernel is never
    called, and the chunk's output and entry are the walk's bit for bit
    (a chunk longer than the ring keeps its last 24 tokens)."""
    R = min(capacity, window)
    assert not pa.gqa_prefill_fits(bucket, R)
    out = {}
    for on_tpu in (True, False):
        conf, impl, params, calls = _layer(monkeypatch, on_tpu, window)
        x, cache, step = _chunk(bucket, bucket - 3, 40, 2, capacity, impl,
                                conf, seed=1)
        out[on_tpu] = impl.apply_cached(conf, params, x, cache, step)
        assert not calls
    (y_k, c_k, _), (y_w, c_w, _) = out[True], out[False]
    assert np.array_equal(y_k, y_w)
    assert all(np.array_equal(c_k[n], c_w[n]) for n in c_k)
    assert pa.gqa_prefill_fits(128, 256) and pa.gqa_prefill_fits(1024, 4096)
    assert pa.gqa_prefill_fits(1024, 17408)


def test_apply_and_a_decode_step_never_take_the_kernel(monkeypatch):
    """Training and `output()` (`apply`), and a decode step (`gqa_decode`),
    keep their forms on a TPU too."""
    conf, impl, params, calls = _layer(monkeypatch, True, 256)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(2, 128, 96)), jnp.float32)
    y, _ = impl.apply(conf, params, {}, x)
    assert y.shape == (2, 128, 96)
    from deeplearning4j_tpu.ops import decode_attention as da
    monkeypatch.setattr(da, "_use_kernel", lambda: False)
    _, cache, _ = _chunk(128, 128, 0, 0, 1024, impl, conf)
    y, _, _ = impl.apply_cached(
        conf, params, x[:, :1].repeat(3, 0)[:3], cache,
        CacheStep(None, jnp.asarray([[3], [9], [0]])))
    assert y.shape == (3, 1, 96)
    assert not calls
