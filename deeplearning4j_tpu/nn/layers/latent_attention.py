"""Latent attention: causal multi-head attention whose keys and values
are expanded from one low-rank latent a token, so that the latent IS the
cache row (`LatentAttentionLayer`, nn/conf/layers.py).

For a token x at position t (no projection has a bias; N is RMS norm,
`attention.rms_norm`):

    cq            = Nq(x Wqa)                       [q_rank]
    [q_nope|q_pe] = cq [Wqb_nope | Wqb_rope]        [H, nope], [H, rope]
    [ckv | k_pe]  = x Wkva                          [kv_rank + rope]
    row_t         = [Nkv(ckv) | rot(k_pe, t)]       the cache row
    [k_nope | v]  = Nkv(ckv) [Wkvb_k | Wkvb_v]      [H, nope], [H, v]
    score(t, s)   = (q_nope . k_nope_s + rot(q_pe, t) . rot(k_pe_s, s))
                    / sqrt(nope + rope)             for s <= t
    y             = concat_h(softmax_s(score) v_s) Wo

`rot` turns the pairs (i, i + rope/2) of its argument by t * theta^(-2i /
rope): the two halves of the slice, a fixed permutation of the
interleaved pairing. The one rotary key slice is shared by all heads.

The row's two parts are kept as two arrays, `ckv` [B, S, kv_rank] and
`kpe` [B, S, rope]: kv_rank + rope values a token, no head axis. One
[B, S, kv_rank + rope] array would hold the same bytes, but 576 is no
multiple of the TPU's 128-lane tile, so the device stores such an array
position-minor and every step's program copies the whole cache into
row-major order and back (seen in the program compiled for a v5e: two
340 MB copies a layer a step at 64 slots of 4,608 positions). 512 lies
on the tile as it is and is read where it lies; the 64-wide part, a
ninth of the bytes, is still relaid by each step, whichever way round
it is stored (38 MB, three times a layer). For the same reason the
parameters keep the two up-projections split by what their columns make
(`Wqb_nope` / `Wqb_rope`, `Wkvb_k` / `Wkvb_v`): a column slice of a
single [rank, H * (a + b)] matrix is a strided copy of the weight in
every step (XLA turns the slice of the product into a product with the
sliced weight). The query's two are held output-major, [H * width,
q_rank]: both serving programs consume them so, and held the other way
round each step transposes them first.

The mathematics is written once, in `latent_attention`, with the cache as
optional explicit state: without one the rows of the call are the whole
context (`apply`: training and `output()`); with one, the call's rows are
written at (cache row, position) first and the context is the cache
(`apply_cached`: the serving steps of nn/decode.py). Three ways to
attend, one result (tier-1 holds them equal); which one follows from
what the call is, not from an option:

* expanded, in `jnp` (`apply`; a prefill chunk off the TPU): keys and
  values are rebuilt from the context's rows, a block of `KEY_BLOCK`
  positions at a time under a running softmax, so a chunk of 1,024
  queries over 4,096 keys never holds more than a [H, 1024, KEY_BLOCK]
  block of scores; only the blocks below the highest position of the
  call are visited. Differentiable with a Python-int limit. On the chip
  XLA writes each block's float32 scores to memory and reads them back
  (256 MB a block at 128 heads: PERF.md section 5, PR 31).
* the kernel (a prefill chunk through the cache on a TPU): the same
  walk as one Pallas kernel, `ops/prefill_attention.prefill_flash`,
  which expands each key block's keys and values in VMEM from the cache
  rows where they lie and keeps scores, running max and sum there; a
  block past the chunk's causal frontier is neither copied nor scored.
  Forward only.
* latent (a decode or verify step): `Wkvb`'s key half is folded into
  the query ([H, kv_rank]), scores and the weighted sum are plain
  batched products against the rows as they lie ([B, S, kv_rank] and
  [B, S, rope], no head axis, no copy), and `Wkvb`'s value half is
  applied to the [H, kv_rank] result. For few queries a row: nothing per
  head is rebuilt for the context.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.layers import LatentAttentionLayer
from deeplearning4j_tpu.nn.layers.attention import rms_norm
from deeplearning4j_tpu.nn.layers.base import (
    LayerImpl,
    apply_dropout,
    register_impl,
)
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.ops import prefill_attention
from deeplearning4j_tpu.ops.activations import get_activation

KEY_BLOCK = 512     # context positions one pass of the expanded form holds
_NEG_INF = -1e30


def rotary(x, positions, theta):
    """Rotary position on the last axis of x [b, T, ..., R] at
    positions [b, T]: the pair (i, i + R/2) is turned by
    positions * theta^(-2i / R). Angles, sines and the products in
    float32 (position 4,607 times a bfloat16 frequency is off by
    radians); the result in x's dtype."""
    half = x.shape[-1] // 2
    freq = jnp.power(jnp.float32(theta),
                     -jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None] * freq  # [b, T, R/2]
    angle = angle.reshape(angle.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _per_head(w, n_heads):
    """[rank, H * width] -> [rank, H, width] (no data moves)."""
    return w.reshape(w.shape[0], n_heads, w.shape[1] // n_heads)


def _attend_expanded(conf, params, q_nope, q_pe, ckv, kpe, qpos, limit):
    """q_nope [b, T, H, nope], q_pe [b, T, H, rope] (rotated) against
    the context's rows ckv [b, S, kv_rank], kpe [b, S, rope]; the key at
    index s is visible to query (b, t) iff s <= qpos[b, t]. `limit`: the
    number of leading context rows any query can see (a Python int keeps
    the loop static, and differentiable). -> [b, T, H, v]."""
    b, T, H, n = q_nope.shape
    S, v = ckv.shape[1], conf.v_dim
    KB = min(KEY_BLOCK, S)
    Wk = _per_head(params["Wkvb_k"], H)
    Wv = _per_head(params["Wkvb_v"], H)
    scale = 1.0 / float(n + conf.rope_dim) ** 0.5

    def block(i, carry):
        m, l, acc = carry
        # the last block of a context that is no multiple of KB starts
        # early; the rows it repeats are masked (idx < i * KB)
        s0 = jnp.minimum(i * KB, S - KB)
        rows = jax.lax.dynamic_slice_in_dim(ckv, s0, KB, axis=1)
        k_nope = jnp.einsum("bsc,chn->bshn", rows, Wk)
        val = jnp.einsum("bsc,chv->bshv", rows, Wv)
        s = (jnp.einsum("bthn,bshn->bhts", q_nope, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bthr,bsr->bhts", q_pe,
                          jax.lax.dynamic_slice_in_dim(kpe, s0, KB, axis=1),
                          preferred_element_type=jnp.float32)) * scale
        idx = s0 + jnp.arange(KB)
        seen = ((idx[None, None, :] <= qpos[:, :, None])
                & (idx >= i * KB)[None, None, :])[:, None]   # [b, 1, T, KB]
        s = jnp.where(seen, s, _NEG_INF)
        # every query sees key 0 in block 0 (positions are >= 0), so the
        # running maximum is a real score from the first block on and a
        # masked score's exp is 0 without a second mask
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhts,bshv->bhtv", p.astype(val.dtype), val,
            preferred_element_type=jnp.float32)
        return m_new, l * alpha + p.sum(-1), acc

    carry = (jnp.full((b, H, T), _NEG_INF, jnp.float32),
             jnp.zeros((b, H, T), jnp.float32),
             jnp.zeros((b, H, T, v), jnp.float32))
    n_blocks = (limit + KB - 1) // KB
    _, l, acc = jax.lax.fori_loop(0, n_blocks, block, carry)
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o.transpose(0, 2, 1, 3).astype(q_nope.dtype)


def _attend_latent(conf, params, q_nope, q_pe, ckv, kpe, qpos):
    """The same attention in the latent space: the context's rows are
    read as they lie, twice, and nothing per head is built for them."""
    H = conf.n_heads
    scale = 1.0 / float(conf.nope_dim + conf.rope_dim) ** 0.5
    q_lat = jnp.einsum("bthn,chn->bthc", q_nope,
                       _per_head(params["Wkvb_k"], H))
    s = (jnp.einsum("bthc,bsc->bhts", q_lat, ckv,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bthr,bsr->bhts", q_pe, kpe,
                      preferred_element_type=jnp.float32)) * scale
    seen = jnp.arange(ckv.shape[1])[None, None, :] <= qpos[:, :, None]
    w = jax.nn.softmax(jnp.where(seen[:, None], s, _NEG_INF), axis=-1)
    o_lat = jnp.einsum("bhts,bsc->bthc", w.astype(ckv.dtype), ckv)
    return jnp.einsum("bthc,chv->bthv", o_lat,
                      _per_head(params["Wkvb_v"], H))


def latent_attention(conf, params, x, positions, *, cache=None, rows=None,
                     keep=None, latent=False):
    """The layer on x [b, T, d] whose tokens occupy `positions` [b, T].

    Without `cache` the call's own rows are the context. With one
    ({"ckv": [B, S, kv_rank], "kpe": [B, S, rope]}) the call's rows are
    written at (`rows` [b], positions) first, zeroed where `keep` [b, T]
    is 0 (the pad of a prefill bucket), and the context is the cache's
    `rows` (None: the call holds every cache row, in order; nothing is
    gathered). `latent` picks the latent-space form; a cached call that
    is not latent is a prefill chunk, whose positions run on from
    `positions[:, 0]` (nn/decode.py), and takes the kernel on a TPU.
    -> (y [b, T, n_out], cache)."""
    b, T, _ = x.shape
    H, c, n, r = conf.n_heads, conf.kv_rank, conf.nope_dim, conf.rope_dim
    cq = rms_norm(x @ params["Wqa"], params["q_norm"], conf.eps)
    q_nope = jnp.einsum("btq,mq->btm", cq,
                        params["Wqb_nope"]).reshape(b, T, H, n)
    q_pe = rotary(jnp.einsum("btq,mq->btm", cq,
                             params["Wqb_rope"]).reshape(b, T, H, r),
                  positions, conf.rope_theta)
    kva = x @ params["Wkva"]
    ckv = rms_norm(kva[..., :c], params["kv_norm"], conf.eps)
    kpe = rotary(kva[..., c:], positions, conf.rope_theta)
    kernel = cache is not None and not latent and \
        prefill_attention.use_kernel()
    limit = T
    if cache is not None:
        at = (jnp.arange(b) if rows is None else rows)[:, None]
        with jax.named_scope("cache_write"):
            if keep is not None:
                ckv = ckv * keep[..., None].astype(ckv.dtype)
                kpe = kpe * keep[..., None].astype(kpe.dtype)
            cache = {"ckv": cache["ckv"].at[at, positions].set(
                         ckv.astype(cache["ckv"].dtype)),
                     "kpe": cache["kpe"].at[at, positions].set(
                         kpe.astype(cache["kpe"].dtype))}
        ckv, kpe = cache["ckv"], cache["kpe"]
        if rows is not None and not kernel:
            ckv, kpe = ckv[rows], kpe[rows]
        limit = jnp.max(positions) + 1
    if kernel:
        o = prefill_attention.prefill_flash(
            q_nope.reshape(b, T, H * n), q_pe, ckv, kpe, params["Wkvb_k"],
            params["Wkvb_v"], at[:, 0], positions[:, 0])
    elif latent:
        o = _attend_latent(conf, params, q_nope, q_pe, ckv, kpe, positions)
    else:
        o = _attend_expanded(conf, params, q_nope, q_pe, ckv, kpe, positions,
                             limit)
    y = o.reshape(b, T, H * conf.v_dim) @ params["Wo"]
    return get_activation(conf.activation or "identity")(y), cache


@register_impl(LatentAttentionLayer)
class LatentAttentionImpl(LayerImpl):
    region = "attention"

    def init(self, conf, rng, dtype):
        H, d = conf.n_heads, conf.n_in
        c, n, r, v = conf.kv_rank, conf.nope_dim, conf.rope_dim, conf.v_dim
        k = jax.random.split(rng, 7)

        def w(key, shape):
            return init_weights(key, shape, conf.weight_init, conf.dist, dtype)

        return {"Wqa": w(k[0], (d, conf.q_rank)),
                "q_norm": jnp.ones((conf.q_rank,), dtype),
                "Wqb_nope": w(k[1], (H * n, conf.q_rank)),
                "Wqb_rope": w(k[5], (H * r, conf.q_rank)),
                "Wkva": w(k[2], (d, c + r)),
                "kv_norm": jnp.ones((c,), dtype),
                "Wkvb_k": w(k[3], (c, H * n)),
                "Wkvb_v": w(k[6], (c, H * v)),
                "Wo": w(k[4], (H * v, conf.n_out))}, {}

    def apply(self, conf, params, state, x, *, train=False, rng=None,
              mask=None):
        if not conf.causal:
            raise ValueError("LatentAttentionLayer is causal only")
        if conf.dropout:
            x = apply_dropout(x, conf.dropout, rng, train=train)
        b, T, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (b, T))
        y, _ = latent_attention(conf, params, x, positions)
        return y, state

    def cache_arrays(self, conf, capacity, kv_dtype, page_size, dtype):
        """One row a position, no head axis, in the compute dtype: the
        normalised key-value latent and the rotated key slice, as two
        arrays (module docstring)."""
        if kv_dtype == "int8":
            raise ValueError("the latent cache row has no int8 form")
        return {"ckv": ((capacity, conf.kv_rank), dtype),
                "kpe": ((capacity, conf.rope_dim), dtype)}

    def apply_cached(self, conf, params, x, entry, step):
        """One serving step through the cache entry of this layer
        (nn/decode.CacheStep says which rows and positions): a prefill
        chunk expands keys and values (in the kernel on a TPU), a
        decode or verify step attends in the latent space. -> (y,
        entry)."""
        return latent_attention(conf, params, x, step.positions,
                                cache=entry, rows=step.rows, keep=step.keep,
                                latent=not step.chunk)
