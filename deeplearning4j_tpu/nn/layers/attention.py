"""Transformer building blocks: LayerNormalization, SelfAttentionLayer.

New capabilities for the Transformer north star (SURVEY.md §7 step 6) — no
reference analogue. Attention computes per-head scaled dot product over
[batch, time, features]; XLA fuses the softmax chain. A ring-attention
sequence-parallel variant lives in deeplearning4j_tpu/parallel/ring_attention.py
and is selected by the parallel plan, not the layer config.

Serving. A layer that has to know where its tokens stand carries
``apply_cached(conf, params, x, entry, step) -> (y, entry)`` beside
`apply`: x [b, T, n_in] is one serving step's tokens, `entry` the
layer's own arrays of the decode cache (None for a layer that keeps
none: `PositionalEncodingLayer`), `step` the nn/decode.CacheStep that
says which rows and positions the call holds and writes and attends an
entry in the cache's stored format. The walk of nn/decode.py calls it
and knows nothing else of the layer.

`SelfAttentionLayer`'s entry is its keys and values as projected, one
[H, D] row a position: {"k": [B, S, H, D], "v": ...} in the compute
dtype (the position on axis 1, so that a step's scatter writes whole
rows), or, with kv_dtype="int8", int8 codes and one float32 scale a
(row, page, head): {"k", "k_scale", "v", "v_scale"}, about a quarter of
the bytes a slot (ops/decode_attention.py quantizes as a step writes
and dequantizes as a key block loads). A decode step (one token a row)
and a speculative verify step (a window of K) write their rows and then
attend the cache with ``key_limit = position + 1``: causal, itself
included, so query i of a window reads what i + 1 decode steps would
have read, and a rejected draft's rows stay unseen until the next
window overwrites them. A prefill chunk attends itself (the flash
kernels inside their envelope, as in training) and the rows its prompt
wrote before it, and merges the two by their log-sum-exps.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.ops.flash_attention import (
    MAX_FLASH_T,
    chunked_flash_attention,
    chunked_unsupported_reason,
    flash_attention,
    flash_attention_lse_masked,
    flash_attention_qkv,
    lse_combine,
    supports as flash_supports,
    supports_chunked as flash_supports_chunked,
    supports_monolithic_fallback as flash_supports_monolithic_fallback,
    supports_qkv as flash_supports_qkv,
)
from deeplearning4j_tpu.nn.conf.layers import (
    LayerNormalization,
    PositionalEncodingLayer,
    RMSNormalization,
    SelfAttentionLayer,
)
from deeplearning4j_tpu.nn.layers.base import LayerImpl, apply_dropout, register_impl
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.ops.activations import get_activation


@register_impl(LayerNormalization)
class LayerNormImpl(LayerImpl):
    per_position = True
    region = "norm"

    def init(self, conf, rng, dtype):
        n = conf.n_out or conf.n_in
        return {"gamma": jnp.ones((n,), dtype), "beta": jnp.zeros((n,), dtype)}, {}

    def apply(self, conf, params, state, x, *, train=False, rng=None, mask=None):
        # Deliberately the plain jnp form: a Pallas fused LN exists
        # (ops/fused_layernorm.py) but LOST a same-window A/B on v5e
        # (0.494 MFU with XLA's lowering vs 0.455 fused at the flagship
        # shapes) — XLA fuses the normalize into neighboring residual/
        # matmul fusions, which a pallas_call boundary forbids. Kept as
        # an op for shapes where that tradeoff flips.
        # (r5: an E[x^2]-mu^2 one-pass variant — the trick that cut the
        # VGG BatchNorm's spatial reductions 30x — A/B'd FLAT here:
        # 1.97M vs 1.99M tok/s interleaved means. XLA already multi-
        # output-fuses LN's lane-axis mean+var into one read at these
        # shapes, so the rewrite only traded numerics for nothing.)
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        xn = (x - mu) * jax.lax.rsqrt(var + conf.eps)
        return xn * params["gamma"] + params["beta"], state


def rms_norm(x, gamma, eps):
    """x * rsqrt(mean(x^2) + eps) * gamma over the last axis; the mean in
    float32 whatever x is (a bfloat16 sum of 7,680 squares keeps three
    digits), the result in x's dtype."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(x.dtype)


@register_impl(RMSNormalization)
class RMSNormImpl(LayerImpl):
    per_position = True
    region = "norm"

    def init(self, conf, rng, dtype):
        return {"gamma": jnp.ones((conf.n_out or conf.n_in,), dtype)}, {}

    def apply(self, conf, params, state, x, *, train=False, rng=None, mask=None):
        return rms_norm(x, params["gamma"], conf.eps), state


def _sp_axis_in_scope(name: str) -> bool:
    """True when `name` is a bound mesh axis (i.e. we are tracing inside
    the sequence-parallel shard_map). An SP-configured layer used OUTSIDE
    shard_map — ordinary inference after SP training, a reloaded config —
    falls back to the dense path, which is the correct full-sequence
    semantics on one host."""
    if not name:
        return False
    try:
        jax.lax.axis_index(name)  # unused op when bound; DCE'd
        return True
    except NameError:
        return False


def sinusoidal(positions, d, dtype):
    """Sinusoidal encodings at explicit positions [...] -> [..., d]:
    float32 arithmetic, cast at the end, so that a serving step and the
    full forward give a position the same row."""
    pos = positions[..., None].astype(jnp.float32)
    dim = jnp.arange(0, d, 2).astype(jnp.float32)
    angle = pos / jnp.power(10000.0, dim / d)
    pe = jnp.zeros(positions.shape + (d,), jnp.float32)
    pe = pe.at[..., 0::2].set(jnp.sin(angle))
    pe = pe.at[..., 1::2].set(jnp.cos(angle[..., : d // 2]))
    return pe.astype(dtype)


@register_impl(PositionalEncodingLayer)
class PositionalEncodingImpl(LayerImpl):
    region = "embed"

    def init(self, conf, rng, dtype):
        if conf.learned:
            pe = 0.02 * jax.random.normal(
                rng, (conf.max_length, conf.n_features), dtype)
            return {"pe": pe}, {}
        return {}, {}

    def apply(self, conf, params, state, x, *, train=False, rng=None, mask=None):
        T, d = x.shape[1], x.shape[2]
        offset = 0
        axis = getattr(conf, "seq_parallel_axis", "")
        if _sp_axis_in_scope(axis):
            # inside the sequence-parallel shard_map: x is the LOCAL block
            # of the sequence — encode its global positions
            if conf.learned:
                # psum of a Python scalar is the static axis size; check at
                # trace time (dynamic_slice would silently CLAMP an
                # overflowing offset, duplicating pe rows across shards)
                n_shards = jax.lax.psum(1, axis)
                if n_shards * T > conf.max_length:
                    raise ValueError(
                        f"global sequence {n_shards}x{T} exceeds learned "
                        f"positional table max_length={conf.max_length}")
            offset = jax.lax.axis_index(axis) * T
        if conf.learned:
            pe = jax.lax.dynamic_slice(params["pe"], (offset, 0), (T, d))
        else:
            pe = sinusoidal(offset + jnp.arange(T), d, x.dtype)
        return x + pe, state

    def apply_cached(self, conf, params, x, entry, step):
        """x + pe(step.positions); the layer keeps nothing in the cache
        (`entry` is None and goes back as it came)."""
        if conf.learned:
            pe = jnp.take(params["pe"], step.positions, axis=0)
        else:
            pe = sinusoidal(step.positions, x.shape[-1], x.dtype)
        return x + pe, entry


def dot_product_attention(q, k, v, *, causal, mask=None, dropout=0.0, rng=None,
                          train=False):
    """q,k,v: [B, H, T, D]. Returns [B, H, T, D]. Computed in f32 for the
    softmax (bf16-safe), outputs cast back to q.dtype."""
    d = q.shape[-1]
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / jnp.sqrt(float(d))
    T = q.shape[2]
    if causal:
        cm = jnp.tril(jnp.ones((T, T), bool))
        scores = jnp.where(cm, scores, -1e30)
    if mask is not None:
        # mask: [B, T] keyed on keys
        scores = jnp.where(mask[:, None, None, :].astype(bool), scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1)
    if dropout and train and rng is not None:
        keep = jax.random.bernoulli(rng, 1.0 - dropout, w.shape)
        w = jnp.where(keep, w / (1.0 - dropout), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", w.astype(v.dtype), v)
    return out.astype(q.dtype)


def dense_attention_lse(qh, kh, vh, kmask):
    """Causal attention of a chunk on itself that keeps its lse, (out,
    lse): what a prefill chunk outside the flash envelope runs (tiny
    serving buckets, the CPU). qh/kh/vh [b, H, T, D]; kmask [b, T].
    float32 softmax like every other attention path."""
    D, T = qh.shape[-1], qh.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                   preferred_element_type=jnp.float32) / jnp.sqrt(
                       jnp.float32(D))
    cm = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(cm, s, -1e30)
    s = jnp.where(kmask[:, None, None, :].astype(bool), s, -1e30)
    m = s.max(-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vh.astype(jnp.float32))
    o = o / jnp.maximum(l, 1e-30)[..., None]
    return o.astype(qh.dtype), m + jnp.log(jnp.maximum(l, 1e-30))


def chunk_attention_lse(qh, kh, vh, kmask):
    """Causal attention of a prefill chunk on itself, (out, lse): the
    autotuned flash kernels where the chunk is inside their envelope
    (the dispatch discipline of training), the dense form outside."""
    b, H, T, D = qh.shape
    if flash_supports(qh.shape, causal=True, dropout=0.0, mask=kmask):
        # flat [b*H, T, D] layout is b-major, so the key mask repeats
        # per head within each batch row
        km = jnp.repeat(jnp.asarray(kmask, jnp.float32), H,
                        axis=0)[:, None, :]
        o, lse = flash_attention_lse_masked(
            qh.reshape(b * H, T, D), kh.reshape(b * H, T, D),
            vh.reshape(b * H, T, D), km, 1.0 / float(D) ** 0.5, True)
        return (o.reshape(b, H, T, D),
                lse.reshape(b, H, T).astype(jnp.float32))
    return dense_attention_lse(qh, kh, vh, kmask)


@register_impl(SelfAttentionLayer)
class SelfAttentionImpl(LayerImpl):
    region = "attention"

    def init(self, conf, rng, dtype):
        k1, k2 = jax.random.split(rng)
        n_in, n = conf.n_in, conf.n_out
        return {
            "Wqkv": init_weights(k1, (n_in, 3 * n), conf.weight_init, conf.dist,
                                 dtype, fan_in=n_in, fan_out=n),
            "bqkv": jnp.zeros((3 * n,), dtype),
            "Wo": init_weights(k2, (n, n), conf.weight_init, conf.dist, dtype),
            "bo": jnp.zeros((n,), dtype),
        }, {}

    def cache_arrays(self, conf, capacity, kv_dtype, page_size, dtype):
        """The arrays one decode slot of this layer holds, {name: (shape,
        dtype)}: keys and values, one [H, D] row a position each, in the
        compute dtype; or int8 codes with one float32 scale a (page,
        head). nn/decode.py allocates them and walks them; the serving
        allocator bills them (serving/kvcache.bytes_per_slot)."""
        H = conf.n_heads
        row = (capacity, H, conf.n_out // H)
        if kv_dtype == "int8":
            scale = (capacity // page_size, H)
            return {"k": (row, jnp.int8), "k_scale": (scale, jnp.float32),
                    "v": (row, jnp.int8), "v_scale": (scale, jnp.float32)}
        return {"k": (row, dtype), "v": (row, dtype)}

    def apply_cached(self, conf, params, x, entry, step):
        """One serving step through this layer's cache entry (module
        docstring): project, write the call's key and value rows (zero
        where `step.keep` is 0: the pad of a prefill bucket), attend.
        x [b, T, n_in] -> (y [b, T, n_out], entry)."""
        b, T, _ = x.shape
        H, n = conf.n_heads, conf.n_out
        qkv = x @ params["Wqkv"] + params["bqkv"]
        q, k, v = (t.reshape(b, T, H, n // H)
                   for t in jnp.split(qkv, 3, axis=-1))
        if step.keep is None:
            entry = step.write(entry, k, v)
        else:
            keep = step.keep[..., None, None]
            entry = step.write(entry, k * keep, v * keep)
        qh = q.transpose(0, 2, 1, 3)                    # [b, H, T, D]
        if step.chunk:
            o, lse = chunk_attention_lse(qh, k.transpose(0, 2, 1, 3),
                                         v.transpose(0, 2, 1, 3), step.keep)
            # the rows this prompt wrote before the chunk's first token
            # (none on a first chunk: that half's lse sits at the mask
            # floor and merges to weight zero)
            before = jnp.broadcast_to(step.positions[:, :1], (b, T))
            o, _ = lse_combine(o, lse, *step.attend(entry, qh, before,
                                                    rows=step.rows))
            o = o.astype(qh.dtype)
        else:
            o, _ = step.attend(entry, qh, step.positions + 1)
        y = o.transpose(0, 2, 1, 3).reshape(b, T, n)
        y = y @ params["Wo"] + params["bo"]
        return get_activation(conf.activation or "identity")(y), entry

    def apply(self, conf, params, state, x, *, train=False, rng=None, mask=None):
        if conf.dropout:
            rng, sub = jax.random.split(rng) if rng is not None else (None, None)
            x = apply_dropout(x, conf.dropout, sub, train=train)
        B, T, _ = x.shape
        H = conf.n_heads
        n = conf.n_out
        D = n // H
        qkv = x @ params["Wqkv"] + params["bqkv"]  # [B, T, 3n]
        drop_attn = conf.attention_dropout if train else 0.0
        use_flash = getattr(conf, "use_flash", True)
        if (use_flash
                and not _sp_axis_in_scope(getattr(conf, "seq_parallel_axis",
                                                  ""))
                and flash_supports_qkv(B, T, n, H, dropout=drop_attn)):
            # packed path: the kernels read head column-slices straight
            # from the projection output — no [B,T,H,D]->[B,H,T,D]
            # relayout in either direction (r4 MFU item a). Attention
            # dropout stays on this path too (r5): the r4 fallback to the
            # flat layout re-paid ~0.9 ms/step of head transposes, most
            # of the VERDICT r4 #2 dropout MFU tax
            out = flash_attention_qkv(qkv, H, causal=conf.causal, mask=mask,
                                      dropout=drop_attn, dropout_rng=rng)
            y = out @ params["Wo"] + params["bo"]
            return get_activation(conf.activation or "identity")(y), state
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(B, T, H, D).transpose(0, 2, 1, 3)

        qh, kh, vh = heads(q), heads(k), heads(v)
        if _sp_axis_in_scope(getattr(conf, "seq_parallel_axis", "")):
            # inside the sequence-parallel shard_map: local q block attends
            # the K/V blocks rotating around the ICI ring; the full [T, T]
            # scores never exist on any one shard. Attention dropout rides
            # the ring since r6 (global-coordinate keep mask; the step rng
            # is replicated across seq shards, which is exactly what the
            # mask needs)
            if mask is not None:
                raise ValueError(
                    "sequence-parallel attention does not support padding "
                    "masks — pad to full length")
            from deeplearning4j_tpu.parallel.ring_attention import (
                ring_attention,
            )

            out = ring_attention(qh, kh, vh,
                                 axis_name=conf.seq_parallel_axis,
                                 causal=conf.causal,
                                 dropout=drop_attn, dropout_rng=rng)
        elif use_flash and flash_supports(
                qh.shape, causal=conf.causal, dropout=drop_attn, mask=mask):
            out = flash_attention(qh, kh, vh, causal=conf.causal, mask=mask,
                                  dropout=drop_attn, dropout_rng=rng)
        elif use_flash and flash_supports_chunked(
                qh.shape, causal=conf.causal, dropout=drop_attn, mask=mask):
            # T beyond the monolithic kernels' envelope: blockwise
            # tiles + lse merge (single-chip ring); padding masks slice
            # per kv tile and dropout hashes global coordinates (r6), so
            # the full training feature set rides this path. Since r8
            # the tier is D-aware (head dims past 128 use shorter proven
            # tiles) and non-causal kv tiles scan instead of unrolling
            # n^2 kernel calls. Past this, the seq mesh axis shards T
            # across chips (sequence_parallel.py)
            out = chunked_flash_attention(qh, kh, vh, causal=conf.causal,
                                          mask=mask, dropout=drop_attn,
                                          dropout_rng=rng)
        elif (use_flash and T > MAX_FLASH_T
              and flash_supports_monolithic_fallback(
                  qh.shape, causal=conf.causal, dropout=drop_attn,
                  mask=mask)):
            # non-tileable T at D <= 128 still compiles monolithically
            # to MONOLITHIC_COMPILE_MAX (every in-kernel feature rides)
            out = flash_attention(qh, kh, vh, causal=conf.causal, mask=mask,
                                  dropout=drop_attn, dropout_rng=rng)
        elif use_flash and T > MAX_FLASH_T:
            # dense [T, T] scores at these lengths are a guaranteed
            # device OOM — fail with instructions, not an opaque OOM
            raise ValueError(chunked_unsupported_reason(
                T, dropout=drop_attn, mask=mask, causal=conf.causal,
                head_dim=D))
        else:
            out = dot_product_attention(
                qh, kh, vh, causal=conf.causal, mask=mask,
                dropout=conf.attention_dropout, rng=rng, train=train,
            )
        out = out.transpose(0, 2, 1, 3).reshape(B, T, n)
        y = out @ params["Wo"] + params["bo"]
        return get_activation(conf.activation or "identity")(y), state
