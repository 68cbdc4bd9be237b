"""Incremental autoregressive decode with an explicit KV cache.

The reference's `rnnTimeStep` (MultiLayerNetwork.java:2147) is a
stateful streaming-inference contract that our SelfAttention layers
reject — attention "needs the full sequence" — so until r11 serving
re-ran the whole forward per generated token: N tokens cost N
full-sequence forwards. This module is the productionized incremental
contract for transformer stacks, on BOTH containers:

* ``make_decode_fn(net)`` — a pure jitted-step body
  ``(params, state, cache, token, pos) -> (probs, cache)``: one new
  token per cache row, positions per row (continuous batching mixes
  rows at different depths), the KV cache threaded as explicit state.
  Attention is single-query against the cache
  (ops/decode_attention.py, `decode_attn` autotune family): it reads
  the cache where it lies, a key block at a time, up to the last block
  a live row of the batch can see, so the step's cost follows the
  longest live row and not the cache's capacity.
* ``make_prefill_fn(net)`` — the chunked-prefill body
  ``(params, state, cache, tokens, kmask, rows, start, last_idx) ->
  (probs_last, cache)``: fills cache rows with a prompt chunk's K/V and
  returns the last real token's output row. Within-chunk attention
  reuses the autotuned flash kernels when the chunk is inside their
  envelope (flash_attention_lse_masked — the same dispatch discipline
  as training); the cross-chunk half (chunk queries against the
  already-written cache prefix, the chunk's rows taken from each key
  block; no block at all for a first chunk) runs through
  `cache_attention`, and the two merge by the standard two-way LSE
  combine. `start` is per-row, so
  a long prompt prefills in several bucket-shaped calls — the serving
  engine interleaves decode steps between them.
* ``make_verify_fn(net)`` — the SPECULATIVE verification body
  ``(params, state, cache, tokens, pos) -> (probs, cache)``: K tokens
  per row at positions ``pos..pos+K-1`` in ONE fixed-shape step. All K
  keys are written before attending and each query row i gets
  ``key_limit = pos+i+1``, which is exactly causal including self — so
  row i's output is bit-identical to what i sequential decode steps
  would produce given the same inputs. Acceptance is therefore a pure
  host-side mask over the K output rows (serving/speculative.py); a
  rejected draft's stale K/V is invisible (key_limit) until the next
  verify window — which always starts at or before the stale region —
  overwrites it.
* ``init_cache(net, batch, capacity)`` — zeroed per-attention-layer
  pytree, built from each attention layer's OWN spec
  (``impl.cache_arrays``; ``cache_specs(net, ...)`` lists them):
  ``{layer: {"k": [B, S, H, D], "v": ...}}`` for `SelfAttentionLayer`
  (key position on axis 1 so per-position scatter writes are
  contiguous), ``{layer: {"ckv": [B, S, kv_rank], "kpe": [B, S,
  rope]}}`` for `LatentAttentionLayer`. The serving allocator bills
  the same spec.

A layer that owns its cache entry AND its cached forward
(``impl.apply_cached(conf, params, x, entry, step)``; `CacheStep` says
which rows and positions the call holds) is called by the walk, not
re-implemented here: its mathematics lives once, in nn/layers/. A layer
that counts its own work (``impl.apply_counted``: the dropless expert
layer) hands its counters to the step, which then returns a third value,
an int32 vector in the order of the fn's ``counters`` attribute (empty,
and two values returned, for a net without such a layer). The decode and
verify fns take one more, optional argument, ``live`` [B] bool: the rows
that hold a request, by the caller's word (the serving engine pads its
batch with idle rows); the others attend no key (key_limit 0, so an
idle row's scratch position never lengthens the walk over the cache)
and a counting layer computes and counts nothing for them. Without it
every row is real.

All three entry fns (and ``init_cache``) take ``kv_dtype`` ("f32" |
"int8") and ``page_size``: the int8 paged cache stores codes plus
per-(row, page, head) f32 scales (``{"k", "k_scale", "v", "v_scale"}``
entries), writes through ops/decode_attention.quantized_cache_update,
and attends through `cache_attention_q8` (dequantize as a block loads) —
~4x less HBM per slot, gated on greedy-sequence parity vs the f32
cache in the serving replay.

Both fns are pure (no net mutation, no rng) so an external jit owner —
the serving engine — controls the compile cache, exactly like
`inference_fn`. Supported graphs: single-input/single-output stacks of
time-pointwise layers (dense / embedding / layernorm / output heads /
activation / dropout) plus causal SelfAttention and PositionalEncoding;
elementwise/merge/scale/subset vertices ride along. Anything that mixes
time any other way (LSTMs, convolutions over time, bidirectional
attention) raises at build time with the offending layer named.

Equivalence contract (tier-1, tests/test_generation.py): greedy decode
through prefill + K incremental steps matches argmax over K
full-sequence forwards at atol 1e-5.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.layers import (
    ActivationLayer,
    BaseOutputLayer,
    DenseLayer,
    DropoutLayer,
    EmbeddingLayer,
    GatedDenseLayer,
    LayerNormalization,
    PositionalEncodingLayer,
    SelfAttentionLayer,
)
from deeplearning4j_tpu.nn.training import tree_cast
from deeplearning4j_tpu.ops import autotune
from deeplearning4j_tpu.ops.activations import get_activation
from deeplearning4j_tpu.ops.decode_attention import (
    cache_attention,
    cache_attention_q8,
    quantized_cache_update,
)

_POINTWISE = (DenseLayer, EmbeddingLayer, LayerNormalization,
              BaseOutputLayer, ActivationLayer, DropoutLayer,
              GatedDenseLayer)

_NEG_INF = -1e30


# ------------------------------------------------------------- model plan

class _Op:
    """One traversal step: a layer or a non-layer vertex."""

    __slots__ = ("kind", "name", "conf", "impl", "preproc", "inputs")

    def __init__(self, kind, name, conf, impl, preproc, inputs):
        self.kind = kind
        self.name = name
        self.conf = conf
        self.impl = impl
        self.preproc = preproc
        self.inputs = inputs


def _plan(net):
    """-> (input_name, output_name, [ _Op ]) for either container,
    validating every layer/vertex is incrementally decodable."""
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    problems, ops = [], []
    if isinstance(net, ComputationGraph):
        from deeplearning4j_tpu.nn.conf.graph_conf import (
            ElementWiseVertexConf,
            LayerVertexConf,
            MergeVertexConf,
            ScaleVertexConf,
            SubsetVertexConf,
        )

        ins, outs = net.conf.network_inputs, net.conf.network_outputs
        if len(ins) != 1 or len(outs) != 1:
            raise ValueError(
                "incremental decode needs a single-input/single-output "
                f"graph; this one has inputs {list(ins)} and outputs "
                f"{list(outs)}")
        for name in net.topo:
            if name in ins:
                continue
            vconf = net.conf.vertices[name]
            inputs = list(net.conf.vertex_inputs[name])
            if isinstance(vconf, LayerVertexConf):
                lc = vconf.layer
                if not _decodable_layer(lc, net.impls[name]):
                    problems.append(f"{name} ({type(lc).__name__})")
                ops.append(_Op("layer", name, lc, net.impls[name],
                               vconf.preprocessor, inputs))
            elif isinstance(vconf, (ElementWiseVertexConf, MergeVertexConf,
                                    ScaleVertexConf, SubsetVertexConf)):
                ops.append(_Op("vertex", name, vconf, None, None, inputs))
            else:
                problems.append(f"{name} ({type(vconf).__name__})")
        in_name, out_name = ins[0], outs[0]
    else:
        prev = "__input__"
        for i, (name, lc, impl) in enumerate(zip(
                net.layer_names, net.layer_confs, net.impls)):
            if not _decodable_layer(lc, impl):
                problems.append(f"{name} ({type(lc).__name__})")
            ops.append(_Op("layer", name, lc, impl,
                           net.conf.get_preprocessor(i), [prev]))
            prev = name
        in_name, out_name = "__input__", prev
    if problems:
        raise ValueError(
            "incremental decode supports transformer stacks (pointwise "
            "layers + causal SelfAttention + PositionalEncoding); these "
            "cannot stream one token at a time: " + ", ".join(problems))
    return in_name, out_name, ops


def _owns_cache(impl) -> bool:
    """The layer carries its cached forward itself (`apply_cached`)."""
    return hasattr(impl, "apply_cached")


def _decodable_layer(lc, impl) -> bool:
    if isinstance(lc, SelfAttentionLayer) or _owns_cache(impl):
        return bool(lc.causal)  # non-causal attention reads the future
    if isinstance(lc, PositionalEncodingLayer):
        return True
    return isinstance(lc, _POINTWISE) or hasattr(impl, "apply_counted")


def _counting_impl(ops):
    """The impl of the plan's counting layers (`apply_counted`, with its
    `counters` names and `merge_counts`: the expert layer's), or None."""
    for op in ops:
        if op.kind == "layer" and hasattr(op.impl, "apply_counted"):
            return op.impl
    return None


def _mark_counters(fn, ops):
    """`fn.counters`: the names of the int32 vector the step returns as
    its third value, () where the plan has no counting layer."""
    fn.counting = _counting_impl(ops)
    fn.counters = tuple(fn.counting.counters) if fn.counting else ()
    return fn


def cache_specs(net, capacity: int, kv_dtype: str = "f32",
                page_size: int = 16) -> dict:
    """{layer: {array: (shape of one slot, dtype name)}} for every
    attention layer, each as the layer's own `cache_arrays` gives it:
    what `init_cache` allocates a batch of and what the serving
    allocator bills (serving/kvcache.bytes_per_slot)."""
    if kv_dtype == "int8" and capacity % page_size != 0:
        raise ValueError(
            f"int8 cache needs page-quantized capacity; {capacity} "
            f"is not a multiple of page_size {page_size}")
    _, _, ops = _plan(net)
    return {op.name: {
        arr: (tuple(shape), jnp.dtype(dt).name)
        for arr, (shape, dt) in op.impl.cache_arrays(
            op.conf, capacity, kv_dtype, page_size,
            net.compute_dtype).items()}
        for op in ops
        if op.kind == "layer" and hasattr(op.impl, "cache_arrays")}


def init_cache(net, batch: int, capacity: int, kv_dtype: str = "f32",
               page_size: int = 16):
    """Zeroed cache, {layer: {array: [batch, ...]}} by `cache_specs`:
    keys and values {"k": [batch, capacity, H, D], "v": ...} in the
    net's compute dtype for `SelfAttentionLayer` (kv_dtype="int8": int8
    codes plus per-(row, page, head) f32 scales, {"k", "k_scale", "v",
    "v_scale"}; capacity must sit on the page grid), one latent row
    {"ckv": [batch, capacity, kv_rank], "kpe": [batch, capacity, rope]}
    for `LatentAttentionLayer`. `capacity` is the per-row key budget
    (prompt + generated, page-quantized by the serving layer)."""
    return {name: {arr: jnp.zeros((batch,) + shape, dt)
                   for arr, (shape, dt) in arrays.items()}
            for name, arrays in cache_specs(net, capacity, kv_dtype,
                                            page_size).items()}


def walk_block(net, capacity: int, kv_dtype: str = "f32",
               page_size: int = 16) -> int | None:
    """The key-block length in which the net's cached attention walks a
    cache of `capacity` positions (ops/decode_attention.py resolves it
    from the capacity and the head size alone, so the host can know how
    many blocks a step visits without asking the program): that of the
    net's first `SelfAttentionLayer`. None where no layer walks the
    cache in blocks (a layer that owns its cached forward reads its
    entry its own way)."""
    _, _, ops = _plan(net)
    for op in ops:
        if op.kind == "layer" and isinstance(op.conf, SelfAttentionLayer):
            D = op.conf.n_out // op.conf.n_heads
            if kv_dtype == "int8":
                return autotune.decode_block_q8(capacity, D, page_size)
            return autotune.decode_block(capacity, D)
    return None


class CacheStep:
    """What a layer that owns its cache entry is told about the serving
    step it is called in: `rows` [b] the cache rows the call's batch
    rows are (None: all of them, in order), `positions` [b, T] the
    position each token occupies, `keep` [b, T] 1 for real tokens (None:
    all; the pad of a prefill bucket writes zero rows), `chunk` True for
    a prefill chunk (many queries a row), False for a decode or verify
    step."""

    __slots__ = ("rows", "positions", "keep", "chunk")

    def __init__(self, rows, positions, keep=None, chunk=False):
        self.rows, self.positions = rows, positions
        self.keep, self.chunk = keep, chunk


def _cache_write(entry, k_new, v_new, rows, positions, kv_dtype,
                 page_size):
    """Write k_new/v_new [b, T, H, D] at (rows x positions [b, T]) —
    the dtype-dispatched cache scatter. Out-of-range positions (the
    engine's inactive-row scratch / a speculative tail past capacity)
    are dropped on both paths: the f32 scatter by jax's out-of-bounds
    default, the int8 path inside quantized_cache_update."""
    if kv_dtype == "int8":
        ck, ks = quantized_cache_update(entry["k"], entry["k_scale"],
                                        k_new, rows, positions, page_size)
        cv, vs = quantized_cache_update(entry["v"], entry["v_scale"],
                                        v_new, rows, positions, page_size)
        return {"k": ck, "k_scale": ks, "v": cv, "v_scale": vs}
    ck = entry["k"].at[rows[:, None], positions].set(
        k_new.astype(entry["k"].dtype))
    cv = entry["v"].at[rows[:, None], positions].set(
        v_new.astype(entry["v"].dtype))
    return {"k": ck, "v": cv}


def _cache_attend(entry, qh, key_limit, kv_dtype, page_size, rows=None):
    """Attend qh [b, H, Tq, D] against a cache entry with per-query
    visible-key bounds — dtype-dispatched. `rows` [b] names the cache
    rows the queries attend (the prefill cross-chunk path); they are
    taken from each key block as the walk loads it, never gathered from
    the whole cache."""
    if kv_dtype == "int8":
        return cache_attention_q8(qh, entry["k"], entry["v"],
                                  entry["k_scale"], entry["v_scale"],
                                  key_limit, page_size, rows)
    return cache_attention(qh, entry["k"], entry["v"], key_limit, rows)


def _live_limit(live, key_limit):
    """`key_limit` [B, T] with the rows `live` [B] does not mark set to
    0: an idle row (fed the scratch position, whose limit would be the
    whole capacity) sees no key, so it never lengthens the walk over the
    cache's blocks. `live` None: every row is real."""
    if live is None:
        return key_limit
    return jnp.where(jnp.asarray(live, bool)[:, None], key_limit, 0)


# ------------------------------------------------------------ shared math

def _sinusoidal_at(positions, d, dtype):
    """Sinusoidal encodings at explicit positions [...] -> [..., d] —
    the per-position twin of PositionalEncodingImpl._sinusoidal (same
    f32 math, cast at the end, so decode matches the full forward)."""
    pos = positions.astype(jnp.float32)[..., None]
    dim = jnp.arange(0, d, 2).astype(jnp.float32)
    angle = pos / jnp.power(10000.0, dim / d)
    pe = jnp.zeros(positions.shape + (d,), jnp.float32)
    pe = pe.at[..., 0::2].set(jnp.sin(angle))
    pe = pe.at[..., 1::2].set(jnp.cos(angle[..., : d // 2]))
    return pe.astype(dtype)


def _dense_lse(qh, kh, vh, kmask):
    """Within-chunk causal attention with (out, lse) — the fallback for
    chunk shapes outside the flash envelope (tiny serving buckets, CPU
    tier-1). qh/kh/vh [b, H, T, D]; kmask [b, T]. f32 softmax like
    every other attention path."""
    D, T = qh.shape[-1], qh.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                   preferred_element_type=jnp.float32) / jnp.sqrt(
                       jnp.float32(D))
    cm = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(cm, s, _NEG_INF)
    s = jnp.where(kmask[:, None, None, :].astype(bool), s, _NEG_INF)
    m = s.max(-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vh.astype(jnp.float32))
    o = o / jnp.maximum(l, 1e-30)[..., None]
    return o.astype(qh.dtype), m + jnp.log(jnp.maximum(l, 1e-30))


def _chunk_self_lse(qh, kh, vh, kmask):
    """Within-chunk causal attention (out, lse), through the autotuned
    flash kernels when the chunk is inside their envelope — the prefill
    half of the "reuse the flash kernels" contract."""
    from deeplearning4j_tpu.ops import flash_attention as fa

    b, H, T, D = qh.shape
    if fa.supports(qh.shape, causal=True, dropout=0.0, mask=kmask):
        # flat [b*H, T, D] layout is b-major, so the key mask repeats
        # per head within each batch row
        km = jnp.repeat(jnp.asarray(kmask, jnp.float32), H,
                        axis=0)[:, None, :]
        o, lse = fa.flash_attention_lse_masked(
            qh.reshape(b * H, T, D), kh.reshape(b * H, T, D),
            vh.reshape(b * H, T, D), km, 1.0 / float(D) ** 0.5, True)
        return (o.reshape(b, H, T, D),
                lse.reshape(b, H, T).astype(jnp.float32))
    return _dense_lse(qh, kh, vh, kmask)


def _merge_lse(o1, lse1, o2, lse2):
    """Two-way blockwise softmax merge (the ring/chunk-loop combine):
    each part carries its own lse; fully-masked parts (lse at the mask
    floor) weigh to zero."""
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    denom = jnp.maximum(w1 + w2, 1e-30)[..., None]
    o = (o1.astype(jnp.float32) * w1[..., None]
         + o2.astype(jnp.float32) * w2[..., None]) / denom
    return o.astype(o1.dtype)


# -------------------------------------------------------------- the walk

def _walk(net, ops, in_name, out_name, params, state, x0, attn, posenc,
          cache=None, step=None, valid=None, counts=None):
    """Topo traversal with inference semantics (train=False, no rng),
    attention/posenc routed to the supplied handlers. A layer that owns
    its cache is called with its entry of `cache` (updated in place in
    that dict) and `step`; a counting layer is told which tokens are
    real (`valid`) and appends its counters to `counts`. Mirrors the
    containers' _forward dtype policy: float inputs and per-layer params
    cast to the compute dtype where the two differ."""
    cdtype = net.compute_dtype
    pdtype = net.param_dtype
    x0 = jnp.asarray(x0)
    if jnp.issubdtype(x0.dtype, jnp.floating):
        x0 = x0.astype(cdtype)
    acts = {in_name: x0}
    for op in ops:
        inputs = [acts[i] for i in op.inputs]
        if op.kind == "layer":
            x = inputs[0]
            if op.preproc is not None:
                x = op.preproc.pre_process(x)
            p = params.get(op.name, {})
            if cdtype != pdtype:
                p = tree_cast(p, cdtype)
            if isinstance(op.conf, SelfAttentionLayer):
                y = attn(op.name, op.conf, p, x)
            elif isinstance(op.conf, PositionalEncodingLayer):
                y = posenc(op.name, op.conf, p, x)
            elif _owns_cache(op.impl):
                y, cache[op.name] = op.impl.apply_cached(
                    op.conf, p, _as_seq(x), cache[op.name], step)
                if x.ndim == 2:     # a one-token walk that arrived 2-D
                    y = y[:, 0, :]  # stays so (see `_as_seq`)
            elif hasattr(op.impl, "apply_counted"):
                y, c = op.impl.apply_counted(op.conf, p, x, valid)
                counts.append(c)
            else:
                y, _ = op.impl.apply(op.conf, p, state.get(op.name, {}),
                                     x, train=False, rng=None)
            acts[op.name] = y
        else:
            acts[op.name] = _vertex(op.conf, inputs)
    return acts[out_name]


def _vertex(vconf, inputs):
    from deeplearning4j_tpu.nn.conf.graph_conf import (
        ElementWiseVertexConf,
        MergeVertexConf,
        ScaleVertexConf,
        SubsetVertexConf,
    )

    if isinstance(vconf, MergeVertexConf):
        return jnp.concatenate(inputs, axis=-1)
    if isinstance(vconf, ScaleVertexConf):
        return inputs[0] * vconf.scale
    if isinstance(vconf, SubsetVertexConf):
        return inputs[0][..., vconf.from_idx:vconf.to_idx + 1]
    if isinstance(vconf, ElementWiseVertexConf):
        op = vconf.op
        out = inputs[0]
        for x in inputs[1:]:
            if op == "add":
                out = out + x
            elif op == "subtract":
                out = out - x
            elif op == "product":
                out = out * x
            elif op == "max":
                out = jnp.maximum(out, x)
            elif op == "average":
                out = out + x
            else:
                raise ValueError(f"elementwise op {op}")
        if op == "average":
            out = out / len(inputs)
        return out
    raise ValueError(f"unhandled vertex {type(vconf).__name__}")


def _live_tokens(live, positions):
    """[B, T] True for the tokens of the rows `live` [B] marks (the
    caller's word on which rows of the batch hold a request); None, and
    every token real, where the caller gave none."""
    if live is None:
        return None
    return jnp.broadcast_to(jnp.asarray(live, bool)[:, None],
                            positions.shape)


def _finish(fn, counts, out, cache):
    """A step's return: (out, cache), and the layers' counters merged
    into one int32 vector in the order of `fn.counters` where the plan
    has counting layers."""
    if not fn.counters:
        return out, cache
    total = fn.counting.merge_counts(counts)
    return out, cache, jnp.stack(
        [total[n] for n in fn.counters]).astype(jnp.int32)


def _split_heads(t, H):
    b, T, n = t.shape
    return t.reshape(b, T, H, n // H)


def _as_seq(x):
    """Re-expand [B, d] to [B, 1, d]. EmbeddingImpl squeezes a [B, 1]
    index column to [B] (reference EmbeddingLayer is feed-forward), so a
    single-token walk's activations can arrive 2-D; adding a [B, 1, d]
    positional term to a 2-D [B, d] would BROADCAST to [B, B, d] and
    silently hand every row past 0 row 0's features. Every handler that
    mixes x with per-row position data goes through this first."""
    return x[:, None, :] if x.ndim == 2 else x


# ------------------------------------------------------------ entry fns

def make_decode_fn(net, kv_dtype: str = "f32", page_size: int = 16):
    """-> pure ``step(params, state, cache, token, pos) -> (probs,
    cache)``. token [B] int32; pos [B] int32 is the position the token
    OCCUPIES (0-based — a row whose prompt filled [0, L) decodes its
    first generated token at pos=L). probs [B, V] is the output layer's
    activation row for that token; cache comes back with the token's
    K/V written at (row, pos)."""
    in_name, out_name, ops = _plan(net)

    def step(params, state, cache, token, pos, live=None):
        B = token.shape[0]
        new_cache = dict(cache)
        rows = jnp.arange(B)
        positions = pos[:, None]                           # [B, 1]

        def attn(name, conf, p, x):
            H, n = conf.n_heads, conf.n_out
            x = _as_seq(x)
            qkv = x[:, 0, :] @ p["Wqkv"] + p["bqkv"]       # [B, 3n]
            q, k_new, v_new = jnp.split(qkv, 3, axis=-1)
            Dh = n // H
            entry = _cache_write(
                new_cache[name], k_new.reshape(B, 1, H, Dh),
                v_new.reshape(B, 1, H, Dh), rows, positions,
                kv_dtype, page_size)
            new_cache[name] = entry
            qh = q.reshape(B, H, 1, Dh)
            o, _ = _cache_attend(
                entry, qh, _live_limit(live, (pos + 1)[:, None]),
                kv_dtype, page_size)
            y = o[:, :, 0, :].reshape(B, n) @ p["Wo"] + p["bo"]
            return get_activation(conf.activation or "identity")(
                y)[:, None, :]

        def posenc(name, conf, p, x):
            x = _as_seq(x)
            d = x.shape[-1]
            if conf.learned:
                pe = jnp.take(p["pe"], pos, axis=0)        # [B, d]
            else:
                pe = _sinusoidal_at(pos, d, x.dtype)
            return x + pe[:, None, :]

        counts = []
        probs = _as_seq(_walk(
            net, ops, in_name, out_name, params, state, token[:, None],
            attn, posenc, cache=new_cache, step=CacheStep(None, positions),
            valid=_live_tokens(live, positions), counts=counts))
        return _finish(step, counts, probs[:, 0, :], new_cache)

    return _mark_counters(step, ops)


def make_prefill_fn(net, kv_dtype: str = "f32", page_size: int = 16):
    """-> pure ``prefill(params, state, cache, tokens, kmask, rows,
    start, last_idx) -> (probs_last, cache)``. tokens [b, Tc] int32 (a
    bucket-shaped prompt chunk, zero-padded); kmask [b, Tc] (1 = real
    token); rows [b] — which cache rows this chunk fills; start [b] —
    the global position of the chunk's first token (0 for the first
    chunk; later chunks of a long prompt attend the cache prefix they
    already wrote); last_idx [b] — the LOCAL index of the last real
    token in this chunk (its output row is gathered device-side so only
    [b, V] comes home; pass Tc-1 for non-final chunks and ignore the
    result). Padded positions write ZERO K/V (masked) and are
    overwritten as decode advances."""
    in_name, out_name, ops = _plan(net)

    def prefill(params, state, cache, tokens, kmask, rows, start,
                last_idx):
        b, Tc = tokens.shape
        new_cache = dict(cache)
        local = jnp.arange(Tc)
        positions = start[:, None] + local[None, :]        # [b, Tc]

        def attn(name, conf, p, x):
            H, n = conf.n_heads, conf.n_out
            Dh = n // H
            x = _as_seq(x)
            qkv = x @ p["Wqkv"] + p["bqkv"]                # [b, Tc, 3n]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            keep = kmask[..., None, None]
            entry = _cache_write(
                new_cache[name], _split_heads(k, H) * keep,
                _split_heads(v, H) * keep, rows, positions,
                kv_dtype, page_size)
            new_cache[name] = entry
            qh = _split_heads(q, H).transpose(0, 2, 1, 3)  # [b, H, Tc, Dh]
            kh = _split_heads(k, H).transpose(0, 2, 1, 3)
            vh = _split_heads(v, H).transpose(0, 2, 1, 3)
            o1, lse1 = _chunk_self_lse(qh, kh, vh, kmask)
            # cross-chunk half: queries against the cache prefix this
            # row wrote before `start` (empty on the first chunk — its
            # lse sits at the mask floor and merges to weight zero)
            limit = jnp.broadcast_to(start[:, None], (b, Tc))
            o2, lse2 = _cache_attend(entry, qh, limit, kv_dtype,
                                     page_size, rows=rows)
            o = _merge_lse(o1, lse1, o2, lse2)
            y = o.transpose(0, 2, 1, 3).reshape(b, Tc, n)
            y = y @ p["Wo"] + p["bo"]
            return get_activation(conf.activation or "identity")(y)

        def posenc(name, conf, p, x):
            x = _as_seq(x)
            d = x.shape[-1]
            if conf.learned:
                pe = jnp.take(p["pe"], positions, axis=0)  # [b, Tc, d]
            else:
                pe = _sinusoidal_at(positions, d, x.dtype)
            return x + pe

        counts = []
        probs = _as_seq(_walk(
            net, ops, in_name, out_name, params, state, tokens, attn, posenc,
            cache=new_cache,
            step=CacheStep(rows, positions, keep=kmask, chunk=True),
            valid=kmask > 0 if prefill.counters else None, counts=counts))
        return _finish(prefill, counts,
                       probs[jnp.arange(b), last_idx, :], new_cache)

    return _mark_counters(prefill, ops)


def make_verify_fn(net, kv_dtype: str = "f32", page_size: int = 16):
    """-> pure ``verify(params, state, cache, tokens, pos) -> (probs,
    cache)`` — the speculative-decode verification step. tokens [B, K]
    int32 is each row's candidate window (its true last token followed
    by K-1 draft tokens); pos [B] is the position the FIRST token
    occupies. probs [B, K, V]: row i is the model's next-token output
    after consuming tokens[:, :i+1] — bit-identical to what i+1
    sequential `make_decode_fn` steps would produce, because all K K/Vs
    are written first and query row i attends with key_limit pos+i+1
    (causal including self). The host-side acceptance mask
    (serving/speculative.py) compares argmax rows against the drafts;
    rejected positions' stale K/V stays invisible until the next verify
    window overwrites it."""
    in_name, out_name, ops = _plan(net)

    def verify(params, state, cache, tokens, pos, live=None):
        B, K = tokens.shape
        new_cache = dict(cache)
        rows = jnp.arange(B)
        positions = pos[:, None] + jnp.arange(K)[None, :]  # [B, K]

        def attn(name, conf, p, x):
            H, n = conf.n_heads, conf.n_out
            Dh = n // H
            x = _as_seq(x)
            qkv = x @ p["Wqkv"] + p["bqkv"]                # [B, K, 3n]
            q, k, v = jnp.split(qkv, 3, axis=-1)
            entry = _cache_write(
                new_cache[name], _split_heads(k, H), _split_heads(v, H),
                rows, positions, kv_dtype, page_size)
            new_cache[name] = entry
            qh = _split_heads(q, H).transpose(0, 2, 1, 3)  # [B, H, K, Dh]
            o, _ = _cache_attend(
                entry, qh, _live_limit(live, positions + 1), kv_dtype,
                page_size)
            y = o.transpose(0, 2, 1, 3).reshape(B, K, n)
            y = y @ p["Wo"] + p["bo"]
            return get_activation(conf.activation or "identity")(y)

        def posenc(name, conf, p, x):
            x = _as_seq(x)
            d = x.shape[-1]
            if conf.learned:
                pe = jnp.take(p["pe"], positions, axis=0)  # [B, K, d]
            else:
                pe = _sinusoidal_at(positions, d, x.dtype)
            return x + pe

        counts = []
        probs = _walk(
            net, ops, in_name, out_name, params, state, tokens, attn, posenc,
            cache=new_cache, step=CacheStep(None, positions),
            valid=_live_tokens(live, positions), counts=counts)
        return _finish(verify, counts, probs, new_cache)

    return _mark_counters(verify, ops)
