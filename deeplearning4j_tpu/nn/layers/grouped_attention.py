"""Grouped-query softmax attention with an optional sliding window, rotary
position, per-head norms on query and key and an output gate
(`GroupedAttentionLayer`, nn/conf/layers.py): the attention of models
that mix window and full layers in one stack.

For a token u at position t (no projection has a bias; Hq query heads
and Hk key-value heads of d; query head h reads key-value head
h // (Hq / Hk)):

    q = Nq(u Wq)   [Hq, d]        Nq, Nk: RMS norm over the d of a head,
    k = Nk(u Wk)   [Hk, d]        one learned gain vector each (where
    v = u Wv       [Hk, d]        `qk_norm`; else none)
    g = sigmoid(u Wg)   [Hq * d]  (where `gate`; else g = 1)
    q, k = rot(q, t), rot(k, t)   where `rope_theta` > 0; else NO position.
                                  With `rotary_dim` r > 0 only the first r
                                  of a head's d turn, pairs (i, i + r / 2)
    o_t = softmax_j(q_t . k_j / sqrt(d)) v_j   over the keys
          t - window < j <= t  (`window` > 0: `window` keys at most, the
          query's own among them), or every j <= t (`window` 0);
          scores and softmax in float32
    out = (concat_h(o) * g) Wo    the gate multiplies BEFORE the output
                                  projection

`rot` is `latent_attention.rotary` (the pairs (i, i + d / 2)). `apply` (a
whole sequence) is plain `jnp` with the window as a mask: this layer is
served, it has no flash path and no custom backward.

THE CACHE ENTRY is the layer's own and lies head-major, a key-value
head's rows together ([B, Hk, rows, d]: what `ops/decode_attention.
gqa_decode` reads):

* `window` 0: {"k", "v"}, `capacity` rows a slot, position p at row p;
* `window` > 0: {"k_win", "v_win"}, min(capacity, window) rows a slot
  WHATEVER THE CAPACITY, A RING: position p lies at row p % rows, and the
  position a row holds is arithmetic on the step's own position, never
  stored. The spec says how many positions the arrays hold
  (serving/kvcache.py bills a ring to its own rows).

What rows forgive and a ring does not, all read from `nn/decode.
CacheStep`:

* a row the step says is not `live` WRITES NOTHING (the engine feeds an
  idle slot the scratch position capacity - 1; in a ring that lands on a
  row some tenant may need);
* a prefill chunk attends the ring AS IT FOUND IT, masked per query by
  the position each row holds (start - window < p < start for the ring's
  half, plain causal with the window inside the chunk), and writes
  AFTER: its first query sees back to start - window + 1, rows its own
  last tokens overwrite. Of a chunk longer than the ring only the last
  `rows` tokens are written;
* a token with `keep` 0 (the pad of a bucket) writes nothing and is seen
  by nothing;
* a slot's new tenant needs no reset: a ring row whose position by that
  arithmetic is negative or beyond the query is masked;
* a step cannot be unwound from a ring (a rejected draft's rows have
  overwritten the oldest rows, which the accepted position still sees):
  `rewindable(conf)` is False where the entry is a ring, so
  `nn/decode.make_verify_fn` refuses the net with the layer named;
* `kv_dtype="int8"` is refused (a ring of requantised pages is its own
  work).

HOW A CHUNK ATTENDS: the same single softmax over the entry's rows as
found and the chunk's own keys, in one of two forms that tier-1 holds
equal. On a TPU, one Pallas kernel (`ops/prefill_attention.gqa_prefill`,
`gqa_prefill` in the device trace): each key-value head's G queries meet
a block of its rows in VMEM, scores, running max and sum never reach
memory, and only the blocks some query sees are copied, each row at its
own length and window. Elsewhere, and for a shape the kernel does not
take (`gqa_prefill_fits`: a chunk longer than its entry, rows that no
block of 16 divides, a chunk of no whole 128-key lanes), `chunk_walk`:
the chunk's own keys by `masked_attention_lse`, the entry by
`ops/decode_attention.ring_attention` a block at a time, merged by their
log-sum-exps. `apply` and the decode step never take the kernel.

HOW A CHUNK WRITES: its rows are one run of each row's entry (a ring's
with at most one wrap), written as such by `CacheStep.write_run`: two
blended blocks of the chunk's length a row, in place, the same rows and
values as a scatter of the positions. A chunk longer than its entry
(the walk's case; only its last `rows` kept tokens stay) and the decode
step (each row at its own position) write by scatter.

INSIDE A LOOP (a span of the graph run several times a token with one
set of weights, nn/decode.py's walk): the entry has a PASS axis after
the batch axis, [B, P, Hk, rows, d], one block of rows a pass, and the
step's `pass_index` says which. The decode write, a chunk's run and both
kernels (`gqa_decode`, `gqa_prefill`, told the pass as a scalar) read
and write that pass's rows where they lie; the `jnp` forms take the
pass's rows out first.

The layer counts, through the `counters` road of nn/decode.py:
`attn_rows_seen`, the cache rows some query of the step could see,
`attn_wrapped`, the live rows of the step whose context is past the
window, and `attn_write_wraps`, the rows of a chunk whose written run
passed the end of the entry (0 in a decode step).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.layers import GroupedAttentionLayer
from deeplearning4j_tpu.nn.layers.attention import rms_norm
from deeplearning4j_tpu.nn.layers.base import (
    LayerImpl,
    apply_dropout,
    register_impl,
)
from deeplearning4j_tpu.nn.layers.latent_attention import rotary
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.ops import prefill_attention
from deeplearning4j_tpu.ops.activations import get_activation
from deeplearning4j_tpu.ops.decode_attention import (
    gqa_decode,
    group_queries,
    pass_rows,
    ring_attention,
    ungroup_queries,
)
from deeplearning4j_tpu.ops.flash_attention import lse_combine

_NEG_INF = -1e30


def _sizes(conf):
    Hq = conf.n_heads
    Hk = conf.n_kv_heads or Hq
    return Hq, Hk, conf.head_dim or conf.n_out // Hq


def entry_names(conf) -> tuple:
    return ("k_win", "v_win") if conf.window else ("k", "v")


def _project(conf, params, x, positions):
    """x [b, T, n_in] at `positions` [b, T] -> q [b, T, Hq, d], k, v
    [b, T, Hk, d] in x's dtype."""
    b, T, _ = x.shape
    Hq, Hk, d = _sizes(conf)
    q = (x @ params["Wq"]).reshape(b, T, Hq, d)
    k = (x @ params["Wk"]).reshape(b, T, Hk, d)
    v = (x @ params["Wv"]).reshape(b, T, Hk, d)
    if conf.qk_norm:
        q = rms_norm(q, params["q_norm"], conf.eps)
        k = rms_norm(k, params["k_norm"], conf.eps)
    if conf.rope_theta:
        q, k = (_turn(conf, a, positions) for a in (q, k))
    return q, k, v


def _turn(conf, x, positions):
    """Rotary position on the first `rotary_dim` dimensions of each head
    of x [b, T, H, d] (the whole head where that is 0); the rest pass."""
    r = conf.rotary_dim
    if not r or r == x.shape[-1]:
        return rotary(x, positions, conf.rope_theta)
    return jnp.concatenate(
        [rotary(x[..., :r], positions, conf.rope_theta), x[..., r:]], -1)


def _output(conf, params, x, o):
    """o [b, T, Hq * d], the heads' outputs side by side -> [b, T, n_out]."""
    if conf.gate:
        gate = jax.nn.sigmoid((x @ params["Wg"]).astype(jnp.float32))
        o = (o.astype(jnp.float32) * gate).astype(o.dtype)
    return get_activation(conf.activation or "identity")(o @ params["Wo"])


def masked_attention_lse(conf, q, k, v, qpos, kpos, kmask=None):
    """q [b, T, Hq, d] at qpos [b, T] against the call's own k, v
    [b, S, Hk, d] at kpos [b, S]: causal, inside the window, `kmask`
    [b, S] 0 hiding a key. Plain products, float32 softmax. ->
    (o [b, Hk, G * T, d] float32, lse [b, Hk, G * T]) in the grouped
    order of `ops/decode_attention.group_queries`."""
    Hq, Hk, d = _sizes(conf)
    G = Hq // Hk
    qg = group_queries(q.transpose(0, 2, 1, 3), Hk)         # [b, Hk, G*T, d]
    s = jnp.einsum("bhqd,bkhd->bhqk", qg, k,
                   preferred_element_type=jnp.float32) / jnp.sqrt(
                       jnp.float32(d))
    seen = kpos[:, None, :] <= qpos[:, :, None]             # [b, T, S]
    if conf.window:
        seen = seen & (kpos[:, None, :] > qpos[:, :, None] - conf.window)
    if kmask is not None:
        seen = seen & (kmask[:, None, :] > 0)
    seen = jnp.tile(seen, (1, G, 1))[:, None]               # [b, 1, G*T, S]
    s = jnp.where(seen, s, _NEG_INF)
    m = s.max(-1)
    p = jnp.where(seen, jnp.exp(s - m[..., None]), 0.0)
    l = p.sum(-1)
    o = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    o = jnp.where(l[..., None] > 0.0, o / jnp.maximum(l, 1e-30)[..., None],
                  0.0)
    return o, m + jnp.log(jnp.maximum(l, 1e-30))


def chunk_walk(conf, q, k, v, k_entry, v_entry, pos, keep, rows):
    """A prefill chunk's attention in `jnp`: q, k, v [b, T, H, d] of the
    chunk at positions pos [b, T] (running on from pos[:, 0]) against its
    own keys (`keep` [b, T] 0 hiding one) and against the cache rows
    `rows` [b] of the entry k_entry, v_entry [B, Hk, R, d] as the chunk
    found it (`ops/decode_attention.ring_attention`), the two halves
    merged by their log-sum-exps. -> [b, Hk, G * T, d] float32, grouped."""
    _, Hk, _ = _sizes(conf)
    b, T = pos.shape
    G, W = conf.n_heads // Hk, conf.window
    start = pos[:, 0]
    o, lse = masked_attention_lse(conf, q, k, v, pos, pos, keep)
    before = jnp.tile(jnp.broadcast_to(start[:, None], (b, T)), (1, G))
    o, _ = lse_combine(o, lse, *ring_attention(
        group_queries(q.transpose(0, 2, 1, 3), Hk), k_entry, v_entry, before,
        start, rows, jnp.tile(pos - W + 1, (1, G)) if W else None))
    return o


def written_rows(pos, keep, R):
    """The entry row each token of a step writes, [b, T]: position p at
    row p % R where `keep`, of a call longer than R only the last R kept
    tokens; R (dropped) for the rest."""
    last = jnp.max(jnp.where(keep, pos, -1), axis=1, keepdims=True)
    return jnp.where(keep & (pos > last - R), pos % R, R)


def scatter_write(entry, new, rows, at, pass_index=None):
    """`entry` {name: [B, Hk, R, d]} with new {name: [b, T, Hk, d]}
    scattered at cache rows `rows` [b], every head, entry rows `at`
    [b, T] (`written_rows`; R is dropped): the decode step's write, each
    row at its own position, and that of a chunk longer than its
    entry. With `pass_index`, the entry is [B, P, Hk, R, d] and the rows
    land in that pass's."""
    Hk = next(iter(entry.values())).shape[-3]
    idx = (rows[:, None, None], jnp.arange(Hk)[None, :, None], at[:, None, :])
    if pass_index is not None:
        idx = idx[:1] + (pass_index,) + idx[1:]
    return {n: a.at[idx].set(new[n].transpose(0, 2, 1, 3).astype(a.dtype),
                             mode="drop")
            for n, a in entry.items()}


def chunk_write(step, entry, new, rows, keep):
    """A prefill chunk's write of new {name: [b, T, Hk, d]} at the step's
    positions (running on from positions[:, 0]) where `keep`: the same
    rows and values as `scatter_write`, as one run a row
    (`CacheStep.write_run`) where the chunk fits its entry; inside a
    loop, into the step's pass."""
    a = next(iter(entry.values()))
    R, T = a.shape[-2], step.positions.shape[1]
    if T > R:
        return scatter_write(entry, new, rows,
                             written_rows(step.positions, keep, R),
                             step.pass_index)
    return step.write_run(entry, {n: x.transpose(0, 2, 1, 3)
                                  for n, x in new.items()}, keep,
                          axis=a.ndim - 2)


def _heads_out(o, conf, dtype):
    """[b, Hk, G * T, d] -> [b, T, Hq * d]."""
    Hq, _, d = _sizes(conf)
    o = ungroup_queries(o, Hq)                              # [b, Hq, T, d]
    b, _, T, _ = o.shape
    return o.transpose(0, 2, 1, 3).reshape(b, T, Hq * d).astype(dtype)


@register_impl(GroupedAttentionLayer)
class GroupedAttentionImpl(LayerImpl):
    region = "attention"
    counters = ("attn_rows_seen", "attn_wrapped", "attn_write_wraps")
    # served inside a loop: its entry takes a pass axis (module docstring)
    passes = True

    @staticmethod
    def rewindable(conf) -> bool:
        """A ring cannot be unwound (module docstring)."""
        return not conf.window

    @staticmethod
    def merge_counts(counts: list) -> dict:
        """Rows seen add up over the layers; the rows past the window,
        and the rows whose write wrapped, are the same rows in every
        window layer (a full layer says 0)."""
        return {"attn_rows_seen": sum(c["attn_rows_seen"] for c in counts),
                **{n: jnp.max(jnp.stack([c[n] for c in counts]))
                   for n in ("attn_wrapped", "attn_write_wraps")}}

    def init(self, conf, rng, dtype):
        Hq, Hk, d = _sizes(conf)
        if Hq % Hk or d % 2 or conf.rotary_dim % 2 or conf.rotary_dim > d:
            raise ValueError(
                f"GroupedAttentionLayer needs n_heads a multiple of "
                f"n_kv_heads, an even head_dim and an even rotary_dim no "
                f"larger; got {Hq}, {Hk}, {d}, {conf.rotary_dim}")
        k = jax.random.split(rng, 5)

        def w(key, shape):
            return init_weights(key, shape, conf.weight_init, conf.dist, dtype)

        params = {"Wq": w(k[0], (conf.n_in, Hq * d)),
                  "Wk": w(k[1], (conf.n_in, Hk * d)),
                  "Wv": w(k[2], (conf.n_in, Hk * d)),
                  "Wo": w(k[3], (Hq * d, conf.n_out))}
        if conf.gate:
            params["Wg"] = w(k[4], (conf.n_in, Hq * d))
        if conf.qk_norm:
            params["q_norm"] = jnp.ones((d,), dtype)
            params["k_norm"] = jnp.ones((d,), dtype)
        return params, {}

    def apply(self, conf, params, state, x, *, train=False, rng=None,
              mask=None):
        if conf.dropout:
            x = apply_dropout(x, conf.dropout, rng, train=train)
        b, T, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (b, T))
        q, k, v = _project(conf, params, x, positions)
        o, _ = masked_attention_lse(conf, q, k, v, positions, positions, mask)
        return _output(conf, params, x, _heads_out(o, conf, x.dtype)), state

    def cache_arrays(self, conf, capacity, kv_dtype, page_size, dtype):
        """The arrays one decode slot of this layer holds, head-major:
        {name: (shape, dtype)} of `capacity` rows for a full layer;
        {name: (shape, dtype, rows)} for a ring, the third entry the
        positions it holds, min(capacity, window)."""
        if kv_dtype == "int8":
            raise ValueError(
                "no int8 form of this layer's cache entry: a ring of "
                "requantised pages is its own work")
        _, Hk, d = _sizes(conf)
        kn, vn = entry_names(conf)
        if not conf.window:
            return {kn: ((Hk, capacity, d), dtype),
                    vn: ((Hk, capacity, d), dtype)}
        rows = min(capacity, conf.window)
        return {kn: ((Hk, rows, d), dtype, rows),
                vn: ((Hk, rows, d), dtype, rows)}

    def apply_cached(self, conf, params, x, entry, step):
        """One serving step through this layer's cache entry (module
        docstring: what it takes from `step`). -> (y, entry, counts)."""
        b, T, _ = x.shape
        Hq, Hk, d = _sizes(conf)
        G, W = Hq // Hk, conf.window
        kn, vn = entry_names(conf)
        R = entry[kn].shape[-2]
        p = step.pass_index
        pos = step.positions
        q, k, v = _project(conf, params, x, pos)
        rows = jnp.arange(b) if step.rows is None else step.rows
        live = (jnp.ones((b,), bool) if step.live is None
                else jnp.asarray(step.live, bool))
        keep = (jnp.ones((b, T), bool) if step.keep is None
                else step.keep > 0) & live[:, None]
        at = written_rows(pos, keep, R)
        n_kept = jnp.sum(keep, axis=1)
        new = {kn: k, vn: v}
        if step.chunk:
            start = pos[:, 0]
            if prefill_attention.use_kernel() and \
                    prefill_attention.gqa_prefill_fits(T, R):
                o = prefill_attention.gqa_prefill(
                    group_queries(q.transpose(0, 2, 1, 3), Hk), entry[kn],
                    entry[vn], k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3), keep, rows, start, window=W,
                    pass_index=p)
            else:
                o = chunk_walk(conf, q, k, v, pass_rows(entry[kn], p),
                               pass_rows(entry[vn], p), pos, keep, rows)
            with jax.named_scope("cache_write"):
                entry = chunk_write(step, entry, new, rows, keep)
            # the rows whose written run passed the entry's end: a row
            # written below the row of the first position written
            first = jax.lax.rem(jnp.min(jax.lax.select(
                at < R, pos, jnp.full_like(pos, jnp.iinfo(pos.dtype).max)),
                axis=1, keepdims=True), R)
            wraps = jnp.sum(jnp.any(at < first, axis=1))
            prior = jnp.minimum(start, W - 1) if W else start
            seen = jnp.sum(jnp.where(n_kept > 0, prior + n_kept, 0))
            ends = start + n_kept
        elif T == 1:
            with jax.named_scope("cache_write"):
                entry = scatter_write(entry, new, rows, at, p)
            o = gqa_decode(q[:, 0], entry[kn], entry[vn], pos[:, 0], live, p)
            o = o.reshape(b, Hk, G, d)                      # grouped, T = 1
            ends = jnp.where(live, pos[:, 0] + 1, 0)
            seen = jnp.sum(jnp.minimum(ends, R))
            wraps = 0
        else:
            raise ValueError(
                "a GroupedAttentionLayer decodes one token a row a step or "
                "prefills a chunk; a window of drafts is not served")
        counts = {"attn_rows_seen": seen.astype(jnp.int32),
                  "attn_wrapped": jnp.sum(ends > W, dtype=jnp.int32)
                  if W else jnp.int32(0),
                  "attn_write_wraps": jnp.int32(wraps)}
        return (_output(conf, params, x, _heads_out(o, conf, x.dtype)),
                entry, counts)
