"""Attention against a paged KV cache — the decode-side op family.

The autoregressive serving path (serving/engine.py GenerationEngine)
threads a per-slot KV cache through a jitted step; its attention reads
are structurally different from training attention:

* `decode_attention` — SINGLE-query attention: one new token's query
  per cache row against everything written so far (`pos` keys). The
  [T, T] score matrix of the training kernels collapses to a [1, S]
  strip, so the cost driver is streaming the cache out of HBM, not the
  MXU — the knob is the key-block length `block_k` the cache is
  streamed in (page multiples), resolved through the ops/autotune.py
  tuning table under the `decode_attn` kernel family.
* `cache_attention` — the general (multi-query) form behind it, also
  the cross-chunk half of chunked prefill (nn/decode.py): chunk queries
  against the already-written cache prefix, returning (out, lse) so the
  caller can LSE-merge with the within-chunk flash result.

Implementation (`_walk_live_blocks`) is one loop over key blocks with
the standard flash running-max/sum merge, an XLA-level kernel whose
block_k is the tuning knob. The loop takes block j out of the cache
WHERE IT LIES (a dynamic slice along the position axis of [B, S, H, D];
the cache is never relaid or copied) and its bound is traced:
ceil(max(key_limit) / block_k) blocks, so one compiled program reads
only the blocks some query of the call can see, whatever the cache's
capacity. A caller that pads its batch with idle rows gives them
key_limit 0 (nn/decode.py does, on the serving engine's word), or the
idle rows' scratch position keeps the bound at the whole capacity. A
row subset (`rows`: the prefill's cross-chunk half) is taken from each
block, not gathered from the cache first. Off-TPU the tuning table is
inactive (autotune.table_active), so interpret/CPU runs always use the
deterministic divisor-search default — bit-identical to the fallback by
construction. Scores and the running state are f32 regardless of cache
dtype.

INT8 QUANTIZED CACHE (r16): the `*_q8` twins read a cache stored as
int8 codes plus one f32 scale per (row, page, head) — per-page
symmetric quantization, scale = maxabs/127, so a page of K (or V)
costs page_size*D bytes instead of page_size*D*4 and HBM streaming
shrinks ~4x (slots per HBM byte is the serving headline this feeds).
Dequantization happens as the walk LOADS a block — a code block
[bk, D] times its page scales, straight into the f32 score dot — so
the quantized path streams codes, never a materialized f32 cache; the
walk itself is the one the bf16/f32 cache uses. The
`decode_attn_q8` tuning family constrains block_k to page multiples
(a block may not split a page's scale broadcast). Cache WRITES go
through `quantized_cache_update`: gather the page-aligned window
covering the new positions, dequantize, insert, zero positions past
the write head (stale values from a previous slot tenancy must not
inflate the fresh page's maxabs), recompute page scales, requantize,
scatter codes + scales back. Re-rounding a page whose scale did not
change is EXACT (round(code*s/s) == code), so settled pages do not
drift as their neighbors fill in.

GROUPED HEADS, A WINDOW, A RING (nn/layers/grouped_attention.py): the
last section of this file, its own walk and kernel, which the row-major
walk above knows nothing of. An entry there lies HEAD-MAJOR,
[B, H, S, D], a key-value head's rows together, and is read as a RING of
its S rows: position p lies at row p % S and the sequence has written
the positions below `head`, so row r holds p_r = (head - 1) - ((head - 1
- r) mod S): arithmetic on the step's own position, never stored; a row
whose p_r is negative was never written by this sequence and is seen by
no query (an entry of `capacity` rows that never wraps is the same
arithmetic). Grouped heads need no walk of their own: the G queries
that read one key-value head are G more query rows of that head
(`group_queries`).

`ring_attention` is that walk in `jnp`, with a lower limit beside the
upper one (a window): a prefill chunk's cross-chunk half, and the CPU's
decode step. `gqa_decode` is the decode step, one new token a cache row:
ONE PALLAS KERNEL on a TPU, named `gqa_decode` in the device trace. A
program instance is a slot and a block of `block_k` rows of all its
key-value heads; each head's G queries meet that head's rows where they
lie, flash running max and sum in float32. Each slot stops at ITS OWN
last live block: the block counts are prefetched scalars, the index map
of a block past a slot's last repeats the last (no copy is issued for
it) and the body is skipped; a slot that is not live visits none. Its
`jnp` twin (`ring_attention` at one query a row) serves the CPU and the
tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import autotune
from deeplearning4j_tpu.util.compat import on_tpu as _use_kernel
from deeplearning4j_tpu.util.compat import tpu_compiler_params

_NEG_INF = -1e30


def _take_block(x, j, length, rows):
    """Block `j` of `length` positions out of x [B, S, ...] where it
    lies (a slice along axis 1, no copy of x), then the row subset
    `rows` [b] of that block (None: every row, in order)."""
    blk = jax.lax.dynamic_slice_in_dim(x, j * length, length, axis=1)
    return blk if rows is None else jnp.take(blk, rows, axis=0)


def _walk_live_blocks(q, load_block, key_limit, S, block_k):
    """The one walk over key blocks, shared by the bf16/f32 cache and
    its int8 twin. q [b, H, Tq, D]; `load_block(j)` hands back block j's
    keys and values as float32 [b, block_k, H, D], the cache's own
    layout (the body names a block's axes head-major for its two
    products; that is a relayout of one block at the most, which XLA
    folds into the products, never of the cache); key_limit [b, Tq] —
    key j is visible to query (r, t) iff j < key_limit[r, t].

    The loop's bound is traced: ceil(max(key_limit) / block_k) blocks,
    at most S // block_k, so ONE program serves every fill of the cache
    and a block no query of the call can see is never read. Such a block
    would add exp(-1e30 - m) = 0 with alpha = 1 to every query that has
    seen a key, so leaving it out changes no bit of the result. A query
    that sees no key at all (key_limit 0: an idle row, a first prefill
    chunk's cross-chunk half) gets a zero row and an lse at the mask
    floor, which a downstream lse merge weighs away.

    Returns (out [b, H, Tq, D] in q.dtype, lse [b, H, Tq] f32)."""
    b, H, Tq, D = q.shape
    sm_scale = 1.0 / jnp.sqrt(jnp.float32(D))
    qf = q.astype(jnp.float32)
    n_live = jnp.clip((jnp.max(key_limit) + block_k - 1) // block_k,
                      0, S // block_k)

    m0 = jnp.full((b, H, Tq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, H, Tq), jnp.float32)
    acc0 = jnp.zeros((b, H, Tq, D), jnp.float32)

    def body(j, carry):
        m, l, acc = carry
        k_j, v_j = load_block(j)
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_j.transpose(0, 2, 1, 3),
                       preferred_element_type=jnp.float32) * sm_scale
        idx = j * block_k + jnp.arange(block_k)
        visible = idx[None, None, None, :] < key_limit[:, None, :, None]
        s = jnp.where(visible, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        # the select matters only while a query has seen no key yet
        # (m_new still at the floor, where exp(s - m_new) would be 1)
        p = jnp.where(visible, jnp.exp(s - m_new[..., None]), 0.0)
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_j.transpose(0, 2, 1, 3),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(0, n_live, body, (m0, l0, acc0))
    out = jnp.where(l[..., None] > 0.0, acc / jnp.maximum(l, 1e-30)[..., None],
                    0.0)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return out.astype(q.dtype), lse


@functools.partial(jax.jit, static_argnames=("block_k",))
def _cache_attention_blocked(q, k, v, key_limit, block_k, rows=None):
    """q [b, H, Tq, D]; k, v [B, S, H, D] (cache layout: key position is
    the second axis so per-position scatter writes are contiguous);
    key_limit [b, Tq]; rows [b] the cache rows the queries attend (None:
    b == B, row for row). The walk is `_walk_live_blocks`: each block is
    sliced out of k and v where they lie, and only of `rows`."""
    def load_block(j):
        return (_take_block(k, j, block_k, rows).astype(jnp.float32),
                _take_block(v, j, block_k, rows).astype(jnp.float32))

    return _walk_live_blocks(q, load_block, key_limit, k.shape[1], block_k)


def cache_attention(q, k, v, key_limit, rows=None):
    """Multi-query attention over a KV cache with a per-query visible-key
    bound. Shapes as `_cache_attention_blocked`; block_k resolves through
    the `decode_attn` tuning-table family (off-TPU: the deterministic
    divisor-search default — bit-identical fallback)."""
    S, D = k.shape[1], k.shape[3]
    bk = autotune.decode_block(S, D)
    return _cache_attention_blocked(q, k, v, key_limit, bk, rows)


def decode_attention(q, k, v, pos):
    """Single-query decode attention: q [B, H, D] is the new token's
    query at position pos [B] per cache row; the token's own K/V must
    already be written at `pos`, so keys j <= pos are visible. Returns
    [B, H, D] in q.dtype."""
    out, _ = cache_attention(q[:, :, None, :], k, v,
                             (pos + 1)[:, None])
    return out[:, :, 0, :]


# ----------------------------------------------------- int8 paged cache

def quantize_pages(x, page_size: int):
    """Per-page symmetric int8 quantization of a cache tensor
    x [B, S, H, D] (S a page multiple). Returns (codes int8 [B, S, H, D],
    scales f32 [B, S//page_size, H]) with scale = maxabs/127 per
    (row, page, head). Round-trip error is bounded by scale/2 per
    element — the bound tests/test_speculative.py proves."""
    B, S, H, D = x.shape
    n_pages = S // page_size
    xp = x.astype(jnp.float32).reshape(B, n_pages, page_size, H, D)
    amax = jnp.max(jnp.abs(xp), axis=(2, 4))
    scales = jnp.maximum(amax, 1e-8) / 127.0
    codes = jnp.clip(jnp.round(xp / scales[:, :, None, :, None]),
                     -127, 127).astype(jnp.int8)
    return codes.reshape(B, S, H, D), scales


def dequantize_pages(codes, scales, page_size: int):
    """Inverse of `quantize_pages` (up to the rounding error):
    codes int8 [B, S, H, D] * per-page scales [B, S//ps, H] -> f32."""
    B, S, H, D = codes.shape
    n_pages = S // page_size
    cp = codes.astype(jnp.float32).reshape(B, n_pages, page_size, H, D)
    return (cp * scales[:, :, None, :, None]).reshape(B, S, H, D)


def quantized_cache_update(codes, scales, new_vals, rows, positions,
                           page_size: int):
    """Write new K (or V) values into an int8 paged cache.

    codes [B, S, H, D] int8, scales [B, S//ps, H] f32; new_vals
    [b, T, H, D]; rows [b] (distinct cache rows); positions [b, T]
    (contiguous per row — a prefill chunk or a verify window).
    Out-of-range positions (the engine's inactive-row scratch, or a
    speculative tail past capacity) are DROPPED, matching the f32
    cache's reliance on jax scatter's drop-out-of-bounds default.

    The page containing a new position must be requantized (its maxabs
    may change), so the update works on the page-aligned window that
    covers the write: gather -> dequantize -> insert -> zero past the
    write head (stale values from a prior tenancy of the row must not
    set the fresh scale) -> new per-page scales -> requantize ->
    scatter. Returns (codes, scales)."""
    B, S, H, D = codes.shape
    b, T = positions.shape
    ps = page_size
    W = min(((T + ps - 1) // ps + 1) * ps, S)
    nw = W // ps
    pos_min = jnp.min(positions, axis=1)
    w0 = jnp.clip(pos_min // ps * ps, 0, S - W)
    widx = w0[:, None] + jnp.arange(W)                     # [b, W]
    p0 = w0 // ps
    pidx = p0[:, None] + jnp.arange(nw)                    # [b, nw]
    wcodes = codes[rows[:, None], widx]                    # [b, W, H, D]
    wscales = scales[rows[:, None], pidx]                  # [b, nw, H]
    wvals = (wcodes.astype(jnp.float32)
             * jnp.repeat(wscales, ps, axis=1)[:, :, :, None])
    local = positions - w0[:, None]
    valid = (positions < S) & (local >= 0) & (local < W)
    # invalid entries scatter to index W — out of bounds, dropped
    local_s = jnp.where(valid, local, W)
    wvals = wvals.at[jnp.arange(b)[:, None], local_s].set(
        new_vals.astype(jnp.float32))
    # zero everything past this row's write head: those positions are
    # invisible until overwritten (key_limit), and stale garbage there
    # would otherwise inflate the page maxabs and crush fresh precision
    pos_max = jnp.max(jnp.where(valid, positions, -1), axis=1)
    wvals = jnp.where((widx > pos_max[:, None])[:, :, None, None],
                      0.0, wvals)
    wq = wvals.reshape(b, nw, ps, H, D)
    amax = jnp.max(jnp.abs(wq), axis=(2, 4))
    new_scales = jnp.maximum(amax, 1e-8) / 127.0
    qcodes = jnp.clip(jnp.round(wq / new_scales[:, :, None, :, None]),
                      -127, 127).astype(jnp.int8).reshape(b, W, H, D)
    codes = codes.at[rows[:, None], widx].set(qcodes)
    scales = scales.at[rows[:, None], pidx].set(new_scales)
    return codes, scales


@functools.partial(jax.jit, static_argnames=("block_k", "page_size"))
def _cache_attention_blocked_q8(q, k_codes, v_codes, k_scale, v_scale,
                                key_limit, block_k, page_size, rows=None):
    """The int8 twin of `_cache_attention_blocked`: the same walk
    (`_walk_live_blocks`), but each key block arrives as int8 codes and
    is dequantized as it is loaded (code * per-page scale, f32) right
    before the score dot. block_k is a page multiple so the [b, ppb, H]
    scale slice broadcasts across whole pages."""
    ppb = block_k // page_size

    def load_block(j):
        def dequantized(codes, scale):
            # [b, ppb, H] -> [b, bk, H, 1]: one scale per page, per head
            s = jnp.repeat(_take_block(scale, j, ppb, rows), page_size,
                           axis=1)
            return (_take_block(codes, j, block_k, rows).astype(jnp.float32)
                    * s[..., None])

        return dequantized(k_codes, k_scale), dequantized(v_codes, v_scale)

    return _walk_live_blocks(q, load_block, key_limit, k_codes.shape[1],
                             block_k)


def cache_attention_q8(q, k_codes, v_codes, k_scale, v_scale, key_limit,
                       page_size: int, rows=None):
    """Multi-query attention over an int8 paged KV cache. Shapes as
    `_cache_attention_blocked_q8`; block_k resolves through the
    `decode_attn_q8` tuning family (page-multiple candidates; off-TPU
    the deterministic page-multiple divisor default)."""
    S, D = k_codes.shape[1], k_codes.shape[3]
    bk = autotune.decode_block_q8(S, D, page_size)
    return _cache_attention_blocked_q8(q, k_codes, v_codes, k_scale,
                                       v_scale, key_limit, bk, page_size,
                                       rows)


# ------------------------------------------------- grouped decode kernel

GQA_BLOCK_K = autotune.DEFAULT_GQA_BLOCK_K


def group_queries(q, n_kv_heads: int):
    """q [b, Hq, Tq, D] -> [b, Hk, G * Tq, D]: the G = Hq / Hk queries
    that read key-value head c (query heads c * G .. c * G + G - 1) as G
    more query rows of that head, row g * Tq + t. Per-query limits go
    with them as jnp.tile(limit, (1, G)); `ungroup_queries` is the way
    back."""
    b, Hq, Tq, D = q.shape
    return q.reshape(b, n_kv_heads, (Hq // n_kv_heads) * Tq, D)


def ungroup_queries(o, n_heads: int):
    """[b, Hk, G * Tq, ...] -> [b, Hq, Tq, ...]."""
    b, Hk, GT = o.shape[:3]
    return o.reshape((b, n_heads, GT // (n_heads // Hk)) + o.shape[3:])


def gqa_block(R: int, block_k: int = GQA_BLOCK_K) -> int:
    """The kernel's block of an entry of R rows: the largest divisor of
    R that is at most `block_k` and a multiple of 16 (a bfloat16 tile's
    sublanes), else R whole."""
    for bk in range(min(block_k, R) // 16 * 16, 0, -16):
        if R % bk == 0:
            return bk
    return R


@functools.partial(jax.jit, static_argnames=("block_k",))
def _ring_attention_blocked(q, k, v, key_limit, head, block_k, rows,
                            key_floor):
    b, H, Tq, D = q.shape
    S = k.shape[2]
    sm_scale = 1.0 / jnp.sqrt(jnp.float32(D))
    newest = head[:, None] - 1
    n_live = (jnp.minimum(jnp.max(head), S) + block_k - 1) // block_k

    def load(x, j):
        blk = jax.lax.dynamic_slice_in_dim(x, j * block_k, block_k, axis=2)
        return blk if rows is None else jnp.take(blk, rows, axis=0)

    def body(j, carry):
        m, l, acc = carry
        # blocks stay in the entry's own type: bfloat16 operands and a
        # float32 sum give the products of the same numbers at a third
        # of the passes of a float32 product
        k_j, v_j = load(k, j), load(v, j)
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(k_j.dtype), k_j,
                       preferred_element_type=jnp.float32) * sm_scale
        row = j * block_k + jnp.arange(block_k)
        p_r = (newest - jnp.mod(newest - row[None, :], S))[:, None, None, :]
        visible = (p_r >= 0) & (p_r < key_limit[:, None, :, None])
        if key_floor is not None:
            visible = visible & (p_r >= key_floor[:, None, :, None])
        s = jnp.where(visible, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(visible, jnp.exp(s - m_new[..., None]), 0.0)
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(v_j.dtype), v_j,
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(
        0, n_live, body, (jnp.full((b, H, Tq), _NEG_INF, jnp.float32),
                          jnp.zeros((b, H, Tq), jnp.float32),
                          jnp.zeros((b, H, Tq, D), jnp.float32)))
    out = jnp.where(l[..., None] > 0.0,
                    acc / jnp.maximum(l, 1e-30)[..., None], 0.0)
    return out.astype(q.dtype), m + jnp.log(jnp.maximum(l, 1e-30))


def ring_attention(q, k, v, key_limit, head, rows=None, key_floor=None):
    """The walk over a head-major entry read as a ring (module
    docstring), in blocks of `gqa_block(S)`, flash running max and sum
    in float32. q [b, H, Tq, D]; k, v [B, H, S, D]; `head` [b]: the
    sequence has written the positions below it; query (r, t) sees the
    positions key_floor[r, t] <= p < key_limit[r, t] ([b, Tq] each; no
    floor: from 0); rows [b] the cache rows the queries attend (None:
    b == B, row for row). The loop's bound is traced: the blocks the
    fullest ring has filled. A query that sees no key gets a zero row and
    an lse at the mask floor. -> (out [b, H, Tq, D] in q.dtype,
    lse [b, H, Tq] float32)."""
    return _ring_attention_blocked(q, k, v, key_limit, head,
                                   gqa_block(k.shape[2]), rows, key_floor)


def pass_rows(x, pass_index):
    """An entry's rows of one pass: x [B, P, H, S, D] at `pass_index`
    -> [B, H, S, D] (a slice: the `jnp` twins' form; the kernels read
    the pass where it lies). x itself where `pass_index` is None."""
    if pass_index is None:
        return x
    return jax.lax.dynamic_index_in_dim(x, pass_index, 1, keepdims=False)


def gqa_decode_jnp(q, k, v, pos, live=None, pass_index=None):
    """`gqa_decode` in plain `jnp`: the ring walk at one query a row."""
    B, Hq, D = q.shape
    k, v = pass_rows(k, pass_index), pass_rows(v, pass_index)
    Hk = k.shape[1]
    limit = pos + 1
    if live is not None:
        limit = jnp.where(jnp.asarray(live, bool), limit, 0)
    o, _ = ring_attention(
        group_queries(q[:, :, None, :], Hk), k, v,
        jnp.tile(limit[:, None], (1, Hq // Hk)), limit)
    return ungroup_queries(o, Hq)[:, :, 0, :]


def _gqa_kernel(nblk_ref, at_ref, wrapped_ref, q_ref, k_ref, v_ref, o_ref,
                m_ref, l_ref, acc_ref, *, block_k, scale):
    """One (slot, block of rows): q_ref [1, Hk, G8, D] holds each
    key-value head's queries (G padded to a sublane tile), k_ref and
    v_ref [1, Hk, block_k, D] that block of every head's rows."""
    f32 = jnp.float32
    b, j = pl.program_id(0), pl.program_id(1)
    Hk = q_ref.shape[1]

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    @pl.when(j < nblk_ref[b])
    def _():
        # ring row r holds the newest position p <= pos with p % R == r:
        # it has been written iff r <= pos % R or the ring has wrapped
        row = j * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                     (1, block_k), 1)
        seen = (row <= at_ref[b]) | (wrapped_ref[b] > 0)
        for h in range(Hk):
            s = jax.lax.dot_general(
                q_ref[0, h], k_ref[0, h], (((1,), (1,)), ((), ())),
                preferred_element_type=f32) * scale          # [G8, block_k]
            s = jnp.where(seen, s, _NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
            l_ref[h] = alpha * l_ref[h] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, h],
                (((1,), (0,)), ((), ())), preferred_element_type=f32)
            m_ref[h] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        l = l_ref[...]
        o_ref[0] = jnp.where(l > 0.0, acc_ref[...] / jnp.maximum(l, 1e-30),
                             0.0).astype(o_ref.dtype)


def gqa_decode_kernel(q, k, v, pos, live=None, pass_index=None, *,
                      interpret=False, block_k=GQA_BLOCK_K):
    """`gqa_decode_jnp` as one Pallas kernel (module docstring). With
    `pass_index`, k and v are [B, P, Hk, R, D] and the pass is one more
    prefetched scalar: the index maps read that pass's block of the
    whole entry where it lies."""
    B, Hq, D = q.shape
    Hk, R = k.shape[-3], k.shape[-2]
    G = Hq // Hk
    G8 = -(-G // 8) * 8
    bk = gqa_block(R, block_k)
    n_blocks = R // bk
    pos = pos.astype(jnp.int32)
    rows = jnp.minimum(pos + 1, R)                # ring rows written
    if live is not None:
        rows = jnp.where(jnp.asarray(live, bool), rows, 0)
    nblk = (rows + bk - 1) // bk
    qg = q.reshape(B, Hk, G, D)
    if G8 != G:
        qg = jnp.concatenate(
            [qg, jnp.zeros((B, Hk, G8 - G, D), q.dtype)], axis=2)

    def whole(b, j, nblk, *refs):
        return b, 0, 0, 0

    def last_live(b, j, nblk):
        # past a slot's last live block the index repeats: no new copy
        return jnp.maximum(jnp.minimum(j, nblk[b] - 1), 0)

    kernel = functools.partial(_gqa_kernel, block_k=bk, scale=1.0 / D ** 0.5)
    scalars = [nblk, pos % R, (pos >= R).astype(jnp.int32)]
    if pass_index is None:
        rows_spec = pl.BlockSpec(
            (1, Hk, bk, D),
            lambda b, j, nblk, at, wrapped: (b, 0, last_live(b, j, nblk), 0))
    else:
        rows_spec = pl.BlockSpec(
            (1, pl.squeezed, Hk, bk, D),
            lambda b, j, nblk, at, wrapped, p: (b, p[0], 0,
                                                last_live(b, j, nblk), 0))
        scalars.append(jnp.reshape(pass_index, (1,)).astype(jnp.int32))
        kernel = without_pass(kernel, 3)

    itemsize = jnp.dtype(k.dtype).itemsize
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(B, n_blocks),
            in_specs=[pl.BlockSpec((1, Hk, G8, D), whole), rows_spec,
                      rows_spec],
            out_specs=pl.BlockSpec((1, Hk, G8, D), whole),
            scratch_shapes=[pltpu.VMEM((Hk, G8, 1), jnp.float32),
                            pltpu.VMEM((Hk, G8, 1), jnp.float32),
                            pltpu.VMEM((Hk, G8, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, Hk, G8, D), q.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=4 * B * Hq * R * D, transcendentals=B * Hq * R,
            bytes_accessed=2 * B * Hk * R * D * itemsize),
        name="gqa_decode",
        interpret=interpret,
    )(*scalars, qg, k, v)
    return out[:, :, :G].reshape(B, Hq, D)


def without_pass(kernel, at: int):
    """`kernel` behind one more prefetched scalar, the pass, which only
    the index maps read: its ref (the `at`-th argument) is dropped."""
    def body(*refs):
        return kernel(*refs[:at], *refs[at + 1:])
    return body


def gqa_decode(q, k, v, pos, live=None, pass_index=None):
    """One new token a cache row: q [B, Hq, D] at position pos [B]
    against the entry k, v [B, Hk, R, D] read as a ring of R rows, the
    token's own row already written at pos % R; query head h reads
    key-value head h // (Hq / Hk). A row not `live` [B] sees nothing and
    gets zeros. With `pass_index` (a scalar: the pass of a loop, the
    walk's `CacheStep`), the entry is [B, P, Hk, R, D] and that pass's
    rows are read. The kernel on a TPU, its `jnp` twin elsewhere. ->
    [B, Hq, D] in q.dtype."""
    if _use_kernel():
        return gqa_decode_kernel(q, k, v, pos, live, pass_index)
    return gqa_decode_jnp(q, k, v, pos, live, pass_index)
