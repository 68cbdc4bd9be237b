"""A prefill chunk's attention as ONE PALLAS KERNEL, forward only: two
kernels, each named so in the device trace. `prefill_flash`: latent
attention (`nn/layers/latent_attention.py`), this docstring. `gqa_prefill`:
grouped heads over a ring or a full entry of rows, read as the chunk found
it beside the chunk's own keys (`nn/layers/grouped_attention.py`; the last
section of this file). They share `gqa_block` and `ops/autotune.py` alone.

A latent prefill chunk of T queries of one cache row, at positions start
.. start + T - 1, attends the row's context 0 .. start + T - 1, whose keys
and values are expanded from the latent rows (`ckv`, kv_rank wide) by
`Wkvb_k` and `Wkvb_v`, beside the one rotary key slice (`kpe`) that every
head shares. Key s is visible to query t iff s <= start + t. The `jnp`
form (`_attend_expanded`) walks the context a block of keys at a time, and
XLA writes each block's float32 scores, [H, T, block] (256 MB at 128
heads, 1,024 queries and 512 keys), to memory and reads them back several
times; in `prefill_flash` scores, running max and running sum stay in
VMEM and never reach HBM.

A program instance is (row, group of heads, block of queries, block of
keys). It expands the key block's keys and values for its heads from the
latent rows where they lie in the cache (`ckv` [bk, kv_rank] times the
heads' columns of `Wkvb_k` and `Wkvb_v`, rounded to the cache's dtype as
the `jnp` form rounds them), once for all the block's queries, and scores
them in sub-blocks of rows as two products: `q_nope . k_nope` and
`q_pe . kpe` against the shared rotary slice. A sub-block that sees no
key of the block is skipped, one that sees all of them is not masked.
Heads and sub-blocks are loops (PERF.md section 6, PR 38). The row
(`rows`) and the chunk's first position (`start`) are prefetched scalars:
past the causal frontier of a query block the key block's index repeats
the last visible one (no copy is issued) and its body is skipped.
Precision is the `jnp` form's: float32 scores, running max and sum;
probabilities cast to the value dtype for the weighted sum; float32
accumulation. No `custom_vjp`: training and `output()` keep the `jnp`
forms, decode and verify steps theirs; off the TPU the layers keep the
`jnp` forms too; tier-1 holds each kernel to its form in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import autotune
from deeplearning4j_tpu.ops.decode_attention import gqa_block, without_pass
from deeplearning4j_tpu.util.compat import on_tpu as use_kernel  # noqa: F401
from deeplearning4j_tpu.util.compat import tpu_compiler_params

_NEG_INF = -1e30
# 4 heads of 1,024 queries and 512 keys want more than the 16 MB default
_VMEM_LIMIT = 32 * 2**20


def _kernel(rows_ref, start_ref, qn_ref, qp_ref, ckv_ref, kpe_ref, wk_ref,
            wv_ref, o_ref, m_ref, l_ref, acc_ref, *, nope, v_dim, bq, bk,
            sub, scale):
    """One (row, head group, query block, key block): qn_ref [1, bq,
    hg * nope], qp_ref [1, hg, bq, rope], ckv_ref [1, bk, kv_rank],
    kpe_ref [1, bk, rope], wk_ref [kv_rank, hg * nope], wv_ref [kv_rank,
    hg * v]; m, l [hg, bq, 1] and acc [hg, bq, v] float32 run over the
    key blocks."""
    f32 = jnp.float32
    i, qb, kb = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    hg = qp_ref.shape[1]
    q0 = start_ref[i] + qb * bq          # the block's first query position
    k0 = kb * bk                         # the block's first key

    @pl.when(kb == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    def update(h, r0, k, v, masked):
        rows = pl.ds(r0, sub)
        s = (jax.lax.dot_general(
                qn_ref[0, rows, pl.ds(pl.multiple_of(h * nope, nope), nope)],
                k,
                (((1,), (1,)), ((), ())), preferred_element_type=f32)
             + jax.lax.dot_general(
                qp_ref[0, h, rows, :], kpe_ref[0],
                (((1,), (1,)), ((), ())), preferred_element_type=f32)) * scale
        if masked:
            key = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            pos = q0 + r0 + jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
            s = jnp.where(key <= pos, s, _NEG_INF)
        # every query sees key 0 in block 0, so the running maximum is a
        # real score from the first block on and a masked score's exp is 0
        m_prev = m_ref[h, rows]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h, rows] = alpha * l_ref[h, rows] + jnp.sum(p, axis=1,
                                                          keepdims=True)
        acc_ref[h, rows] = alpha * acc_ref[h, rows] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=f32)
        m_ref[h, rows] = m_new

    # heads and sub-blocks are loops: unrolled, 4 heads of 2 sub-blocks
    # took four times as long to compile (4.4 s against 1.0-1.1 s)
    def head(h, carry):
        ckv = ckv_ref[0]
        k = jnp.dot(ckv, wk_ref[:, pl.ds(pl.multiple_of(h * nope, nope),
                                         nope)],
                    preferred_element_type=f32).astype(ckv.dtype)
        v = jnp.dot(ckv, wv_ref[:, pl.ds(pl.multiple_of(h * v_dim, v_dim),
                                         v_dim)],
                    preferred_element_type=f32).astype(ckv.dtype)

        def part(j, carry):
            r0 = pl.multiple_of(j * sub, sub)
            first = q0 + r0              # the sub-block's first position
            pl.when(k0 + bk - 1 <= first)(
                functools.partial(update, h, r0, k, v, False))
            pl.when((k0 + bk - 1 > first) & (k0 <= first + sub - 1))(
                functools.partial(update, h, r0, k, v, True))
            return carry

        return jax.lax.fori_loop(0, bq // sub, part, carry)

    @pl.when(k0 <= q0 + bq - 1)
    def _():
        jax.lax.fori_loop(0, hg, head, 0)

    @pl.when(kb == pl.num_programs(3) - 1)
    def _():
        for h in range(hg):
            o_ref[0, :, h * v_dim:(h + 1) * v_dim] = (
                acc_ref[h] / jnp.maximum(l_ref[h], 1e-30)).astype(o_ref.dtype)


def prefill_flash(q_nope, q_pe, ckv, kpe, w_k, w_v, rows, start, *,
                  interpret=False):
    """q_nope [b, T, H * nope], q_pe [b, T, H, rope] (rotated) of the
    chunks of the cache rows `rows` [b], whose first queries lie at
    `start` [b]; the cache's latent rows ckv [B, S, kv_rank] and rotary
    slices kpe [B, S, rope], the chunk's own already written; w_k
    [kv_rank, H * nope] and w_v [kv_rank, H * v] the up-projections.
    -> [b, T, H * v] in q_nope's dtype."""
    T, H, S = q_nope.shape[1], q_pe.shape[2], ckv.shape[1]
    bq = gqa_block(T, autotune.DEFAULT_PREFILL_BLOCK_Q)
    return _prefill_flash(
        q_nope, q_pe, ckv, kpe, w_k, w_v, rows, start,
        hg=(autotune.DEFAULT_PREFILL_HEADS
            if H % autotune.DEFAULT_PREFILL_HEADS == 0 else 1), bq=bq,
        bk=gqa_block(S, autotune.DEFAULT_PREFILL_BLOCK_K),
        sub=gqa_block(bq, autotune.DEFAULT_PREFILL_SUB_ROWS),
        interpret=interpret)


# jitted, so that the layers of a program that share a chunk's shapes
# share one trace and one lowering of the kernel (each costs about 0.2 s
# of every process's set-up, compile cache or not)
@functools.partial(jax.jit,
                   static_argnames=("hg", "bq", "bk", "sub", "interpret"))
def _prefill_flash(q_nope, q_pe, ckv, kpe, w_k, w_v, rows, start, *, hg, bq,
                   bk, sub, interpret):
    b, T, _ = q_nope.shape
    H, rope = q_pe.shape[2], q_pe.shape[3]
    S, c = ckv.shape[1], ckv.shape[2]
    nope, v_dim = w_k.shape[1] // H, w_v.shape[1] // H
    n_k = S // bk

    def key_block(i, g, qb, kb, rows, start):
        # past the query block's causal frontier the index repeats the
        # last visible block: no new copy
        last = jnp.minimum((start[i] + (qb + 1) * bq - 1) // bk, n_k - 1)
        return rows[i], jnp.minimum(kb, last), 0

    def query(i, g, qb, kb, rows, start):
        return i, qb, g

    def query_pe(i, g, qb, kb, rows, start):
        return i, g, qb, 0

    def weight(i, g, qb, kb, rows, start):
        return 0, g

    itemsize = jnp.dtype(ckv.dtype).itemsize
    pairs = b * T * S
    return pl.pallas_call(
        functools.partial(_kernel, nope=nope, v_dim=v_dim, bq=bq, bk=bk,
                          sub=sub, scale=1.0 / (nope + rope) ** 0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, H // hg, T // bq, n_k),
            in_specs=[pl.BlockSpec((1, bq, hg * nope), query),
                      pl.BlockSpec((1, hg, bq, rope), query_pe),
                      pl.BlockSpec((1, bk, c), key_block),
                      pl.BlockSpec((1, bk, rope), key_block),
                      pl.BlockSpec((c, hg * nope), weight),
                      pl.BlockSpec((c, hg * v_dim), weight)],
            out_specs=pl.BlockSpec((1, bq, hg * v_dim), query),
            scratch_shapes=[pltpu.VMEM((hg, bq, 1), jnp.float32),
                            pltpu.VMEM((hg, bq, 1), jnp.float32),
                            pltpu.VMEM((hg, bq, v_dim), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, T, H * v_dim), q_nope.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        # the causal half of every (query, key) pair, and the expansion
        cost_estimate=pl.CostEstimate(
            flops=pairs * H * (nope + rope + v_dim)
            + 2 * b * (T // bq) * S * c * H * (nope + v_dim),
            transcendentals=pairs * H // 2,
            bytes_accessed=(2 * b * T * H * (nope + v_dim)
                            + b * (H // hg) * S * (c + rope)) * itemsize),
        name="prefill_flash",
        interpret=interpret,
    )(rows.astype(jnp.int32), start.astype(jnp.int32), q_nope,
      q_pe.transpose(0, 2, 1, 3), ckv, kpe, w_k, w_v)


# ------------------------------------------------- grouped prefill kernel
#
# Grouped attention's chunk (`nn/layers/grouped_attention.py` holds the
# equations): T queries of one cache row at positions start .. start +
# T - 1, G query heads a key-value head, attend two sources of keys under
# ONE softmax:
#
# * the layer's entry AS THE CHUNK FOUND IT, [B, Hk, R, d], before the
#   chunk's own rows are written: read as a ring (`ops/decode_attention`'s
#   last section), row r holds p_r = (start - 1) - ((start - 1 - r) mod R),
#   seen by the query at q iff p_r >= 0 and, in a window layer,
#   p_r > q - window (every p_r lies below start). A full layer's entry
#   never wraps, and the same arithmetic gives p_r = r below start;
# * the chunk's own keys, [b, Hk, T, d]: key j (position start + j) is
#   seen by query t iff j <= t and `keep[j]`. The kernel takes a chunk no
#   longer than its entry (`gqa_prefill_fits`), so T <= R <= window and
#   no own key lies below any query's window.
#
# A program instance is (row, key-value head, block of queries, step of
# keys): the steps walk the entry's blocks, then the chunk's own. The G
# heads' queries of a sub-block of rows are stacked into one product
# against one copy of the key block. Of the entry, only the blocks some
# query of the query block can see are walked, oldest first, each row at
# its own length and floor: XLA plans the walk ahead of the kernel
# (`_ring_plan`, prefetched as scalars with the rows and the starts); the
# index of a step past the last such block repeats that block's (no copy
# is issued) and its body is skipped. Of the chunk's own blocks, those
# past the query block's causal frontier are treated so. A sub-block of
# the entry's is skipped where it sees no key, unmasked where it sees
# every key, masked elsewhere; one of the chunk's own keys is masked (a
# served chunk is one query block, whose own keys are its diagonal).
# Sub-blocks are a loop. Precision is the `jnp` walk's: float32 scores
# times 1 / sqrt(d), float32 running max and sum, probabilities cast to
# the value dtype for the weighted sum, float32 accumulation; a query
# that sees nothing gets a zero row.

# a masked score: below the running maximum's floor, so its exp is 0 even
# for a query that has seen no key yet
_MASKED = 2 * _NEG_INF
# 6 heads of 1,024 queries: queries, output, max, sum and accumulator
# double-buffered and in float32 want more than the 16 MB default
_GQA_VMEM_LIMIT = 64 * 2**20


def gqa_prefill_fits(T: int, R: int) -> bool:
    """Whether `gqa_prefill` takes a chunk of T queries over an entry of
    R rows: a chunk no longer than the entry (a longer one would have to
    mask its own keys by the window), rows that blocks of 16 divide (a
    bfloat16 tile's sublanes), and a chunk of whole 128-key lanes (its
    `keep` is read a lane block at a time). The served buckets are."""
    return T <= R and R % 16 == 0 and T % 128 == 0


def _own_block(T: int, block: int) -> int:
    """The largest multiple of 128 that divides T and is at most `block`,
    else T whole."""
    for bo in range(min(block, T) // 128 * 128, 0, -128):
        if T % bo == 0:
            return bo
    return T


def _floor(pos, window: int):
    """The oldest position a query at `pos` sees."""
    return jnp.maximum(pos - window + 1, 0) if window else 0


def _held(r, a, base, R: int):
    """The position row r of a ring of R rows holds, `a` the row of the
    newest and `base` the position row 0 holds in the newest's round."""
    return base + r - jnp.where(r > a, R, 0)


def _ring_plan(start, T: int, *, R: int, window: int, bq: int, bk: int):
    """The walk over the entry, as XLA computes it ahead of the kernel,
    for the chunks starting at `start` [b]: for each row, query block and
    step of the walk, the block of rows taken (`blk`, oldest first; past
    the last block some query of the query block sees, that last block
    again), the newest and the oldest position it holds; for each row and
    query block, how many blocks are seen (`n`); for each row, the row of
    the newest position (`a`) and the position row 0 holds in its round
    (`base`): row r holds base + r, less R past `a`. -> (blk, newest,
    oldest [b * nq * R // bk], n [b * nq], a, base [b]), int32."""
    n_ring = R // bk
    start = start.astype(jnp.int32)[:, None]
    q0 = start + bq * jnp.arange(T // bq, dtype=jnp.int32)[None, :]
    lo = jnp.broadcast_to(jnp.maximum(start - R, _floor(q0, window)),
                          q0.shape)
    n = jnp.minimum(jnp.where(start > lo, (start - 1) // bk - lo // bk + 1, 0),
                    n_ring)                                     # [b, nq]
    step = jnp.minimum(jnp.arange(n_ring)[None, None, :],
                       jnp.maximum(n - 1, 0)[..., None])
    blk = ((lo // bk)[..., None] + step) % n_ring               # [b, nq, n]
    a = (start - 1) % R
    base = start - 1 - a
    a, base = a[..., None], base[..., None]
    r_lo = blk * bk
    has_a = (r_lo <= a) & (a < r_lo + bk)
    newest = jnp.where(has_a, start[..., None] - 1,
                       _held(r_lo + bk - 1, a, base, R))
    oldest = jnp.where(has_a & (a < r_lo + bk - 1), start[..., None] - R,
                       _held(r_lo, a, base, R))
    return tuple(x.reshape(-1).astype(jnp.int32)
                 for x in (blk, newest, oldest, n, a, base))


def _gqa_kernel(rows_ref, start_ref, blk_ref, newest_ref, oldest_ref,
                n_ref, a_ref, base_ref, q_ref, k_ref, v_ref, ko_ref, vo_ref,
                keep_ref, o_ref, m_ref, l_ref, acc_ref, vm_ref, *,
                R, window, bq, bk, bo, sub, scale):
    """One (row, key-value head, query block, key step): q_ref [1, 1, G,
    bq, d]; k_ref, v_ref [1, 1, bk, d] a block of the entry's rows;
    ko_ref, vo_ref [1, 1, bo, d] and keep_ref [1, 1, bo] a block of the
    chunk's own; m, l [G, bq, 1] and acc [G, bq, d] float32 run over the
    steps; vm_ref [bk, d] the entry's values with the rows no query of
    the block sees zeroed."""
    f32 = jnp.float32
    i, qb, s = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    G, n_ring, nq = q_ref.shape[2], R // bk, pl.num_programs(2)
    t0 = qb * bq                         # the block's first query ...
    q0 = start_ref[i] + t0               # ... and its position

    @pl.when(s == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, f32)
        l_ref[...] = jnp.zeros(l_ref.shape, f32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, f32)

    def update(r0, k, v, seen):
        # the G heads' queries of the sub-block stacked: one product of
        # G * sub rows against the block's keys, then its values. `lax`
        # alone: each `jnp` call is a traced function of its own, and this
        # body is traced three times a kernel, in every process that
        # serves (its set-up)
        lax = jax.lax
        rows, n = pl.ds(r0, sub), k.shape[0]
        s_ = lax.reshape(lax.mul(lax.dot_general(
            lax.reshape(q_ref[0, 0, :, rows, :], (G * sub, k.shape[1])), k,
            (((1,), (1,)), ((), ())), preferred_element_type=f32), scale),
            (G, sub, n))
        if seen is not None:
            s_ = lax.select(lax.broadcast_in_dim(seen, s_.shape, (1, 2)), s_,
                            lax.full_like(s_, _MASKED))
        m_prev = m_ref[:, rows]
        m_new = lax.max(m_prev, lax.expand_dims(lax.reduce_max(s_, (2,)),
                                                (2,)))
        p = lax.exp(lax.sub(s_, m_new))
        alpha = lax.exp(lax.sub(m_prev, m_new))
        l_ref[:, rows] = lax.add(lax.mul(alpha, l_ref[:, rows]),
                                 lax.expand_dims(lax.reduce_sum(p, (2,)), (2,)))
        acc_ref[:, rows] = lax.add(
            lax.mul(alpha, acc_ref[:, rows]),
            lax.reshape(lax.dot_general(
                lax.reshape(lax.convert_element_type(p, v.dtype),
                            (G * sub, n)), v, (((1,), (0,)), ((), ())),
                preferred_element_type=f32), (G, sub, v.shape[1])))
        m_ref[:, rows] = m_new

    def sweep(load, visit, seen, whole=None):
        """The sub-blocks of query rows against one key block:
        `visit(r0)`, some query of the sub-block at row r0 sees a key of
        it; `seen(r0)` the [sub, keys] mask; `whole(r0)`, every query
        sees every key, and the mask is left out."""
        def part(j, carry):
            r0 = pl.multiple_of(j * sub, sub)
            if whole is None:
                pl.when(visit(r0))(lambda: update(r0, *load(), seen(r0)))
                return carry
            every = whole(r0)
            pl.when(every)(lambda: update(r0, *load(), None))
            pl.when(visit(r0) & jnp.logical_not(every))(
                lambda: update(r0, *load(), seen(r0)))
            return carry

        jax.lax.fori_loop(0, bq // sub, part, 0)

    @pl.when(s < n_ref[i * nq + qb])
    def _():
        at = (i * nq + qb) * n_ring + s
        r_lo = blk_ref[at] * bk              # the block's first row
        newest, oldest = newest_ref[at], oldest_ref[at]
        a, base = a_ref[i], base_ref[i]
        row_pos = _held(r_lo + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1),
                        a, base, R)
        col_pos = _held(r_lo + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0),
                        a, base, R)
        # a row no query of the block sees may hold anything (a slot's
        # last tenant, a row never written): its weight is 0, its value
        # must be too
        vm_ref[...] = jnp.where(col_pos >= _floor(q0, window), v_ref[0, 0],
                                0).astype(vm_ref.dtype)
        sweep(lambda: (k_ref[0, 0], vm_ref[...]),
              lambda r0: newest >= _floor(q0 + r0, window),
              lambda r0: row_pos >= _floor(
                  q0 + r0 + jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0),
                  window),
              lambda r0: oldest >= _floor(q0 + r0 + sub - 1, window))

    # the chunk's own keys, every sub-block masked
    j0 = (s - n_ring) * bo

    @pl.when((s >= n_ring) & (j0 <= t0 + bq - 1))
    def _():
        key = j0 + jax.lax.broadcasted_iota(jnp.int32, (1, bo), 1)
        sweep(lambda: (ko_ref[0, 0], vo_ref[0, 0]),
              lambda r0: j0 <= t0 + r0 + sub - 1,
              lambda r0: (key <= t0 + r0 + jax.lax.broadcasted_iota(
                  jnp.int32, (sub, 1), 0)) & (keep_ref[0] > 0))

    @pl.when(s == pl.num_programs(3) - 1)
    def _():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


def gqa_prefill(qg, k, v, k_own, v_own, keep, rows, start, *, window: int,
                pass_index=None, interpret=False):
    """A grouped prefill chunk's attention (the section above): qg [b,
    Hk, G * T, d] the chunk's queries grouped as `ops/decode_attention.
    group_queries` groups them (rotated and normed) at positions start ..
    start + T - 1 of the cache rows `rows` [b], `start` [b]; k, v [B, Hk,
    R, d] the layer's entry as the chunk found it (a ring of R rows where
    `window` > 0); k_own, v_own [b, Hk, T, d] the chunk's own keys and
    values, `keep` [b, T] 0 hiding a key (a bucket's pad). T <= R
    (`gqa_prefill_fits`). With `pass_index` (a scalar: the pass of a
    loop), k and v are [B, P, Hk, R, d] and the pass is one more
    prefetched scalar: the index maps read that pass's blocks where they
    lie. -> [b, Hk, G * T, d] in qg's dtype."""
    T, R = k_own.shape[2], k.shape[-2]
    if T > R:
        raise ValueError(f"gqa_prefill takes a chunk no longer than its "
                         f"entry; got {T} queries over {R} rows")
    bq = gqa_block(T, autotune.DEFAULT_GQA_PREFILL_BLOCK_Q)
    return _gqa_prefill(
        qg, k, v, k_own, v_own, keep, rows, start, window=window, bq=bq,
        bk=gqa_block(R, autotune.DEFAULT_GQA_PREFILL_BLOCK_K),
        bo=_own_block(T, autotune.DEFAULT_GQA_PREFILL_BLOCK_K),
        sub=gqa_block(bq, autotune.DEFAULT_GQA_PREFILL_SUB_ROWS),
        interpret=interpret, pass_index=pass_index)


# jitted, as `_prefill_flash`: the layers of a program that share a
# chunk's shapes share one trace and one lowering of the kernel
@functools.partial(jax.jit, static_argnames=("window", "bq", "bk", "bo",
                                             "sub", "interpret"))
def _gqa_prefill(qg, k, v, k_own, v_own, keep, rows, start, *, window, bq,
                 bk, bo, sub, interpret, pass_index=None):
    b, Hk, GT, d = qg.shape
    T, R = k_own.shape[2], k.shape[-2]
    G, n_ring, n_own = GT // T, R // bk, T // bo
    dtype = k.dtype
    nq = T // bq
    keep = (keep > 0).astype(jnp.int32)
    plan = _ring_plan(start, T, R=R, window=window, bq=bq, bk=bk)

    def query(i, c, qb, s, *refs):
        return i, c, 0, qb, 0

    def ring_block(i, qb, s, blk):
        # past the last block the query block sees, the index repeats
        # that block's: no new copy
        return blk[(i * nq + qb) * n_ring + jnp.minimum(s, n_ring - 1)]

    def ring(i, c, qb, s, rows, start, blk, *refs):
        return rows[i], c, ring_block(i, qb, s, blk), 0

    def ring_of_pass(i, c, qb, s, rows, start, blk, *refs):
        # the pass is the last prefetched scalar
        return rows[i], refs[-1][0], c, ring_block(i, qb, s, blk), 0

    def own_index(s, qb):
        # `lax.div` of non-negative numbers: a floor division would cost
        # a lowering of its sign correction
        return jnp.clip(s - n_ring, 0, jax.lax.div(qb * bq + bq - 1, bo))

    def own(i, c, qb, s, *refs):
        return i, c, own_index(s, qb), 0

    def own_keep(i, c, qb, s, *refs):
        return i, 0, own_index(s, qb)

    kernel = functools.partial(_gqa_kernel, R=R, window=window, bq=bq,
                               bk=bk, bo=bo, sub=sub, scale=1.0 / d ** 0.5)
    scalars = [rows.astype(jnp.int32), start.astype(jnp.int32), *plan]
    ring_spec = pl.BlockSpec((1, 1, bk, d), ring)
    if pass_index is not None:
        ring_spec = pl.BlockSpec((1, pl.squeezed, 1, bk, d), ring_of_pass)
        kernel = without_pass(kernel, len(scalars))
        scalars.append(jnp.reshape(pass_index, (1,)).astype(jnp.int32))
    itemsize = jnp.dtype(dtype).itemsize
    pairs = b * GT * (R + T) // 2          # about half of every pair
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b, Hk, nq, n_ring + n_own),
            in_specs=[pl.BlockSpec((1, 1, G, bq, d), query), ring_spec,
                      ring_spec,
                      pl.BlockSpec((1, 1, bo, d), own),
                      pl.BlockSpec((1, 1, bo, d), own),
                      pl.BlockSpec((1, 1, bo), own_keep)],
            out_specs=pl.BlockSpec((1, 1, G, bq, d), query),
            scratch_shapes=[pltpu.VMEM((G, bq, 1), jnp.float32),
                            pltpu.VMEM((G, bq, 1), jnp.float32),
                            pltpu.VMEM((G, bq, d), jnp.float32),
                            pltpu.VMEM((bk, d), dtype)]),
        out_shape=jax.ShapeDtypeStruct((b, Hk, G, T, d), qg.dtype),
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=_GQA_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=4 * pairs * d, transcendentals=pairs,
            bytes_accessed=(2 * b * GT * d
                            + b * Hk * (T // bq) * 2 * (R + T) * d)
            * itemsize),
        name="gqa_prefill",
        interpret=interpret,
    )(*scalars, qg.astype(dtype).reshape(b, Hk, G, T, d), k, v,
      k_own.astype(dtype), v_own.astype(dtype), keep.reshape(b, 1, T))
    return out.reshape(b, Hk, GT, d)
