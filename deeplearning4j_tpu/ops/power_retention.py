"""Power retention of degree 2: the op family behind
`PowerRetentionLayer` (nn/layers/power_retention.py holds the layer's
equations; Manifest AI, "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239).

The weight of key j for query t is `decay(t, j) * (q_t . k_j)^2 / d`,
normalised by the sum of the weights. The power is even, so a weight is
never negative, and it is an inner product of the symmetric squares of q
and k (`phi2`), so the sums over the past are a STATE of fixed size:

    phi2(x)  = [c_ij x_i x_j] over the pairs i <= j, c = sqrt(2 - delta_ij)
               / sqrt(d), so that phi2(q) . phi2(k) = (q . k)^2 / d exactly
    S_t      = g_t S_(t-1) + v_t phi2(k_t)^T       [d, D]   value-major
    z_t      = g_t z_(t-1) + phi2(k_t)             [D]
    y_t      = S_t phi2(q_t) / (z_t . phi2(q_t) + eps)

`phi2` orders the pairs by DIAGONAL: entry b * d + i is the pair
(i, (i - b) mod d), b = 0 .. d / 2. Diagonals 1 .. d / 2 - 1 hold every
pair at distance b once. The last, b = d / 2, meets every one of its
pairs twice (i and i + d / 2 name the same pair), so it is held whole at
weight 1 / sqrt(d) in place of sqrt(2 / d): the two copies add up to the
pair's own term and the inner product above stays exact. That makes D =
d (d / 2 + 1), 8,320 at d = 128: the d (d + 1) / 2 = 8,256 pairs padded
by the half diagonal's second copy (0.8 %) to a whole number of 128-lane
rows. A diagonal is `x * roll(x, b)`, a whole lane row at d = 128, so
the kernel below makes phi2 of a query or a key from the 128-vector
itself with one lane rotation a diagonal and never reads a D-vector from
memory. The state is held value-major ([d, D]: D along the lanes) for
the same reason: `v phi2(k)^T` is a column times a lane row and the
read-out a lane-row product, nothing is transposed; and the device
stores an array whose last axis is a multiple of 128 as it is written
(8,256 is none: the compiler then keeps such an array [D, d] and copies
all of it into the kernel's order and back, every step).

Four entries:

* `retention_chunk`: many tokens a row (a prefill chunk, a whole
  sequence): the state as the chunk found it, decayed to each query,
  plus the attention form inside a sub-chunk of `SUB_CHUNK` tokens, then
  the state advanced over the sub-chunk's kept tokens; a `lax.scan` over
  the sub-chunks, so `phi2` of a 1,024-token chunk's queries (40 x 1024
  x 8256 values) is never whole in memory. Plain `jnp`, differentiable
  as written. Products in the inputs' dtype with float32 accumulation;
  decays, weights and the state in float32.
* `retention_chunk_kernel`: the same chunk through the cache's rows,
  the state's terms as one Pallas kernel, `retention_chunk` in the
  device trace. A program instance is (row, key-value head, tile of
  `DIAGONAL_TILE` diagonals): the tile of the state, [d, tile] with its
  z, lives in VMEM across all of the chunk's sub-chunks, is read from
  the cache once and written back once, IN PLACE. Diagonal tiles are
  independent in the update and add up in the read-out, which the
  instances of a (row, head) accumulate in order. For each sub-chunk it
  makes phi2 of the queries and keys in VMEM, a diagonal at a time (one
  lane rotation and a product, as the decode kernel does), reads the
  state as the sub-chunk found it with the matrix unit (`S phi2(q)`;
  `z . phi2(q)` as `q^T M q`, M the tile's z laid on the diagonals of a
  [d, d] matrix) and advances it: `S <- g S + [(v tail)^T; tail] phi2(k)`
  in one product, z's weights `tail` in two bfloat16 parts whose sum
  holds 16 bits of it. Operands in the inputs' dtype, float32
  accumulation, the state float32 (its own dtype) throughout. The decay
  to each query and the attention form inside a sub-chunk stay in XLA.
  A fresh row starts from zero and skips its first sub-chunk's read-out.
  No gradient: `apply` keeps `retention_chunk`.
* `retention_chunk_rows`: a prefill chunk's entry, the kernel on a TPU
  (`chunk_kernel_fits`), elsewhere `retention_chunk` on the rows
  gathered and written back; tier-1 holds the kernel to it in interpret
  mode.
* `retention_decode`: one token a row. On a TPU one Pallas kernel,
  `retention_decode` in the device trace: a pass over a (row, key-value
  head)'s state in value tiles that decays the tile, adds `v phi2(k)^T`,
  writes it back IN PLACE (`input_output_aliases`: a donated cache stays
  one copy) and accumulates `S phi2(q)` for the head's group of queries
  and `z . phi2(q)`: the state is read once and written once a step.
  The read-out is exact float32 on the vector unit (the state is a sum
  over thousands of tokens; a matrix-unit pass would round it to
  bfloat16). Off the TPU its `jnp` twin (`retention_decode_jnp`), which
  tier-1 holds the kernel to in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.util.compat import on_tpu as _use_kernel
from deeplearning4j_tpu.util.compat import tpu_compiler_params

SUB_CHUNK = 128     # tokens whose phi2 a chunk holds at once
VALUE_TILE = 64     # rows of a head's [d, D] state one kernel step holds
DIAGONAL_TILE = 13  # diagonals of a head's state one chunk-kernel step holds
# a chunk of 1,024 tokens: its queries, keys and read-out stay in VMEM
# beside the state's tile, about 22 MB; the default scope is 16 MB
_CHUNK_VMEM_LIMIT = 48 * 2**20
_MASKED = -1e30


def state_dim(d: int) -> int:
    """D: the d / 2 + 1 diagonals of a d-vector's symmetric square."""
    return d * (d // 2 + 1)


def _diagonal(x, b: int, roll):
    """Diagonal b of the symmetric square of x [..., d], scaled: the
    pairs (i, (i - b) mod d)."""
    d = x.shape[-1]
    if b == 0:
        return x * x * (1.0 / d ** 0.5)
    return x * roll(x, b) * ((1.0 if 2 * b == d else 2.0) / d) ** 0.5


def phi2(x):
    """The symmetric square of x [..., d] (d even), by diagonals (module
    docstring) -> [..., d (d / 2 + 1)], so that phi2(q) . phi2(k) =
    (q . k)^2 / d."""
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"phi2 needs an even head size, got {d}")
    return jnp.concatenate(
        [_diagonal(x, b, lambda a, s: jnp.roll(a, s, axis=-1))
         for b in range(d // 2 + 1)], axis=-1)


# ------------------------------------------------------------- chunk form

def _sub_chunks(q, k, v, log_g, keep, C):
    """The chunk's tokens padded to whole sub-chunks of C: a pad adds
    nothing and decays nothing (keep 0, log g 0). -> (q, k, v, log_g,
    keep) as [b, n, C, ...], log_g and keep float32."""
    b, T = q.shape[:2]
    keep = (jnp.ones((b, T), jnp.float32) if keep is None
            else keep.astype(jnp.float32))
    log_g = log_g.astype(jnp.float32) * keep[..., None]
    pad = -T % C
    n = (T + pad) // C
    return tuple(
        jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)).reshape(
            (b, n, C) + a.shape[2:]) for a in (q, k, v, log_g, keep))


def _within(qg, kc, vc, G, kp):
    """The attention form inside each sub-chunk: qg [..., C, Hk, R, d],
    kc and vc [..., C, Hk, d], G [..., C, Hk] the running log decay from
    the sub-chunk's start, kp [..., C] its keep. -> (num [..., C, Hk, R,
    d], den [..., C, Hk, R]) float32."""
    C, d = qg.shape[-4], qg.shape[-1]
    f32 = jnp.float32
    sc = jnp.einsum("...chrd,...jhd->...hrcj", qg, kc,
                    preferred_element_type=f32)
    causal = jnp.tril(jnp.ones((C, C), bool))
    seen = causal[..., None] & (kp > 0)[..., None, :, None]
    decay = jnp.exp(jnp.where(seen, G[..., :, None, :] - G[..., None, :, :],
                              _MASKED))                 # [..., c, j, Hk]
    w = sc * sc * (1.0 / d) * jnp.moveaxis(decay, -1, -3)[..., None, :, :]
    num = jnp.einsum("...hrcj,...jhv->...chrv", w.astype(vc.dtype), vc,
                     preferred_element_type=f32)
    return num, jnp.moveaxis(w.sum(-1), -1, -3)


def _advance(vc, G, kp):
    """What a sub-chunk adds to the state: tail [..., C, Hk] the decay
    of each kept token to the sub-chunk's end, out [..., Hk] the decay
    over all of it, vt [..., C, Hk, d] = v tail in v's dtype."""
    tail = jnp.exp(G[..., -1:, :] - G) * kp[..., None]
    vt = (vc.astype(jnp.float32) * tail[..., None]).astype(vc.dtype)
    return tail, jnp.exp(G[..., -1, :]), vt


def retention_chunk(q, k, v, log_g, s, z, *, keep=None, eps=1e-6,
                    sub_chunk=SUB_CHUNK):
    """q [b, T, Hq, d], k and v [b, T, Hk, d] (Hq a multiple of Hk: query
    head h reads the state of head h // (Hq / Hk)), log_g [b, T, Hk]
    float32 (<= 0), the state s [b, Hk, d, D] and z [b, Hk, D] as the
    chunk finds it. `keep` [b, T]: 0 for a token that is no part of the
    sequence (the pad of a bucket): it adds nothing and decays nothing.
    -> (y [b, T, Hq, d] in q's dtype, s, z after the chunk's kept
    tokens, in their own dtype)."""
    b, T, Hq, d = q.shape
    Hk = k.shape[2]
    R = Hq // Hk
    C = min(sub_chunk, T)
    f32 = jnp.float32

    def body(carry, xs):
        s, z = carry
        qc, kc, vc, lg, kp = xs
        G = jnp.cumsum(lg, axis=1)                          # [b, C, Hk]
        qg = qc.reshape(b, C, Hk, R, d)
        pq, pk = phi2(qg), phi2(kc)
        # the state as the sub-chunk found it, decayed to each query
        into = jnp.exp(G)
        num = jnp.einsum("bchrD,bhvD->bchrv", pq, s.astype(pq.dtype),
                         preferred_element_type=f32) * into[..., None, None]
        den = jnp.einsum("bchrD,bhD->bchr", pq, z.astype(pq.dtype),
                         preferred_element_type=f32) * into[..., None]
        wnum, wden = _within(qg, kc, vc, G, kp)
        y = (num + wnum) / (den + wden + eps)[..., None]
        # the state advanced over the sub-chunk's kept tokens
        tail, out, vt = _advance(vc, G, kp)
        s_new = out[..., None, None] * s.astype(f32) + jnp.einsum(
            "bjhv,bjhD->bhvD", vt, pk, preferred_element_type=f32)
        z_new = out[..., None] * z.astype(f32) + jnp.einsum(
            "bjh,bjhD->bhD", tail, pk.astype(f32),
            preferred_element_type=f32)
        return ((s_new.astype(s.dtype), z_new.astype(z.dtype)),
                y.reshape(b, C, Hq, d).astype(qc.dtype))

    xs = _sub_chunks(q, k, v, log_g, keep, C)
    (s, z), y = jax.lax.scan(body, (s, z),
                             tuple(jnp.moveaxis(a, 1, 0) for a in xs))
    return jnp.moveaxis(y, 0, 1).reshape(b, -1, Hq, d)[:, :T], s, z


def _chunk_kernel(rows_ref, fresh_ref, used_ref, out_ref, q_ref, k_ref, u_ref,
                  s_ref, z_ref, s_out, z_out, num_ref, den_ref, pq_ref,
                  pk_ref, *, d, nb):
    """One (row, key-value head, tile of nb diagonals) over all of the
    chunk's n sub-chunks: q_ref [n, R C, d] the head's queries, k_ref
    [n, C, d] its keys, u_ref [n, d + 8, C] the update's left factor,
    s_ref [d, tile] and z_ref [1, tile] the state's tile as the chunk
    found it. The tile lives in s_out and z_out across the sub-chunks
    up to the last that keeps a token (`used_ref`: past it a sub-chunk
    adds and decays nothing); num_ref and den_ref [n, R C, d] add up the
    read-out over the tiles (den by lanes)."""
    f32 = jnp.float32
    i, h, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n = q_ref.shape[2]
    dt = pq_ref.dtype
    fresh = fresh_ref[i] > 0
    c = (2.0 / d) ** 0.5
    b0 = j * nb
    # [m, i] -> (i - m) mod d: the diagonal that the pair (i, m) lies on
    on_diagonal = jax.lax.rem(
        jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
        - jax.lax.broadcasted_iota(jnp.int32, (d, d), 0) + d, d)

    def weight(l):
        """Diagonal b0 + l's weight over sqrt(2 / d): diagonals 0 and d / 2
        weigh 1 / sqrt(d), and only a tile's first and last can be one."""
        if l not in (0, nb - 1):
            return None
        b = b0 + l
        return jnp.where(jnp.logical_or(b == 0, 2 * b == d), 0.5 ** 0.5, 1.0)

    def lanes(l):
        return slice(l * d, (l + 1) * d)

    def diagonal(x, xc, l):
        p = xc * pltpu.roll(x, b0 + l, 1)
        w = weight(l)
        return p if w is None else p * w

    @pl.when(j == 0)
    def _():
        num_ref[...] = jnp.zeros(num_ref.shape, f32)
        den_ref[...] = jnp.zeros(den_ref.shape, f32)

    s_out[0, 0] = jnp.where(fresh, 0.0, s_ref[0, 0]).astype(s_out.dtype)
    z_out[0, 0] = jnp.where(fresh, 0.0, z_ref[0, 0]).astype(z_out.dtype)

    def sub_chunk(t, carry):
        # the state as the sub-chunk found it, read by its queries (not
        # where the row starts here: a zero state reads zero)
        @pl.when(jnp.logical_or(t > 0, jnp.logical_not(fresh)))
        def _():
            qt = q_ref[0, 0, t]
            x = qt.astype(f32)
            xc = x * c
            zm = jnp.zeros((d, d), f32)
            for l in range(nb):
                pq_ref[:, lanes(l)] = diagonal(x, xc, l).astype(dt)
                w = weight(l)
                zl = z_out[0, 0, :, lanes(l)].astype(f32) * c
                zm = jnp.where(on_diagonal == b0 + l,
                               zl if w is None else zl * w, zm)
            num_ref[0, 0, t] += jax.lax.dot_general(
                pq_ref[...], s_out[0, 0].astype(dt),
                (((1,), (1,)), ((), ())), preferred_element_type=f32)
            # z . phi2(q) = q^T M q, the tile's z laid on M's diagonals
            den_ref[0, 0, t] += x * jnp.dot(qt, zm.astype(dt),
                                            preferred_element_type=f32)

        # the state advanced over the sub-chunk's kept tokens
        kx = k_ref[0, 0, t].astype(f32)
        kxc = kx * c
        for l in range(nb):
            pk_ref[:, lanes(l)] = diagonal(kx, kxc, l).astype(dt)
        add = jnp.dot(u_ref[0, 0, t], pk_ref[...], preferred_element_type=f32)
        g = out_ref[(i * pl.num_programs(1) + h) * n + t]
        s_out[0, 0] = (g * s_out[0, 0].astype(f32) + add[:d]).astype(
            s_out.dtype)
        z_out[0, 0] = (g * z_out[0, 0].astype(f32) + jnp.sum(
            add[d:], axis=0, keepdims=True)).astype(z_out.dtype)
        return carry

    jax.lax.fori_loop(0, used_ref[i], sub_chunk, 0)


def _diagonal_tile(d: int, most: int) -> int:
    """The largest divisor of a head's d / 2 + 1 diagonals that is at
    most `most`: the diagonals a kernel step holds of the state."""
    n = d // 2 + 1
    return max(t for t in range(1, min(most, n) + 1) if n % t == 0)


def chunk_kernel_fits(T: int, d: int) -> bool:
    """Whether the chunk kernel takes a chunk of T tokens at head size d:
    a diagonal is one lane row of 128, a sub-chunk whole bfloat16 tiles."""
    return d == 128 and min(SUB_CHUNK, T) % 16 == 0


def retention_chunk_kernel(q, k, v, log_g, s, z, rows, fresh, *, keep=None,
                           eps=1e-6, interpret=False):
    """`retention_chunk` through the cache's rows, the state's terms as one
    Pallas kernel: s [S, Hk, d, D] and z [S, Hk, D] the whole cache, rows
    [b] the chunk's rows in it, fresh [b] bool the rows whose state starts
    at zero here. -> (y [b, T, Hq, d] in q's dtype, s, z) with the rows'
    states advanced IN PLACE where the caller donates them."""
    f32 = jnp.float32
    b, T, Hq, d = q.shape
    Hk = k.shape[2]
    R = Hq // Hk
    dt = q.dtype
    C = min(SUB_CHUNK, T)
    qc, kc, vc, lg, kp = _sub_chunks(q, k.astype(dt), v.astype(dt), log_g,
                                     keep, C)
    n = qc.shape[1]
    G = jnp.cumsum(lg, axis=2)                              # [b, n, C, Hk]
    tail, out, vt = _advance(vc, G, kp)
    # the update's left factor [b, Hk, n, d + 8, C]: (v tail)^T, then z's
    # weights `tail` as two parts in dt (the second is 0 where dt is
    # float32), then zeros to a whole tile of rows
    hi = tail.astype(dt)
    lo = (tail - hi.astype(f32)).astype(dt)
    u = jnp.concatenate(
        [jnp.moveaxis(vt, (3, 4), (1, 3)),
         jnp.moveaxis(hi, 3, 1)[:, :, :, None],
         jnp.moveaxis(lo, 3, 1)[:, :, :, None],
         jnp.zeros((b, Hk, n, 6, C), dt)], axis=3)
    qg = qc.reshape(b, n, C, Hk, R, d)
    # sub-chunks up to the last that keeps a token: the rest of a bucket
    # is pad, which changes no state and whose queries are not read
    used = jnp.max(jnp.where(jnp.any(kp > 0, axis=2),
                             jnp.arange(1, n + 1), 0), axis=1)
    s, z, num, den = _state_terms(
        jnp.moveaxis(qg, (3, 4), (1, 3)).reshape(b, Hk, n, R * C, d),
        jnp.moveaxis(kc, 3, 1), u, jnp.moveaxis(out, 2, 1).reshape(-1),
        s, z, rows.astype(jnp.int32), fresh.astype(jnp.int32),
        used.astype(jnp.int32), nb=_diagonal_tile(d, DIAGONAL_TILE),
        interpret=interpret)
    # the state as each sub-chunk found it, decayed to each query, and
    # the attention form inside the sub-chunk
    into = jnp.exp(G)
    num = (jnp.moveaxis(num.reshape(b, Hk, n, R, C, d), (1, 3), (3, 4))
           * into[..., None, None])
    den = (jnp.moveaxis(den.sum(-1).reshape(b, Hk, n, R, C), (1, 3), (3, 4))
           * into[..., None])
    wnum, wden = _within(qg, kc, vc, G, kp)
    y = (num + wnum) / (den + wden + eps)[..., None]
    return y.reshape(b, n * C, Hq, d)[:, :T].astype(dt), s, z


# jitted, as ops/prefill_attention's kernels: the layers of a program
# that share a chunk's shapes share one trace and one lowering
@functools.partial(jax.jit, static_argnames=("nb", "interpret"))
def _state_terms(q, k, u, out, s, z, rows, fresh, used, *, nb, interpret):
    b, Hk, n, RC, d = q.shape
    C = k.shape[3]
    D = s.shape[-1]
    tile = nb * d
    dt = q.dtype

    def head(i, h, j, *scalars):
        return i, h, 0, 0, 0

    def state(i, h, j, rows, *scalars):
        return rows[i], h, 0, j

    f32 = jnp.float32
    itemsize = jnp.dtype(s.dtype).itemsize
    s, z, num, den = pl.pallas_call(
        functools.partial(_chunk_kernel, d=d, nb=nb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, Hk, D // tile),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec((1, 1, n, RC, d), head),
                      pl.BlockSpec((1, 1, n, C, d), head),
                      pl.BlockSpec((1, 1, n, d + 8, C), head),
                      pl.BlockSpec((1, 1, d, tile), state),
                      pl.BlockSpec((1, 1, 1, tile), state)],
            out_specs=[pl.BlockSpec((1, 1, d, tile), state),
                       pl.BlockSpec((1, 1, 1, tile), state),
                       pl.BlockSpec((1, 1, n, RC, d), head),
                       pl.BlockSpec((1, 1, n, RC, d), head)],
            scratch_shapes=[pltpu.VMEM((RC, tile), dt),
                            pltpu.VMEM((C, tile), dt)]),
        out_shape=[jax.ShapeDtypeStruct(s.shape, s.dtype),
                   jax.ShapeDtypeStruct(z.shape[:2] + (1, D), z.dtype),
                   jax.ShapeDtypeStruct((b, Hk, n, RC, d), f32),
                   jax.ShapeDtypeStruct((b, Hk, n, RC, d), f32)],
        input_output_aliases={7: 0, 8: 1},
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_CHUNK_VMEM_LIMIT),
        cost_estimate=pl.CostEstimate(
            flops=2 * b * Hk * n * D * (RC * d + (d + 8) * C + RC * d // nb),
            transcendentals=0,
            bytes_accessed=2 * b * Hk * (d + 1) * D * itemsize
            + b * Hk * n * (RC * d * (2 + 8) + (2 * d + 8) * C) * 2),
        name="retention_chunk",
        interpret=interpret,
    )(rows, fresh, used, out, q, k, u, s, z[:, :, None])
    return s, z[:, :, 0], num, den


def retention_chunk_rows(q, k, v, log_g, s, z, rows, fresh, *, keep=None,
                         eps=1e-6):
    """A prefill chunk through the cache's rows: s [S, Hk, d, D] and z
    [S, Hk, D] the whole cache, rows [b] the chunk's rows in it, fresh
    [b] bool the rows whose state starts at zero here (the rest as in
    `retention_chunk`). -> (y, s, z), the rows' states advanced. On a
    TPU the kernel where it fits, elsewhere `retention_chunk` on the
    rows gathered and written back."""
    if _use_kernel() and chunk_kernel_fits(q.shape[1], q.shape[-1]):
        return retention_chunk_kernel(q, k, v, log_g, s, z, rows, fresh,
                                      keep=keep, eps=eps)
    fresh = fresh[:, None, None]
    y, s_rows, z_rows = retention_chunk(
        q, k, v, log_g, jnp.where(fresh[..., None], 0, s[rows]),
        jnp.where(fresh, 0, z[rows]), keep=keep, eps=eps)
    with jax.named_scope("cache_write"):
        return y, s.at[rows].set(s_rows), z.at[rows].set(z_rows)


# ------------------------------------------------------------ decode step

def retention_decode_jnp(s, z, q, k, v, g):
    """The decode step in plain `jnp` (the kernel's twin): s [B, Hk, d,
    D], z [B, Hk, D], q [B, Hq, d], k and v [B, Hk, d], g [B, Hk] the
    step's decay (1 with k = 0 leaves a row's state as it is; 0 starts
    it anew) -> (num [B, Hq, d], den [B, Hq] float32, s, z)."""
    f32 = jnp.float32
    B, Hq, d = q.shape
    Hk = k.shape[1]
    pk = phi2(k.astype(f32))
    pq = phi2(q.astype(f32)).reshape(B, Hk, Hq // Hk, -1)
    g = g.astype(f32)
    s_new = (g[..., None, None] * s.astype(f32)
             + v.astype(f32)[..., :, None] * pk[..., None, :])
    z_new = g[..., None] * z.astype(f32) + pk
    exact = jax.lax.Precision.HIGHEST
    num = jnp.einsum("bhrD,bhvD->bhrv", pq, s_new, precision=exact,
                     preferred_element_type=f32)
    den = jnp.einsum("bhrD,bhD->bhr", pq, z_new, precision=exact,
                     preferred_element_type=f32)
    return (num.reshape(B, Hq, d), den.reshape(B, Hq),
            s_new.astype(s.dtype), z_new.astype(z.dtype))


def _decode_kernel(g_ref, x_ref, v_ref, s_ref, z_ref,
                   s_out, z_out, num_ref, den_ref, *, d, n_rep):
    """One (row, key-value head, value tile): x_ref [rows, d] holds the
    group's `n_rep` queries and, in row `n_rep`, the key."""
    f32 = jnp.float32
    g = g_ref[pl.program_id(0), pl.program_id(1)]
    x = x_ref[0, 0]
    vcol = v_ref[0, 0]                                      # [tile, 1]
    diagonals = range(d // 2 + 1)

    def diagonal(b):
        return _diagonal(x, b, lambda a, sh: pltpu.roll(a, sh, 1))

    def lanes(b):
        return slice(b * d, (b + 1) * d)

    acc = [jnp.zeros(s_ref.shape[2:3] + (d,), f32) for _ in range(n_rep)]
    for b in diagonals:
        p = diagonal(b)
        tile = (g * s_ref[0, 0, :, lanes(b)].astype(f32)
                + vcol * p[n_rep:n_rep + 1])
        s_out[0, 0, :, lanes(b)] = tile.astype(s_out.dtype)
        for h in range(n_rep):
            acc[h] = acc[h] + tile * p[h:h + 1]
    num = [jnp.sum(a, axis=1, keepdims=True) for a in acc]
    num += [jnp.zeros_like(num[0])] * (num_ref.shape[3] - n_rep)
    num_ref[0, 0] = jnp.concatenate(num, axis=1)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dacc = jnp.zeros(x.shape, f32)
        for b in diagonals:
            p = diagonal(b)
            row = g * z_ref[0, 0, :, lanes(b)].astype(f32) + p[n_rep:n_rep + 1]
            z_out[0, 0, :, lanes(b)] = row.astype(z_out.dtype)
            dacc = dacc + p * row
        den_ref[0, 0] = jnp.sum(dacc, axis=1, keepdims=True)


def retention_decode_kernel(s, z, q, k, v, g, *, interpret=False,
                            value_tile=VALUE_TILE):
    """`retention_decode_jnp` as one Pallas kernel; s and z are updated
    in place where the caller donates them."""
    f32 = jnp.float32
    B, Hk, d, D = s.shape
    Hq = q.shape[1]
    R = Hq // Hk
    rows = -(-(R + 1) // 8) * 8
    tv = value_tile if d % value_tile == 0 else d
    x = jnp.concatenate(
        [q.astype(f32).reshape(B, Hk, R, d), k.astype(f32)[:, :, None],
         jnp.zeros((B, Hk, rows - R - 1, d), f32)], axis=2)

    def whole(b, c, t):
        return b, c, 0, 0

    def tiled(b, c, t):
        return b, c, t, 0

    itemsize = jnp.dtype(s.dtype).itemsize
    s_new, z_new, num, den = pl.pallas_call(
        functools.partial(_decode_kernel, d=d, n_rep=R),
        grid=(B, Hk, d // tv),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, rows, d), whole),
            pl.BlockSpec((1, 1, tv, 1), tiled),
            pl.BlockSpec((1, 1, tv, D), tiled),
            pl.BlockSpec((1, 1, 1, D), whole),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, tv, D), tiled),
            pl.BlockSpec((1, 1, 1, D), whole),
            pl.BlockSpec((1, 1, tv, rows), tiled),
            pl.BlockSpec((1, 1, rows, 1), whole),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(s.shape, s.dtype),
            jax.ShapeDtypeStruct((B, Hk, 1, D), z.dtype),
            jax.ShapeDtypeStruct((B, Hk, d, rows), f32),
            jax.ShapeDtypeStruct((B, Hk, rows, 1), f32),
        ],
        input_output_aliases={3: 0, 4: 1},
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=48 * 2**20),
        cost_estimate=pl.CostEstimate(
            flops=B * Hk * d * D * (3 + 2 * R), transcendentals=0,
            bytes_accessed=2 * B * Hk * (d + 1) * D * itemsize),
        name="retention_decode",
        interpret=interpret,
    )(g.astype(f32), x, v.astype(f32)[..., None], s, z[:, :, None])
    num = jnp.swapaxes(num[..., :R], 2, 3).reshape(B, Hq, d)
    return num, den[:, :, :R, 0].reshape(B, Hq), s_new, z_new[:, :, 0]


def retention_decode(s, z, q, k, v, g):
    """One token a row through the state: the kernel on a TPU, its
    `jnp` twin elsewhere (same arguments and results)."""
    if _use_kernel():
        return retention_decode_kernel(s, z, q, k, v, g)
    return retention_decode_jnp(s, z, q, k, v, g)
