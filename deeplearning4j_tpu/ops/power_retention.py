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

Three entries:

* `retention_chunk`: many tokens a row (a prefill chunk, a whole
  sequence): the state as the chunk found it, decayed to each query,
  plus the attention form inside a sub-chunk of `SUB_CHUNK` tokens, then
  the state advanced over the sub-chunk's kept tokens; a `lax.scan` over
  the sub-chunks, so `phi2` of a 1,024-token chunk's queries (40 x 1024
  x 8256 values) is never whole in memory. Plain `jnp`, differentiable
  as written. Products in the inputs' dtype with float32 accumulation;
  decays, weights and the state in float32.
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

from deeplearning4j_tpu.util.compat import tpu_compiler_params

SUB_CHUNK = 128     # tokens whose phi2 a chunk holds at once
VALUE_TILE = 64     # rows of a head's [d, D] state one kernel step holds
_MASKED = -1e30


def state_dim(d: int) -> int:
    """D: the d / 2 + 1 diagonals of a d-vector's symmetric square."""
    return d * (d // 2 + 1)


def _use_kernel() -> bool:
    return jax.default_backend() == "tpu"


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
    pad = -T % C
    keep = (jnp.ones((b, T), jnp.float32) if keep is None
            else keep.astype(jnp.float32))
    log_g = log_g.astype(jnp.float32) * keep[..., None]
    if pad:
        q, k, v, log_g, keep = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, log_g, keep))
    n = (T + pad) // C

    def chunked(a):                 # [b, n * C, ...] -> [n, b, C, ...]
        return jnp.moveaxis(a.reshape((b, n, C) + a.shape[2:]), 1, 0)

    causal = jnp.tril(jnp.ones((C, C), bool))
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
        # the attention form inside the sub-chunk
        sc = jnp.einsum("bchrd,bjhd->bhrcj", qg, kc,
                        preferred_element_type=f32)
        seen = causal[None, :, :, None] & (kp > 0)[:, None, :, None]
        decay = jnp.exp(jnp.where(seen, G[:, :, None] - G[:, None, :],
                                  _MASKED))                 # [b, c, j, Hk]
        w = sc * sc * (1.0 / d) * decay.transpose(0, 3, 1, 2)[:, :, None]
        num = num + jnp.einsum("bhrcj,bjhv->bchrv", w.astype(vc.dtype), vc,
                               preferred_element_type=f32)
        den = den + w.sum(-1).transpose(0, 3, 1, 2)
        y = num / (den + eps)[..., None]
        # the state advanced over the sub-chunk's kept tokens
        tail = jnp.exp(G[:, -1:] - G) * kp[..., None]       # [b, C, Hk]
        out = jnp.exp(G[:, -1])                             # [b, Hk]
        vt = (vc.astype(f32) * tail[..., None]).astype(vc.dtype)
        s_new = out[..., None, None] * s.astype(f32) + jnp.einsum(
            "bjhv,bjhD->bhvD", vt, pk, preferred_element_type=f32)
        z_new = out[..., None] * z.astype(f32) + jnp.einsum(
            "bjh,bjhD->bhD", tail, pk.astype(f32),
            preferred_element_type=f32)
        return ((s_new.astype(s.dtype), z_new.astype(z.dtype)),
                y.reshape(b, C, Hq, d).astype(qc.dtype))

    (s, z), y = jax.lax.scan(
        body, (s, z), tuple(chunked(a) for a in (q, k, v, log_g, keep)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, n * C, Hq, d)
    return y[:, :T], s, z


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
