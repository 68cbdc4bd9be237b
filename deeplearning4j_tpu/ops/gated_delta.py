"""The gated delta rule: the op family behind `GatedDeltaNetLayer`
(nn/layers/gated_deltanet.py holds the layer's equations; Yang, Kautz and
Hatamizadeh, "Gated Delta Networks", arXiv:2412.06464).

A value head keeps a state S [dk, dv], key-major (S^T k is a value). For
each token, with a decay a = exp(g) (g <= 0) and a write strength beta in
[0, 1]:

    S = a S
    d = beta (v - S^T k)        the correction: what the decayed S reads
    S = S + k d^T               for k, moved toward v
    o = S^T q

Three entries:

* `gated_delta_chunk`: many tokens a row (a prefill chunk, a whole
  sequence), the chunked form of arXiv:2412.06464 section 3. Inside a
  sub-chunk of C tokens, with G_t the sum of the sub-chunk's g up to t and
  S0 the state as the sub-chunk found it, the corrections D [C, dv] solve

      (I + L) D = beta V - (beta exp(G) K) S0,
      L_ts = beta_t (k_t . k_s) exp(G_t - G_s)   for s < t, else 0,

  so with T = (I + L)^-1 (`_unit_lower_inverse`, forward substitution:
  L is strictly lower) D = U - W S0, U = T beta V and W = T beta exp(G) K
  (the WY form). Then

      O   = exp(G) Q S0 + (exp(G_t - G_s) q_t . k_s)_{s <= t} D
      S_C = exp(G_C) S0 + (exp(G_C - G_s) k_s)^T D.

  U, W and the masked products of every sub-chunk are made at once; a
  `lax.scan` carries the state from sub-chunk to sub-chunk. Plain `jnp`,
  float32, every product at full precision; differentiable as written.
* `gated_delta_decode`: one token a row. On a TPU one Pallas kernel,
  `gated_delta_decode` in the device trace: a program instance takes one
  slot and a block of `HEAD_BLOCK` value heads, reads each head's state
  into VMEM once, decays it, reads S^T k, adds the rank-one correction,
  reads S^T q and writes the state back IN PLACE (`input_output_aliases`:
  a donated cache stays one copy). Every product is an exact float32 sum
  on the vector unit: 7 operations a state entry against its 8 bytes, so
  the pass is bound by memory. A slot that is not live computes nothing:
  its state is written back as it was read, bit for bit. Off the TPU its
  `jnp` twin (`gated_delta_decode_jnp`), which tier-1 holds the kernel to
  in interpret mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deeplearning4j_tpu.util.compat import on_tpu as _use_kernel
from deeplearning4j_tpu.util.compat import tpu_compiler_params

SUB_CHUNK = 64      # tokens whose corrections are solved together
HEAD_BLOCK = 8      # value heads one kernel instance takes (512 KB of state)
HIGHEST = jax.lax.Precision.HIGHEST


def _mm(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _unit_lower_inverse(L):
    """(I + L)^-1 for L [..., C, C] strictly lower triangular, one row at
    a time: row i is e_i - L[i] (I + L)^-1, whose rows below i are done."""
    C = L.shape[-1]
    eye = jnp.eye(C, dtype=L.dtype)

    def row(i, T):
        li = jax.lax.dynamic_index_in_dim(L, i, axis=-2, keepdims=False)
        return T.at[..., i, :].set(eye[i] - _mm("...j,...jc->...c", li, T))

    return jax.lax.fori_loop(1, C, row, jnp.broadcast_to(eye, L.shape))


def gated_delta_chunk(q, k, v, g, beta, S, *, keep=None):
    """q, k [b, T, H, dk] and v [b, T, H, dv] (one key per value head),
    g [b, T, H] the log decays (<= 0) and beta [b, T, H] the write
    strengths, S [b, H, dk, dv] the state as the chunk finds it. `keep`
    [b, T]: 0 for a token that is no part of the sequence (the pad of a
    bucket): it writes nothing and decays nothing. -> (o [b, T, H, dv]
    float32, S after the chunk's kept tokens, in S's dtype)."""
    f32 = jnp.float32
    b, T, H, dk = q.shape
    dv = v.shape[-1]
    C = min(SUB_CHUNK, T)
    pad = -T % C
    keep = (jnp.ones((b, T), f32) if keep is None else keep.astype(f32))
    g = g.astype(f32) * keep[..., None]
    beta = beta.astype(f32) * keep[..., None]
    q, k, v = (a.astype(f32) for a in (q, k, v))
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    n = (T + pad) // C

    def chunked(a):             # [b, n * C, H, ...] -> [n, b, H, C, ...]
        a = a.reshape((b, n, C, H) + a.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(a, 1, 0), 3, 2)

    q, k, v, g, beta = (chunked(a) for a in (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=-1)                              # [n, b, H, C]
    causal = jnp.tril(jnp.ones((C, C), bool))
    diff = G[..., :, None] - G[..., None, :]                # t, s
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    L = jnp.where(strict, beta[..., :, None] * decay
                  * _mm("...tk,...sk->...ts", k, k), 0.0)
    Tinv = _unit_lower_inverse(L)
    U = _mm("...ts,...sv->...tv", Tinv, beta[..., None] * v)
    W = _mm("...ts,...sk->...tk", Tinv, (beta * jnp.exp(G))[..., None] * k)
    qk = decay * _mm("...tk,...sk->...ts", q, k)
    qg = q * jnp.exp(G)[..., None]
    kt = k * jnp.exp(G[..., -1:] - G)[..., None]
    last = jnp.exp(G[..., -1])                              # [n, b, H]

    def body(s, xs):
        U_i, W_i, qk_i, qg_i, kt_i, last_i = xs
        D = U_i - _mm("...tk,...kv->...tv", W_i, s)
        o = _mm("...tk,...kv->...tv", qg_i, s) + _mm("...ts,...sv->...tv",
                                                     qk_i, D)
        s = last_i[..., None, None] * s + _mm("...tk,...tv->...kv", kt_i, D)
        return s, o

    s, o = jax.lax.scan(body, S.astype(f32), (U, W, qk, qg, kt, last))
    o = jnp.moveaxis(o, 0, 1)                               # [b, n, H, C, dv]
    o = jnp.swapaxes(o, 2, 3).reshape(b, n * C, H, dv)
    return o[:, :T], s.astype(S.dtype)


# ------------------------------------------------------------ decode step

def gated_delta_decode_jnp(S, q, k, v, a, beta, live=None):
    """The decode step in plain `jnp` (the kernel's twin): S [B, H, dk,
    dv], q and k [B, H, dk], v [B, H, dv], a [B, H] the step's decay (0
    starts a state anew), beta [B, H], live [B] bool (None: all). ->
    (o [B, H, dv] float32, S in its dtype; a row not live keeps its own).
    Sums written as the kernel writes them: exact float32 on any
    backend."""
    f32 = jnp.float32
    q, k, v, a, beta = (x.astype(f32) for x in (q, k, v, a, beta))
    s = S.astype(f32)
    dec = a[..., None, None] * s
    d = beta[..., None] * (v - jnp.sum(k[..., None] * dec, axis=-2))
    new = dec + k[..., None] * d[..., None, :]
    o = jnp.sum(q[..., None] * new, axis=-2)
    if live is not None:
        new = jnp.where(jnp.asarray(live, bool)[:, None, None, None], new, s)
    return o, new.astype(S.dtype)


def _decode_kernel(s_ref, x_ref, r_ref, s_out, o_ref):
    """One (slot, block of value heads): s_ref [1, hb, dk, dv]; x_ref [1,
    hb, 2, dk] holds q and k as lane rows; r_ref [1, hb, 4, dv] holds v
    and, broadcast along the lanes, the decay, beta and whether the slot
    is live. A key place has to lie on a sublane to meet its row of the
    state: q and k are turned into columns by selecting the diagonal of
    their broadcast and summing the lanes (exact, and no transpose)."""
    f32 = jnp.float32
    s = s_ref[0].astype(f32)
    x, r = x_ref[0], r_ref[0]
    dk = x.shape[-1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))

    def column(row):                                # [hb, 1, dk] -> [hb, dk, 1]
        return jnp.sum(jnp.where(eye, row, 0.0), axis=2, keepdims=True)

    q, k = column(x[:, 0:1]), column(x[:, 1:2])
    v, a, beta, live = r[:, 0:1], r[:, 1:2], r[:, 2:3], r[:, 3:4]
    dec = a * s
    d = beta * (v - jnp.sum(k * dec, axis=1, keepdims=True))
    new = dec + k * d
    o_ref[0] = jnp.sum(q * new, axis=1, keepdims=True)
    s_out[0] = jnp.where(live > 0, new, s).astype(s_out.dtype)


def gated_delta_decode_kernel(S, q, k, v, a, beta, live=None, *,
                              interpret=False, head_block=HEAD_BLOCK):
    """`gated_delta_decode_jnp` as one Pallas kernel; S is updated in
    place where the caller donates it."""
    f32 = jnp.float32
    B, H, dk, dv = S.shape
    hb = head_block if H % head_block == 0 else H
    live = (jnp.ones((B,), f32) if live is None
            else jnp.asarray(live, bool).astype(f32))
    x = jnp.stack([q.astype(f32), k.astype(f32)], axis=2)      # [B, H, 2, dk]
    rows = jnp.stack([v.astype(f32)] + [
        jnp.broadcast_to(y[..., None], (B, H, dv))
        for y in (a.astype(f32), beta.astype(f32),
                  jnp.broadcast_to(live[:, None], (B, H)))], axis=2)

    def heads(b, h):
        return b, h, 0, 0

    itemsize = jnp.dtype(S.dtype).itemsize
    s_new, o = pl.pallas_call(
        _decode_kernel,
        grid=(B, H // hb),
        in_specs=[pl.BlockSpec((1, hb, dk, dv), heads),
                  pl.BlockSpec((1, hb, 2, dk), heads),
                  pl.BlockSpec((1, hb, 4, dv), heads)],
        out_specs=[pl.BlockSpec((1, hb, dk, dv), heads),
                   pl.BlockSpec((1, hb, 1, dv), heads)],
        out_shape=[jax.ShapeDtypeStruct(S.shape, S.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, dv), f32)],
        input_output_aliases={0: 0},
        compiler_params=tpu_compiler_params(
            dimension_semantics=("parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=7 * B * H * dk * dv, transcendentals=0,
            bytes_accessed=2 * B * H * dk * dv * itemsize),
        name="gated_delta_decode",
        interpret=interpret,
    )(S, x, rows)
    return o[:, :, 0], s_new


def gated_delta_decode(S, q, k, v, a, beta, live=None):
    """One token a row through the state: the kernel on a TPU, its `jnp`
    twin elsewhere (same arguments and results)."""
    if _use_kernel():
        return gated_delta_decode_kernel(S, q, k, v, a, beta, live)
    return gated_delta_decode_jnp(S, q, k, v, a, beta, live)
