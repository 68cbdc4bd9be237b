"""The plain reference of the `pangu_ultra_moe` family: float32
`jax.numpy`, `Precision.HIGHEST`, keys and values expanded for the whole
row (no latent-space shortcut), every held expert applied to every token
through a mask, no cache, no kernels, no batching, one row at a time. It
imports nothing of the program and takes no array the program made.

    x = embed[tokens]                           no position is added
    L x [ h = x + N2(Attn(N1(x)))               N: RMS norm,
          x = h + N4(FF(N3(h))) ]               x * rsqrt(mean(x^2) + eps) * g
    logits = Nf(x) Wout                         over the vocabulary HELD

    Attn(x):  cq = Nq(x Wqa); [q_nope | q_pe] = cq Wqb per head
              [ckv | k_pe] = x Wkva; ckv = Nkv(ckv)
              [k_nope | v] = ckv Wkvb per head
              score = (q_nope . k_nope + rot(q_pe) . rot(k_pe))
                      / sqrt(nope + rope)
              causal softmax; heads concatenated; Wo
    FF, leading `n_dense` layers:  (silu(x Wgate) * (x Wup)) Wdown
    FF, the rest:  s = sigmoid(x Wg) over ALL experts; the top_k largest;
              w = s_top / (sum of the top_k + 1e-20) * routed_scaling
              sum over the selected experts HELD (first_expert ..
              first_expert + n_held - 1) of w_e E_e(x), + Shared(x); every
              expert and the shared one a gated block like the dense FF

Departures from the published model, all the configuration's (its
`assumed` and `reduced` lists), none the reference's own:
- one chip's share of a 16-chip expert-parallel deployment: the routed sum
  runs over the held experts only and what the absent ones would add is
  left out; the vocabulary is the slice held (ids, logits, argmax);
- the multi-token-prediction module is not held;
- `rot` pairs element i with i + rope/2 (the two halves of the slice), a
  fixed permutation of the published interleaved pairing: the same model
  under seeded weights;
- sigmoid scores with no selection bias and no group limit; sandwich norms
  placed as above; softmax scale (nope + rope)^-0.5; no rotary scaling.

Weights: {"embed", "norm_f", "Wout", and per layer (`layer_weights`
below names them)}; they come from `benchmarks/families/pangu_ultra_moe.py`.

Every matrix product goes through `mm`. `mm_highest` is the reference
proper; `mm_fp8` the control: both operands of every product rounded to
float8 (e4m3, one scale a tensor), the nearest precision below the
bfloat16 the configuration states. The router's product is a product like
any other: the control rounds it too.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
HEAD_GROUP = 8      # heads whose [T, T] scores are held at once


def mm_highest(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return q * scale


def mm_fp8(a, b):
    return jnp.matmul(_fp8(a), _fp8(b), precision=HIGHEST)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rot(x, positions, theta):
    """x [T, ..., R] at positions [T]: the pair (i, i + R/2) turned by
    positions * theta^(-2i / R)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * freq       # [T, R/2]
    angle = angle.reshape((angle.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gated(x, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def attention(x, w, dims, mm):
    """One row x [T, d] -> [T, d]."""
    T = x.shape[0]
    H, c = dims["H"], dims["kv_rank"]
    n, r, v = dims["nope"], dims["rope"], dims["v"]
    pos = jnp.arange(T)
    cq = rms_norm(mm(x, w["Wqa"]), w["q_norm"], dims["eps"])
    q = mm(cq, w["Wqb"]).reshape(T, H, n + r)
    q_nope, q_pe = q[..., :n], rot(q[..., n:], pos, dims["theta"])
    kva = mm(x, w["Wkva"])
    ckv = rms_norm(kva[:, :c], w["kv_norm"], dims["eps"])
    k_pe = rot(kva[:, c:], pos, dims["theta"])                   # [T, r]
    kv = mm(ckv, w["Wkvb"]).reshape(T, H, n + v)
    k_nope, val = kv[..., :n], kv[..., n:]
    causal = jnp.tril(jnp.ones((T, T), bool))

    def heads(args):
        qn, qp, kn, vv = args                        # [G, T, .]
        s = (mm(qn, jnp.swapaxes(kn, -1, -2))
             + mm(qp, k_pe.T)) / jnp.sqrt(float(n + r))
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return mm(p, vv)                             # [G, T, v]

    G = HEAD_GROUP if H % HEAD_GROUP == 0 else 1

    def grouped(t):                         # [T, H, .] -> [H/G, G, T, .]
        t = t.transpose(1, 0, 2)
        return t.reshape(H // G, G, T, t.shape[-1])

    o = jax.lax.map(heads, (grouped(q_nope), grouped(q_pe),
                            grouped(k_nope), grouped(val)))
    o = o.reshape(H, T, v).transpose(1, 0, 2).reshape(T, H * v)
    return mm(o, w["Wo"])


def experts(x, w, dims, mm):
    """The expert layer on x [T, d]: every held expert runs on every
    token, and a mask keeps the pairs the router selected."""
    s = jax.nn.sigmoid(mm(x, w["Wg"]))                           # [T, E]
    top_s, top_i = jax.lax.top_k(s, dims["top_k"])
    wts = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20) * dims["scaling"]
    held = w["We_gate"].shape[0]

    def one(y, e):
        # this expert's weight for each token: 0 where it was not selected
        g = jnp.sum(jnp.where(top_i == dims["first_expert"] + e, wts, 0.0), -1)
        out = gated(x, w["We_gate"][e], w["We_up"][e], w["We_down"][e], mm)
        return y + g[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    return y + gated(x, w["Ws_gate"], w["Ws_up"], w["Ws_down"], mm)


def layer(x, w, dims, mm=mm_highest):
    """One block on one row x [T, d]; `w` holds a dense layer's
    feed-forward (`Wgate`) or an expert layer's (`Wg`)."""
    eps = dims["eps"]
    h = x + rms_norm(attention(rms_norm(x, w["n1"], eps), w, dims, mm),
                     w["n2"], eps)
    f = rms_norm(h, w["n3"], eps)
    f = (gated(f, w["Wgate"], w["Wup"], w["Wdown"], mm) if "Wgate" in w
         else experts(f, w, dims, mm))
    return h + rms_norm(f, w["n4"], eps)


def logits_at(x, at, norm_f, Wout, dims, mm=mm_highest):
    """Logits [len(at), V] of the rows `at` of the last layer's x."""
    return mm(rms_norm(x[at], norm_f, dims["eps"]), Wout)


def forward(W, tokens, dims, mm=mm_highest):
    """Logits [T, V] of one row of tokens [T], all weights at once (the
    tests' sizes): W = {"embed", "norm_f", "Wout", "layers": [per layer]}."""
    x = W["embed"][tokens]
    for w in W["layers"]:
        x = layer(x, w, dims, mm)
    return logits_at(x, jnp.arange(tokens.shape[0]), W["norm_f"], W["Wout"],
                     dims, mm)


def served_gap(lg, served, valid):
    """By how much each served token's logit lies below the best of its
    row of `lg` [n, V]; 0 where it is the reference's own choice."""
    gap = jnp.max(lg, -1) - jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
    return jnp.where(valid, gap, 0.0)
