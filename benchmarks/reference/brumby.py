"""The plain reference of the `brumby` family: float32 `jax.numpy`,
`Precision.HIGHEST`, power retention in its ATTENTION form (every weight
of every earlier key computed, queries in blocks), no state, no cache,
no kernels, no batching, one row at a time. It imports nothing of the
program and takes no array the program made.

Block, pre-norm, no bias anywhere but the gate's, untied head:

    x = embed[tokens]                         no position is added
    L x [ h = x + Ret(N1(x))                  N: RMS norm,
          x = h + Wdown(silu(Wgate N2(h)) * Wup N2(h)) ]
                                              x * rsqrt(mean(x^2) + eps) * g
    logits = Nf(x) Wout                       the whole vocabulary

`Ret(u)`, d = 128, 40 query heads, 8 key-value heads, query head h reads
key-value head c = h // 5 (a line marked A is no key of the published
`config.json`: the configuration's `assumed` holds it):

    q = rot(Nq(u Wq))  [40, d],  k = rot(Nk(u Wk))  [8, d],  v = u Wv  [8, d]
        Nq, Nk: RMS norm over the d of a head, learned gains (A: Qwen3's
        q/k norm, kept); rot: rotary on the two halves, theta 1e6 (A)
    log g = log_sigmoid(u Wg + bg)  [8]       one scalar a key-value head
                                              a token (A: shape and bias)
    for j <= t:  w[t, j] = exp(G[t] - G[j]) * (q_t . k_j / sqrt(d))^2,
                 G[t] = sum_{l <= t} log g_l  (A: the scale, which only
                                               meets eps)
    y_t = sum_j w[t, j] v_j / (sum_j w[t, j] + 1e-6)      (A: eps)
    out = concat_h(y) Wo

The power is even (A: degree 2), so every weight is >= 0 and the sum is a
normaliser. The same function as a recurrence over a fixed state
(S_t = g_t S_(t-1) + phi(k_t) v_t^T with phi the symmetric square; the
program's form) is NOT computed here: the reference never builds phi.
All 40 published layers are retention layers (A: `max_window_layers` 40,
`use_sliding_window` false).

Weights: {"embed", "norm_f", "Wout", and per layer (`layer` below names
them)}; they come from `benchmarks/families/brumby.py`.

Every matrix product goes through `mm`. `mm_highest` is the reference
proper; `mm_fp8` the control: both operands of every product rounded to
float8 (e4m3, one scale a tensor), the nearest precision below the
bfloat16 the configuration states. The gate's product is a product like
any other: the control rounds it too.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512   # queries whose [block, T] weights are held at once
SUM_EPS = 1e-6


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
    """x [T, H, d] at positions [T]: the pair (i, i + d/2) turned by
    positions * theta^(-2i / d)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def retention(u, w, dims, mm):
    """One row u [T, hidden] -> [T, hidden], the attention form."""
    T = u.shape[0]
    Hq, Hk, d = dims["Hq"], dims["Hk"], dims["d"]
    pos = jnp.arange(T)
    q = rot(rms_norm(mm(u, w["Wq"]).reshape(T, Hq, d), w["q_norm"],
                     dims["eps"]), pos, dims["theta"])
    k = rot(rms_norm(mm(u, w["Wk"]).reshape(T, Hk, d), w["k_norm"],
                     dims["eps"]), pos, dims["theta"])
    v = mm(u, w["Wv"]).reshape(T, Hk, d)
    G = jnp.cumsum(jax.nn.log_sigmoid(mm(u, w["Wg"]) + w["bg"]), axis=0)
    B = min(QUERY_BLOCK, T)
    n_blocks = -(-T // B)
    pad = n_blocks * B - T
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(n_blocks, B, Hq, d)
    Gb = jnp.pad(G, ((0, pad), (0, 0))).reshape(n_blocks, B, Hk)
    tb = jnp.arange(n_blocks * B).reshape(n_blocks, B)

    def head(c, qh, Gq, t):
        """Queries qh [B, d] of one head at positions t [B] against all
        T keys of key-value head c."""
        s = mm(qh, k[:, c].T) / jnp.sqrt(float(d))              # [B, T]
        seen = pos[None, :] <= t[:, None]
        decay = jnp.exp(jnp.where(seen, Gq[:, None] - G[None, :, c], -jnp.inf))
        wt = decay * s * s
        return mm(wt, v[:, c]) / (jnp.sum(wt, -1, keepdims=True) + SUM_EPS)

    def block(args):
        qh, Gq, t = args                      # [B, Hq, d], [B, Hk], [B]
        return jnp.stack([head(h // (Hq // Hk), qh[:, h],
                               Gq[:, h // (Hq // Hk)], t)
                          for h in range(Hq)], axis=1)          # [B, Hq, d]

    y = jax.lax.map(block, (qb, Gb, tb)).reshape(n_blocks * B, Hq * d)[:T]
    return mm(y, w["Wo"])


def gated(x, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def layer(x, w, dims, mm=mm_highest):
    """One block on one row x [T, hidden]."""
    eps = dims["eps"]
    h = x + retention(rms_norm(x, w["n1"], eps), w, dims, mm)
    return h + gated(rms_norm(h, w["n2"], eps), w["Wgate"], w["Wup"],
                     w["Wdown"], mm)


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
