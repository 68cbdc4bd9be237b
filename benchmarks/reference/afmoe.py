"""The plain reference of the `afmoe` family (Arcee Trinity): float32
`jax.numpy` under `jax.default_matmul_precision("highest")`, the window
as a mask over the whole sequence, every held expert applied to every
token through a mask, no cache, no ring, no kernels, no batching, one
row at a time. It imports nothing of the program and takes no array the
program made. Lines marked A are the configuration's `assumed`.

    x0 = embed[tokens] * sqrt(hidden)        A: `mup_enabled` read as the
                                             embedding's scale alone;
                                             nothing is added for position
    L x [ h = x + N2(Attn(N1(x)))            N: RMS norm with a learned gain,
          x = h + N4(FF(N3(h))) ]            x * rsqrt(mean(x^2) + eps) * g
    logits = Nf(x) Wout                      A: four norms a layer placed so
                                             ("sandwich norm"; depth scaling
                                             is the initialiser's); untied
                                             head over the vocabulary HELD;
                                             no bias anywhere

    Attn(u), Hq query heads on Hk key-value heads of d, query head h
    reads key-value head h // (Hq / Hk):
      q = RMSq(u Wq) [Hq, d], k = RMSk(u Wk) [Hk, d], v = u Wv [Hk, d],
      g = sigmoid(u Wg) [Hq * d]             A: RMSq, RMSk over a head's d,
                                             one learned gain vector each
      a SLIDING layer (`layer_types[l] == "sliding_attention"`):
        q, k = rot(q), rot(k), theta 10000   A: pairs as the two halves of d;
        query t sees keys t - window < j <= t  no scaling. A: the window
                                             counts the query's own key
      a FULL layer: NO position at all (A); query t sees every j <= t
      o_t = softmax_j(q_t . k_j / sqrt(d)) v_j, scores and softmax float32
      out = (concat_h(o) * g) Wo             A: the gate multiplies before
                                             the output projection

    FF, leading `n_dense` layers:  (silu(u Wgate) * (u Wup)) Wdown
    FF, the rest:  s = sigmoid(u Wr) over ALL E experts, float32;
      the top_k experts are the largest of s + b, b [E] the router's
      selection bias (A: a float32 buffer the published training moves
      towards balance; here seeded); their weights are s WITHOUT b,
      divided by their sum + 1e-20 (`route_norm`), times `route_scale`;
      y = sum over the selected experts HELD (first_expert ..
      first_expert + n_held - 1) of w_e E_e(u), + Shared(u); every expert
      and the shared one a gated block like the dense FF. `n_group` and
      `topk_group` are 1: no group limit.

Departures from the published model, all the configuration's (its
`assumed` and `reduced` lists), none the reference's own: one chip's
share of a 32-chip expert-parallel deployment (the routed sum runs over
the held experts only and what the absent ones would add is left out;
the vocabulary is the slice held: ids, logits, argmax); eight of the
sixty layers; seeded weights.

Weights: {"embed", "norm_f", "Wout", and per layer (`layer` below names
them)}; they come from `benchmarks/families/afmoe.py`.

Every matrix product goes through `mm`. `mm_highest` is the reference
proper; `mm_fp8` the control: both operands of every product rounded to
float8 (e4m3, one scale a tensor), the nearest precision below the
bfloat16 the configuration states. The router's product and the two
products of attention are products like any other: the control rounds
them too.

A row may be taken in pieces (`block_rows`: rows t0 .. t0 + P - 1 against
the keys and values of the whole row so far, which the caller carries
from piece to piece), so that a 17,408-token row's scores fit, and so
that ONE compiled program a kind of feed-forward block serves every
length and both kinds of attention layer (`sliding` may be a traced
flag: on the chip a program with float32 products takes a quarter of a
minute to compile, whatever its size). `forward` is one piece, the
whole row. Attention goes one key-value head at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


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
    angle = (positions.astype(jnp.float32)[:, None] * freq)[:, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gated(x, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def attention(x, K, V, t0, sliding, w, dims, mm):
    """Rows t0 .. t0 + P - 1 of one sequence: x [P, hidden] -> ([P,
    hidden], K, V). K, V [S, Hk, d] hold this layer's keys and values of
    the rows before t0 (what lies at t0 and beyond is overwritten or not
    seen); S >= t0 + P. `sliding`: a window layer (rotary position and
    the window) or a full one (neither); a flag, which may be traced."""
    P, S = x.shape[0], K.shape[0]
    Hq, Hk, d, W = dims["Hq"], dims["Hk"], dims["d"], dims["window"]
    G = Hq // Hk
    pos = t0 + jnp.arange(P)
    q = rms_norm(mm(x, w["Wq"]).reshape(P, Hq, d), w["q_norm"], dims["eps"])
    k = rms_norm(mm(x, w["Wk"]).reshape(P, Hk, d), w["k_norm"], dims["eps"])
    v = mm(x, w["Wv"]).reshape(P, Hk, d)
    gate = jax.nn.sigmoid(mm(x, w["Wg"]))
    q = jnp.where(sliding, rot(q, pos, dims["theta"]), q)
    k = jnp.where(sliding, rot(k, pos, dims["theta"]), k)
    K = jax.lax.dynamic_update_slice(K, k, (t0, 0, 0))
    V = jax.lax.dynamic_update_slice(V, v, (t0, 0, 0))
    key = jnp.arange(S)
    seen = key[None, :] <= pos[:, None]                          # [P, S]
    seen = seen & (jnp.logical_not(sliding) | (key[None, :] > pos[:, None] - W))

    def head(a):
        qc, kc, vc = a                          # [G, P, d], [S, d], [S, d]
        s = mm(qc, kc.T) / jnp.sqrt(jnp.float32(d))
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return mm(p, vc)                        # [G, P, d]

    o = jax.lax.map(head, (q.reshape(P, Hk, G, d).transpose(1, 2, 0, 3),
                           K.transpose(1, 0, 2), V.transpose(1, 0, 2)))
    o = o.transpose(2, 0, 1, 3).reshape(P, Hq * d)
    return mm(o * gate, w["Wo"]), K, V


def experts(x, w, dims, mm):
    """The expert layer on x [T, hidden]: every held expert runs on
    every token, and a mask keeps the pairs the router selected."""
    s = jax.nn.sigmoid(mm(x, w["Wr"]))                           # [T, E]
    _, top_i = jax.lax.top_k(s + w["bsel"], dims["top_k"])
    top_s = jnp.take_along_axis(s, top_i, axis=-1)
    wts = top_s / (jnp.sum(top_s, -1, keepdims=True) + 1e-20) * dims["scaling"]
    held = w["We_gate"].shape[0]

    def one(y, e):
        # this expert's weight for each token: 0 where it was not selected
        g = jnp.sum(jnp.where(top_i == dims["first_expert"] + e, wts, 0.0), -1)
        out = gated(x, w["We_gate"][e], w["We_up"][e], w["We_down"][e], mm)
        return y + g[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    return y + gated(x, w["Ws_gate"], w["Ws_up"], w["Ws_down"], mm)


def block_rows(x, K, V, t0, sliding, w, dims, mm=mm_highest):
    """One block on rows t0 .. t0 + P - 1 of one sequence, x [P,
    hidden], with the layer's keys and values of the row so far (K, V:
    `attention`) -> (y, K, V); `w` holds a dense layer's feed-forward
    (`Wgate`) or an expert layer's (`Wr`)."""
    eps = dims["eps"]
    a, K, V = attention(rms_norm(x, w["n1"], eps), K, V, t0, sliding, w,
                        dims, mm)
    h = x + rms_norm(a, w["n2"], eps)
    f = rms_norm(h, w["n3"], eps)
    f = (gated(f, w["Wgate"], w["Wup"], w["Wdown"], mm) if "Wgate" in w
         else experts(f, w, dims, mm))
    return h + rms_norm(f, w["n4"], eps), K, V


def empty_rows(n, dims):
    """The keys and values of a row of `n` positions, none written."""
    shape = (n, dims["Hk"], dims["d"])
    return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)


def layer(x, w, dims, sliding, mm=mm_highest):
    """One block on one whole row x [T, hidden]: `block_rows` in one
    piece."""
    return block_rows(x, *empty_rows(x.shape[0], dims), 0, sliding, w, dims,
                      mm)[0]


def embed(table, tokens, dims):
    return table[tokens] * jnp.sqrt(jnp.float32(dims["hidden"]))


def logits_at(x, at, norm_f, Wout, dims, mm=mm_highest):
    """Logits [len(at), V] of the rows `at` of the last layer's x."""
    return mm(rms_norm(x[at], norm_f, dims["eps"]), Wout)


def forward(W, tokens, dims, mm=mm_highest):
    """Logits [T, V] of one row of tokens [T], all weights at once (the
    tests' sizes): W = {"embed", "norm_f", "Wout", "layers": [per layer]};
    layer l is a window layer where dims["sliding"][l]."""
    with jax.default_matmul_precision("highest"):
        x = embed(W["embed"], tokens, dims)
        for w, sliding in zip(W["layers"], dims["sliding"]):
            x = layer(x, w, dims, sliding, mm)
        return logits_at(x, jnp.arange(tokens.shape[0]), W["norm_f"],
                         W["Wout"], dims, mm)


def served_gap(lg, served, valid):
    """By how much each served token's logit lies below the best of its
    row of `lg` [n, V]; 0 where it is the reference's own choice."""
    gap = jnp.max(lg, -1) - jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
    return jnp.where(valid, gap, 0.0)
