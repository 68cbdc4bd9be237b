"""The plain reference of the `qwen3_next` family (Qwen3-Next): float32
`jax.numpy` under `jax.default_matmul_precision("highest")`, the delta rule
as its TOKEN-BY-TOKEN RECURRENCE (a `lax.scan` over positions, never the
chunked form), full attention as a mask over the whole row, every held
expert applied to every token through a mask, no cache, no kernels, no
batching, one row at a time. It imports nothing of the program and takes
no array the program made. Lines marked A are the configuration's
`assumed`.

    x0 = embed[tokens]                    nothing is added for position
    L x [ h = x + Mix(N1(x))              N: RMS norm, x * rsqrt(mean(x^2)
          x = h + MoE(N2(h)) ]               + 1e-6) * g
    logits = Nf(x) Wout                   untied head over the vocabulary
                                          HELD; no bias anywhere
    A: the published norms are zero-centred, x / rms(x) * (1 + w); the
       gains are held as 1 + w (the layout alone changes)
    layer i is a full-attention layer where (i + 1) % 4 == 0
    (`full_attention_interval` 4), a gated delta-rule layer elsewhere;
    every layer is an expert layer (`decoder_sparse_step` 1)
    A: the multi-token-prediction module is not held

    Attn(u), 16 query heads on 2 key-value heads of 256, query head h
    reads key-value head h // 8:
      q = Nq(u Wq), k = Nk(u Wk), v = u Wv, g = sigmoid(u Wg) [4096]
      A: the published q_proj interleaves each head's query and gate
         columns; here they are Wq and Wg, a permutation of the same
      q, k turned over their first 64 of 256 dimensions, pairs (i, i +
      32), theta 1e7; dimensions 64..255 pass unturned
      o_t = softmax_{j <= t}(q_t . k_j / 16) v_j, scores and softmax float32
      out = (concat_h(o) * g) Wo

    GDN(u), 16 key heads and 32 value heads of 128, value head j reads
    key head j // 2:
      [q | k | v] = u Wqkv (2048 + 2048 + 4096), z = u Wz [32, 128],
      b = u Wb [32], a = u Wa [32]
      A: the published in_proj_qkvz / in_proj_ba group these per key
         head; here they are four matrices, a permutation of the same
      c_t = silu(sum_{i=0..3} w_i * [q|k|v]_{t-3+i})   depthwise over the
            8,192 channels, causal, no bias; inputs before 0 are zero
      q = l2n(c_q) / sqrt(128), k = l2n(c_k), v = c_v,
            l2n(x) = x * rsqrt(sum x^2 + 1e-6)
      beta = sigmoid(b), g = -exp(A_log) * softplus(a + dt_bias)
      for each token, a state S_j [128, 128] a value head, from 0:
        S = exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S = S + k_t d^T;
        o_t = S^T q_t
      y = RMSNorm_128(o) * w_n * silu(z) per value head; out = concat(y) Wo

    MoE(u): the logits l = u_f32 Wr_f32 over ALL E = 512 experts; the
      top_k = 10 are the 10 largest, weighed by the softmax over those
      10 (`norm_topk_prob`; no scaling); y = sum over the selected experts
      HELD (first_expert .. first_expert + n_held - 1) of w_e E_e(u)
      + sigmoid(u Wsg) Shared(u), Wsg [2048, 1]; every expert a gated
      block Wdown(silu(Wgate u) * (Wup u)), 512 wide.

Departures from the published model, all the configuration's (its
`assumed` and `reduced` lists), none the reference's own: one chip's
share of a 32-chip expert-parallel deployment (the routed sum runs over
the held experts only and what the absent ones would add is left out;
the vocabulary is the slice held: ids, logits, argmax); eight of the 48
layers; seeded weights.

Weights: {"embed", "norm_f", "Wout", and per layer (`gdn_layer`,
`attn_layer` below name them)}; they come from
`benchmarks/families/qwen3_next.py`.

Every matrix product goes through `mm`. `mm_highest` is the reference
proper; `mm_fp8` the control: both operands of every product rounded to
float8 (e4m3, one scale a tensor), the nearest precision below the
bfloat16 the configuration states. The router's product and the two
products of attention are products like any other: the control rounds
them too. The recurrence's sums over a state are no product of two
tensors: they are float32 in both, as the configuration states the
state.

A row may be taken in pieces (`block_rows`: rows t0 .. t0 + P - 1 with
the layer's carry as the row so far left it: a delta-rule layer's state
and last three inputs, a full layer's keys and values), so that a
36,864-token row's scores fit, and so that ONE compiled program a kind of
layer serves every length. `forward` is one piece, the whole row.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
L2_EPS = 1e-6


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


def rot(x, positions, theta, r):
    """x [T, H, d] at positions [T]: of the first r dimensions the pair
    (i, i + r/2) turned by positions * theta^(-2i / r); the rest pass."""
    half = r // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = (positions.astype(jnp.float32)[:, None] * freq)[:, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., :half], x[..., half:r]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., r:]], -1)


def gated(x, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def attention(x, K, V, t0, w, dims, mm):
    """Rows t0 .. t0 + P - 1 of one sequence: x [P, hidden] -> ([P,
    hidden], K, V). K, V [S, Hk, d] hold the layer's keys and values of
    the rows before t0; S >= t0 + P. Attention goes one key-value head
    at a time."""
    P, S = x.shape[0], K.shape[0]
    Hq, Hk, d = dims["Hq"], dims["Hk"], dims["d"]
    G = Hq // Hk
    pos = t0 + jnp.arange(P)
    q = rms_norm(mm(x, w["Wq"]).reshape(P, Hq, d), w["q_norm"], dims["eps"])
    k = rms_norm(mm(x, w["Wk"]).reshape(P, Hk, d), w["k_norm"], dims["eps"])
    v = mm(x, w["Wv"]).reshape(P, Hk, d)
    gate = jax.nn.sigmoid(mm(x, w["Wg"]))
    q = rot(q, pos, dims["theta"], dims["rotary"])
    k = rot(k, pos, dims["theta"], dims["rotary"])
    K = jax.lax.dynamic_update_slice(K, k, (t0, 0, 0))
    V = jax.lax.dynamic_update_slice(V, v, (t0, 0, 0))
    seen = jnp.arange(S)[None, :] <= pos[:, None]                 # [P, S]

    def head(a):
        qc, kc, vc = a                          # [G, P, d], [S, d], [S, d]
        s = mm(qc, kc.T) / jnp.sqrt(jnp.float32(d))
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return mm(p, vc)                        # [G, P, d]

    o = jax.lax.map(head, (q.reshape(P, Hk, G, d).transpose(1, 2, 0, 3),
                           K.transpose(1, 0, 2), V.transpose(1, 0, 2)))
    o = o.transpose(2, 0, 1, 3).reshape(P, Hq * d)
    return mm(o * gate, w["Wo"]), K, V


def _l2n(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_rule(x, S, win, w, dims, mm):
    """Rows of one sequence, x [P, hidden] (P >= 3), with the state S
    [Hv, dk, dv] and the convolution's last inputs win [3, channels] as
    the rows before them left them -> ([P, hidden], S, win)."""
    P = x.shape[0]
    Hk, Hv, dk, dv = dims["Hk_lin"], dims["Hv_lin"], dims["dk"], dims["dv"]
    R = Hv // Hk
    u = mm(x, w["Wqkv"])
    ext = jnp.concatenate([win, u])
    K = w["conv"].shape[0]
    c = jax.nn.silu(sum(w["conv"][i] * ext[i:i + P] for i in range(K)))
    q = _l2n(c[:, :Hk * dk].reshape(P, Hk, dk)) / jnp.sqrt(jnp.float32(dk))
    k = _l2n(c[:, Hk * dk:2 * Hk * dk].reshape(P, Hk, dk))
    v = c[:, 2 * Hk * dk:].reshape(P, Hv, dv)
    beta = jax.nn.sigmoid(mm(x, w["Wb"]))
    g = -jnp.exp(w["A_log"]) * jax.nn.softplus(mm(x, w["Wa"]) + w["dt_bias"])

    def step(S, t):
        q_t, k_t, v_t, g_t, b_t = t
        qh, kh = jnp.repeat(q_t, R, axis=0), jnp.repeat(k_t, R, axis=0)
        S = jnp.exp(g_t)[:, None, None] * S
        d = b_t[:, None] * (v_t - jnp.sum(kh[:, :, None] * S, axis=1))
        S = S + kh[:, :, None] * d[:, None, :]
        return S, jnp.sum(qh[:, :, None] * S, axis=1)

    S, o = jax.lax.scan(step, S, (q, k, v, g, beta))
    z = mm(x, w["Wz"]).reshape(P, Hv, dv)
    y = rms_norm(o, w["norm"], dims["eps"]) * jax.nn.silu(z)
    return mm(y.reshape(P, Hv * dv), w["Wo"]), S, ext[P:]


def experts(x, w, dims, mm):
    """The expert layer on x [T, hidden]: every held expert runs on
    every token, and a mask keeps the pairs the router selected."""
    top_l, top_i = jax.lax.top_k(mm(x, w["Wr"]), dims["top_k"])    # [T, k]
    wts = jax.nn.softmax(top_l, axis=-1)
    held = w["We_gate"].shape[0]

    def one(y, e):
        g = jnp.sum(jnp.where(top_i == dims["first_expert"] + e, wts, 0.0), -1)
        out = gated(x, w["We_gate"][e], w["We_up"][e], w["We_down"][e], mm)
        return y + g[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    shared = gated(x, w["Ws_gate"], w["Ws_up"], w["Ws_down"], mm)
    return y + jax.nn.sigmoid(mm(x, w["Ws_g"])) * shared


def block_rows(x, carry, w, dims, mm=mm_highest, t0=0):
    """One block on rows t0 .. t0 + P - 1 of one sequence, x [P, hidden],
    with the layer's carry as the row so far left it (`empty_carry`) ->
    (y, carry); `w` holds a delta-rule layer's mixer (`Wqkv`) or a full
    layer's (`Wq`)."""
    eps = dims["eps"]
    u = rms_norm(x, w["n1"], eps)
    if "Wqkv" in w:
        a, *carry = delta_rule(u, *carry, w, dims, mm)
    else:
        a, *carry = attention(u, *carry, t0, w, dims, mm)
    h = x + a
    return h + experts(rms_norm(h, w["n2"], eps), w, dims, mm), tuple(carry)


def empty_carry(full: bool, n, dims):
    """A layer's carry before a row's first piece: a full layer's keys
    and values of a row of `n` positions, none written; a delta-rule
    layer's zero state and zero inputs before position 0."""
    if full:
        shape = (n, dims["Hk"], dims["d"])
        return jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32)
    channels = 2 * dims["Hk_lin"] * dims["dk"] + dims["Hv_lin"] * dims["dv"]
    return (jnp.zeros((dims["Hv_lin"], dims["dk"], dims["dv"]), jnp.float32),
            jnp.zeros((dims["conv"] - 1, channels), jnp.float32))


def logits_at(x, at, norm_f, Wout, dims, mm=mm_highest):
    """Logits [len(at), V] of the rows `at` of the last layer's x."""
    return mm(rms_norm(x[at], norm_f, dims["eps"]), Wout)


def forward(W, tokens, dims, mm=mm_highest):
    """Logits [T, V] of one row of tokens [T], all weights at once (the
    tests' sizes): W = {"embed", "norm_f", "Wout", "layers": [per layer]};
    layer l is a full-attention layer where dims["full"][l]."""
    with jax.default_matmul_precision("highest"):
        x = W["embed"][tokens]
        T = tokens.shape[0]
        for w, full in zip(W["layers"], dims["full"]):
            x, _ = block_rows(x, empty_carry(full, T, dims), w, dims, mm)
        return logits_at(x, jnp.arange(T), W["norm_f"], W["Wout"], dims, mm)


def served_gap(lg, served, valid):
    """By how much each served token's logit lies below the best of its
    row of `lg` [n, V]; 0 where it is the reference's own choice."""
    gap = jnp.max(lg, -1) - jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
    return jnp.where(valid, gap, 0.0)
