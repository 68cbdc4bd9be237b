"""The plain reference of the `ouro` family (ByteDance Ouro, a looped
language model): float32 `jax.numpy` under
`jax.default_matmul_precision("highest")`, the loop run literally pass
after pass, no cache, no kernels, no batching, one row at a time. It
imports nothing of the program and takes no array the program made.
Lines marked A are the configuration's `assumed`.

    x0 = E[id]                               nothing added for position, no
                                             embedding scale. A: no bias
                                             anywhere (the config has no
                                             bias key)
    Block i (i = 0 .. L-1), its weights shared by every pass.
    A: sandwich norms, four RMS norms a block (eps 1e-6):
      h = x + N2_i(Attn_i(N1_i(x)))         N: x * rsqrt(mean(x^2) + eps) * g
      y = h + N4_i(FF_i(N3_i(h)))
    Attn: Hq query heads and Hk key-value heads of d (16, 16, 128);
      q = u Wq, k = u Wk, v = u Wv           A: no per-head norm
      q, k = rot(q), rot(k)                  rotary over the whole head, pairs
                                             (i, i + d/2), theta 1e6, no
                                             scaling (rope_scaling null)
      o_t = softmax_{j <= t}(q_t . k_j / sqrt(d)) v_j, scores and softmax
                                             in float32
      out = concat_h(o) Wo                   no output gate
    FF: (silu(u Wgate) * (u Wup)) Wdown, 5632 wide
    The loop: x^(0) = x0, x^(t+1) = Nf(Block_{L-1}(... Block_0(x^(t))))
      for t = 0 .. times - 1 (times = total_ut_steps = 4). A: the final
      norm Nf closes every pass, and its output is the next pass's input.
    logits = x^(times) Wout                  untied, [hidden, vocab], over the
                                             last pass
    The cache (the program's; this reference has none): pass t of block i
      attends the keys and values that pass t of block i computed at the
      earlier positions. A: one entry a (layer, pass); every pass sees a
      token at the same position.
    The exit gate: early_exit_threshold 1, so every token runs all four
      passes and the head reads the last; the gate's projection changes
      nothing and is not held (A).

Weights: {"embed", "norm_f", "Wout", and per block (`block` below names
them)}; they come from `benchmarks/families/ouro.py`, which makes them a
block at a time (`served_gaps` on the chip: the float32 copy of all of
them, 10.7 GB, is made a block at a time for each pass and dropped).

Every matrix product goes through `mm`. `mm_highest` is the reference
proper; `mm_fp8` the control: both operands of every product rounded to
float8 (e4m3, one scale a tensor), the nearest precision below the
bfloat16 the configuration states. The two products of attention are
products like any other: the control rounds them too.

`fault` runs one of three wrong models in the program's place, each a
mistake a server of this model could make: "one_pass" (the stack once,
then Nf and the head), "shared_rows" (one cache shared by the passes:
pass t >= 1 of block i attends the keys and values of pass 0 of block i,
its own at no position), "norm_last" (Nf after the last pass only).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FAULTS = ("one_pass", "shared_rows", "norm_last")


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


def attention(u, w, dims, mm, kv_u=None):
    """One row u [S, hidden] (normed) -> [S, hidden], every position t
    seeing the keys j <= t. `kv_u`: the input whose keys and values are
    attended in place of u's own (the "shared_rows" fault)."""
    S = u.shape[0]
    Hq, Hk, d = dims["Hq"], dims["Hk"], dims["d"]
    G = Hq // Hk
    pos = jnp.arange(S)
    src = u if kv_u is None else kv_u
    q = rot(mm(u, w["Wq"]).reshape(S, Hq, d), pos, dims["theta"])
    k = rot(mm(src, w["Wk"]).reshape(S, Hk, d), pos, dims["theta"])
    v = mm(src, w["Wv"]).reshape(S, Hk, d)
    seen = pos[None, :] <= pos[:, None]                         # [S, S]

    def head(a):
        qc, kc, vc = a                          # [G, S, d], [S, d], [S, d]
        s = mm(qc, kc.T) / jnp.sqrt(jnp.float32(d))
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return mm(p, vc)                        # [G, S, d]

    o = jax.lax.map(head, (q.reshape(S, Hk, G, d).transpose(1, 2, 0, 3),
                           k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return mm(o.transpose(2, 0, 1, 3).reshape(S, Hq * d), w["Wo"])


def gated(x, w_gate, w_up, w_down, mm):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


def block(x, w, dims, mm=mm_highest, kv_x=None):
    """One block on one whole row x [S, hidden] -> [S, hidden]. `kv_x`:
    the block's input in pass 0, whose keys and values pass t attends
    in the "shared_rows" fault (None: its own)."""
    eps = dims["eps"]
    kv_u = None if kv_x is None else rms_norm(kv_x, w["n1"], eps)
    h = x + rms_norm(attention(rms_norm(x, w["n1"], eps), w, dims, mm, kv_u),
                     w["n2"], eps)
    f = gated(rms_norm(h, w["n3"], eps), w["Wgate"], w["Wup"], w["Wdown"], mm)
    return h + rms_norm(f, w["n4"], eps)


def passes_of(dims, fault=None) -> int:
    return 1 if fault == "one_pass" else dims["times"]


def closes_pass(dims, t, fault=None) -> bool:
    """Whether Nf follows pass t."""
    return fault != "norm_last" or t == passes_of(dims, fault) - 1


def forward(W, tokens, dims, mm=mm_highest, fault=None):
    """Logits [T, V] of one row of tokens [T], all weights at once (the
    tests' sizes): W = {"embed", "norm_f", "Wout", "layers": [per
    block]}, the loop run `dims["times"]` times (or the `fault`'s
    model)."""
    with jax.default_matmul_precision("highest"):
        x = W["embed"][tokens]
        first = []
        for t in range(passes_of(dims, fault)):
            for i, w in enumerate(W["layers"]):
                if t == 0:
                    first.append(x)
                kv_x = first[i] if fault == "shared_rows" and t else None
                x = block(x, w, dims, mm, kv_x)
            if closes_pass(dims, t, fault):
                x = rms_norm(x, W["norm_f"], dims["eps"])
        return mm(x, W["Wout"])


def served_gap(lg, served, valid):
    """By how much each served token's logit lies below the best of its
    row of `lg` [n, V]; 0 where it is the reference's own choice."""
    gap = jnp.max(lg, -1) - jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
    return jnp.where(valid, gap, 0.0)
