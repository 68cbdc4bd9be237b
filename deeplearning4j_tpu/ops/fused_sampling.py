"""Fused on-device sampling — temperature/top-k/top-p in one pass.

The serving decode loop's sampling used to be the classic host round
trip G019/G024 police: pull the whole [B, V] logits row home, softmax
and argsort in numpy, `np.random.choice` per slot — one device->host
transfer plus host-side O(V log V) work per emitted token. This module
keeps the whole chain on device and returns only the [B] token ids (the
batch-boundary fetch the decode loop already pays for).

Design:

* The sample is REPARAMETERIZED: the caller supplies per-(row, vocab)
  Gumbel noise (``jax.random.gumbel`` — device-side, generated from the
  engine's PRNG key, never host randomness), and the op is a pure
  deterministic function of (logits, noise). ``argmax(z + gumbel)``
  over the kept set IS a categorical sample over it — so the kernel
  needs no in-kernel RNG and the off-TPU fallback is bit-identical by
  construction (the same math runs in interpret mode / the jnp
  reference).
* Temperature scales the centered logits (f32); top-k and top-p
  restrict the kept set via vectorized THRESHOLD BISECTION (no sort:
  a fixed 24-step binary search per row finds the k-th-largest logit /
  the nucleus probability cutoff — deterministic, branch-free, and
  kernel-friendly). Ties at the threshold are kept (the standard
  "at least k" convention).
* ``temperature == 0`` is greedy and returns ``jnp.argmax(logits, -1)``
  EXACTLY — bit-identical to the argmax the decode step always did.

Dispatch follows the fused_layernorm idiom: a Pallas kernel (one
[rows, V] block per program, f32 accumulation, row block resolved
through the ``sample`` autotune family) inside its `supports()`
envelope (V a lane-tile multiple, rows legal for the (1, bn) token
row); outside it — including the tiny-vocab serving LM — the SAME math
runs as the pure-jnp reference. Off-TPU the kernel runs in interpret
mode, so CPU tier-1 exercises the identical code path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deeplearning4j_tpu.ops import autotune

_NEG_INF = -1e30
_BISECT_STEPS = 24


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def supports(batch: int, vocab: int) -> bool:
    """Whether the Pallas kernel's envelope covers a [batch, vocab]
    logits block: lane-tiled vocab, sublane-tiled rows, and a legal
    (1, bn) token-row block (the fused_layernorm stat-row rule)."""
    if vocab % autotune.LANES != 0 or batch % 8 != 0:
        return False
    bn = autotune.sample_rows(batch, vocab)
    return bn % autotune.LANES == 0 or bn == batch


def _select_body(logits, noise, temperature, top_k, top_p):
    """The shared selection math (kernel body AND jnp reference run
    exactly this): centered/temperature-scaled logits, top-k and top-p
    keep-masks via threshold bisection, Gumbel-perturbed argmax.
    logits/noise [bn, V]; returns token ids [bn] int32. f32 throughout."""
    lf = logits.astype(jnp.float32)
    V = lf.shape[-1]
    m = jnp.max(lf, axis=-1, keepdims=True)
    z = (lf - m) / jnp.float32(temperature)            # max row value: 0
    keep = jnp.ones(z.shape, jnp.bool_)
    if top_k and top_k < V:
        # largest threshold t with count(z >= t) >= k: after the
        # bisection `lo` sits just below the k-th largest value, so
        # `z >= lo` keeps the top k (plus exact ties)
        lo = jnp.min(z, axis=-1) - 1.0
        hi = jnp.zeros(z.shape[:-1], jnp.float32) + 1e-6
        for _ in range(_BISECT_STEPS):
            mid = 0.5 * (lo + hi)
            cnt = jnp.sum((z >= mid[..., None]).astype(jnp.float32), -1)
            ge = cnt >= float(top_k)
            lo = jnp.where(ge, mid, lo)
            hi = jnp.where(ge, hi, mid)
        keep = keep & (z >= lo[..., None])
    if top_p and top_p < 1.0:
        e = jnp.exp(z)
        p = e / jnp.sum(e, axis=-1, keepdims=True)
        # largest prob cutoff u with mass({p >= u}) >= top_p: the kept
        # nucleus is the smallest high-prob set reaching top_p mass
        # (the max-prob token always survives: u <= max p)
        lo = jnp.zeros(p.shape[:-1], jnp.float32)
        hi = jnp.max(p, axis=-1) + 1e-6
        for _ in range(_BISECT_STEPS):
            mid = 0.5 * (lo + hi)
            mass = jnp.sum(jnp.where(p >= mid[..., None], p, 0.0), -1)
            ge = mass >= float(top_p)
            lo = jnp.where(ge, mid, lo)
            hi = jnp.where(ge, hi, mid)
        keep = keep & (p >= lo[..., None])
    score = jnp.where(keep, z + noise.astype(jnp.float32), _NEG_INF)
    best = jnp.max(score, axis=-1, keepdims=True)
    # first-match argmax (ties break low, like jnp.argmax): TPU needs
    # the 2D broadcasted iota form
    idx = jax.lax.broadcasted_iota(jnp.int32, score.shape,
                                   len(score.shape) - 1)
    hit = jnp.where(score >= best, idx, V)
    return jnp.min(hit, axis=-1).astype(jnp.int32)


def _sample_kernel(logits_ref, noise_ref, out_ref, *, temperature, top_k,
                   top_p):
    tok = _select_body(logits_ref[...], noise_ref[...], temperature,
                       top_k, top_p)
    out_ref[...] = tok.reshape(out_ref.shape)


def _sample_pallas(logits, noise, temperature, top_k, top_p):
    B, V = logits.shape
    bn = autotune.sample_rows(B, V)
    grid = (B // bn,)
    out = pl.pallas_call(
        functools.partial(_sample_kernel, temperature=temperature,
                          top_k=top_k, top_p=top_p),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, V), lambda i: (i, 0)),
            pl.BlockSpec((bn, V), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, B), jnp.int32),
        name="fused_sample",
        interpret=_use_interpret(),
    )(logits, noise)
    return out[0]


def fused_sample(logits, noise, *, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0):
    """Sample one token id per row of ``logits [B, V]``.

    ``noise [B, V]`` is caller-supplied Gumbel noise (see
    `gumbel_noise`); temperature/top_k/top_p are STATIC Python values
    (they select the compiled program). ``temperature == 0`` ignores
    the noise entirely and is bit-identical to
    ``jnp.argmax(logits, -1)``. Returns [B] int32."""
    if temperature is None or float(temperature) <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    B, V = logits.shape
    if supports(B, V):
        return _sample_pallas(logits, noise, float(temperature),
                              int(top_k or 0), float(top_p or 1.0))
    return _select_body(logits, noise, float(temperature),
                        int(top_k or 0), float(top_p or 1.0))


def gumbel_noise(key, batch: int, vocab: int):
    """Per-(row, vocab) Gumbel noise for `fused_sample` — generated
    device-side from a jax PRNG key (the G004/G024 discipline: no host
    randomness anywhere near the decode loop)."""
    return jax.random.gumbel(key, (batch, vocab), jnp.float32)
