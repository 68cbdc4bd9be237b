"""Fused negative-sampling sampled-softmax scoring — one pass over the
gathered embedding rows.

The sharded embedding engine's SGNS step (embedding/engine.py) scores a
[B, D] center strip against its [B, D] positive rows and [B, K, D]
negative block: two sigmoid'd contractions whose results feed both the
loss and the closed-form gradients. This module fuses the two
contractions and the sigmoids into one Pallas program per row block —
the sampled-softmax inner loop of word2vec SGNS, following the
every-kernel-benchmarked discipline (Dragon-Alpha, arXiv:2305.08819):
registered in the ``neg_softmax`` autotune family, swept by
tools/kerneltune.py, resolved through the tuning table.

Dispatch follows the fused_sampling idiom: outside the `supports()`
envelope the pure-jnp reference (`_score_body`) runs. Its expressions
are verbatim the legacy dense path's (nlp/lookup.sgns_step), which is
what makes the engine's ep=1 bit-parity contract hold on the tiny-vocab
shapes the envelope excludes. The kernel computes the same dot products
as multiply + lane reduction in f32 (the chip's compiler refuses the
reference's free-dimension-less batched dots), equal to the reference
within float rounding (tests/test_embedding.py, atol 1e-6).

The [B, K] negative-score output is padded to a [B, LANES] lane tile in
kernel (K is a handful; the last dimension must tile) and sliced back by
the public entry point.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from deeplearning4j_tpu.ops import autotune


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def supports(batch: int, k: int, dim: int) -> bool:
    """Whether the Pallas kernel's envelope covers a (c [batch, dim],
    pos [batch, dim], neg [batch, k, dim]) triple: lane-tiled dim,
    sublane-tiled rows, K inside one lane tile (the padded neg-score
    block), and a legal (1, bn) positive-score row."""
    if dim % autotune.LANES != 0 or batch % 8 != 0:
        return False
    if not 0 < k <= autotune.LANES:
        return False
    bn = autotune.neg_softmax_rows(batch, dim)
    return bn % autotune.LANES == 0 or bn == batch


def _score_body(c, pos, neg):
    """The shared scoring math (kernel body AND jnp reference run
    exactly this — and it is verbatim nlp/lookup.sgns_step's forward):
    c/pos [bn, D], neg [bn, K, D]; returns sigmoid'd dot products
    (pos_score [bn], neg_score [bn, K])."""
    pos_score = jax.nn.sigmoid(jnp.einsum(
        "bd,bd->b", c, pos, preferred_element_type=jnp.float32))
    neg_score = jax.nn.sigmoid(jnp.einsum(
        "bd,bkd->bk", c, neg, preferred_element_type=jnp.float32))
    return pos_score, neg_score


def _neg_softmax_kernel(c_ref, pos_ref, neg_ref, pos_out_ref, neg_out_ref):
    # the same dot products as _score_body, spelled as multiply + lane
    # reduction: Mosaic refuses a batched dot with no free dimension
    # ("bd,bd->b" / "bd,bkd->bk" have none on the lhs)
    c = c_ref[...].astype(jnp.float32)
    pos_score = jax.nn.sigmoid(
        jnp.sum(c * pos_ref[...].astype(jnp.float32), axis=-1))
    neg_score = jax.nn.sigmoid(
        jnp.sum(c[:, None, :] * neg_ref[...].astype(jnp.float32), axis=-1))
    pos_out_ref[...] = pos_score.reshape(pos_out_ref.shape)
    bn, k = neg_score.shape
    neg_out_ref[...] = jnp.pad(neg_score,
                               ((0, 0), (0, autotune.LANES - k)))


def _neg_softmax_pallas(c, pos, neg):
    B, D = c.shape
    K = neg.shape[1]
    bn = autotune.neg_softmax_rows(B, D)
    grid = (B // bn,)
    pos_score, neg_pad = pl.pallas_call(
        functools.partial(_neg_softmax_kernel),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((bn, K, D), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((bn, autotune.LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, B), c.dtype),
            jax.ShapeDtypeStruct((B, autotune.LANES), c.dtype),
        ],
        name="neg_softmax",
        interpret=_use_interpret(),
    )(c, pos, neg)
    return pos_score[0], neg_pad[:, :K]


def neg_softmax_scores(c, pos, neg):
    """Sigmoid'd SGNS scores for a batch of (center, positive,
    K-negatives) triples: c/pos [B, D], neg [B, K, D] ->
    (pos_score [B], neg_score [B, K]).

    Inside the `supports()` envelope the fused Pallas kernel runs (row
    block from the ``neg_softmax`` autotune family; interpret mode
    off-TPU); outside it the SAME math runs as the pure-jnp reference —
    bit-identical to the legacy dense sgns_step forward."""
    B, D = c.shape
    K = neg.shape[1]
    if supports(B, K, D):
        return _neg_softmax_pallas(c, pos, neg)
    return _score_body(c, pos, neg)
