"""Fused softmax cross-entropy head for large vocabularies (Pallas/TPU).

The stock mcxent path materializes the full [N, V] logits in f32 several
times per step (forward matmul, logsumexp pass, backward p - onehot pass,
then the dx / dW dots re-read it) — at N=16k, V=10k that is ~3 GB of HBM
traffic per training step, measured at ~6.4 ms of an 18.6 ms Transformer-LM
step on v5e. This kernel computes

    loss[n] = logsumexp_v(x[n] @ W + b) - (x[n] @ W + b)[labels[n]]

without ever writing logits to HBM: the forward streams W in vocab chunks
and keeps an online (max, sumexp, label-logit) accumulator in VMEM; the
backward recomputes each logits chunk from (x, W, b, lse) and immediately
contracts p - onehot into dx (one kernel, vocab-chunk inner) and into
dW/db (a second kernel, row-block inner) — the standard
recompute-over-store trade (cf. flash attention, ops/flash_attention.py).

MXU operands stay in the input dtype (bf16 under the TPU dtype policy);
all softmax math and accumulators are f32. Falls back to interpret mode
off-TPU so unit tests exercise the same code on CPU.

Replaces the capability of the reference's fused output-layer delta
(BaseOutputLayer.java computeGradientAndScore computes the softmax/loss
gradient jointly rather than via d(log(softmax))) at TPU scale.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import autotune
from deeplearning4j_tpu.ops.partition import rows_per_device
from deeplearning4j_tpu.util.compat import tpu_compiler_params

LANES = autotune.LANES
NEG_INF = -1e30

# Block caps: resolved per (V, d) config through the tuning layer
# (ops/autotune.py — table entry when tuned on TPU, else the swept v5e
# defaults: 1024-row blocks x 2048-wide vocab chunks at d=256 under the
# 32MB scoped limit; see autotune.xent_blocks for the d-scaling rule).
# The names remain as the measured-default record.
BLOCK_N = autotune.DEFAULT_XENT_BLOCK_N
BLOCK_V = autotune.DEFAULT_XENT_BLOCK_V

# Use the fused kernel only where the dense path's [N, V] materialization
# actually hurts; small heads fuse fine inside XLA.
MIN_FUSED_VOCAB = 2048
MAX_FUSED_D = 1024

# Dispatch override: None = auto (TPU only), True = always (interpret mode
# off-TPU — used by unit tests), False = never.
FORCE_FUSED = None


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def supports(n: int, d: int, v: int) -> bool:
    """Whether the fused head handles this shape (else: dense path).

    Ragged row counts are fine — softmax_xent_head pads tokens to the
    128-row grid internally — so `n` does not gate the dispatch."""
    del n
    return v >= MIN_FUSED_VOCAB and d % 128 == 0 and d <= MAX_FUSED_D


# ------------------------------------------------------------------ forward

def _fwd_kernel(x_ref, w_ref, b_ref, lab_ref, loss_ref, lse_ref,
                m_scr, l_scr, ll_scr, *, block_v, n_chunks):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        ll_scr[...] = jnp.zeros_like(ll_scr)

    x = x_ref[...]                                        # [bn, d]
    w = w_ref[...]                                        # [d, bv]
    s = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s + b_ref[...].astype(jnp.float32)                # [bn, bv]

    lab = lab_ref[...]                                    # [bn, 1] int32
    cols = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    onehot = cols == lab                                  # [bn, bv]

    m = m_scr[:, 0]
    l = l_scr[:, 0]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    alpha = jnp.exp(m - m_new)
    l = l * alpha + jnp.sum(jnp.exp(s - m_new[:, None]), axis=-1)
    ll = ll_scr[:, 0] + jnp.sum(jnp.where(onehot, s, 0.0), axis=-1)

    bn = s.shape[0]
    m_scr[...] = jax.lax.broadcast_in_dim(m_new, (bn, LANES), (0,))
    l_scr[...] = jax.lax.broadcast_in_dim(l, (bn, LANES), (0,))
    ll_scr[...] = jax.lax.broadcast_in_dim(ll, (bn, LANES), (0,))

    @pl.when(j == n_chunks - 1)
    def _emit():
        lse = m_new + jnp.log(jnp.maximum(l, 1e-30))
        lse_ref[...] = jax.lax.broadcast_in_dim(lse, (bn, LANES), (0,))
        loss_ref[...] = jax.lax.broadcast_in_dim(lse - ll, (bn, LANES), (0,))


def _fused_fwd(x, w, b, labels, bn, bv):
    N, d = x.shape
    V = w.shape[1]
    n_chunks = V // bv
    lab2 = labels.astype(jnp.int32).reshape(N, 1)
    b2 = b.reshape(1, V)
    kern = functools.partial(_fwd_kernel, block_v=bv, n_chunks=n_chunks)
    loss, lse = pl.pallas_call(
        kern,
        grid=(N // bn, n_chunks),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),
            pl.BlockSpec((1, bv), lambda i, j: (0, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, LANES), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, LANES), jnp.float32),
            jax.ShapeDtypeStruct((N, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bn, LANES), jnp.float32),
            pltpu.VMEM((bn, LANES), jnp.float32),
            pltpu.VMEM((bn, LANES), jnp.float32),
        ],
        compiler_params=tpu_compiler_params(
            vmem_limit_bytes=32 * 1024 * 1024),
        name="softmax_xent_fwd",
        interpret=_use_interpret(),
    )(x, w, b2, lab2)
    return loss[:, 0], lse[:, 0]


# ----------------------------------------------------------------- backward

def _dx_kernel(x_ref, w_ref, b_ref, lab_ref, lse_ref, g_ref, dx_ref,
               acc_scr, *, block_v, n_chunks):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...]
    w = w_ref[...]
    s = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s + b_ref[...].astype(jnp.float32)
    lse = lse_ref[:, 0]
    p = jnp.exp(s - lse[:, None])                         # [bn, bv]
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    onehot = cols == lab_ref[...]
    g = (p - jnp.where(onehot, 1.0, 0.0)) * g_ref[:, 0][:, None]
    acc_scr[...] += jax.lax.dot_general(
        g.astype(w.dtype), w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == n_chunks - 1)
    def _emit():
        dx_ref[...] = acc_scr[...].astype(dx_ref.dtype)


def _dwdb_kernel(x_ref, w_ref, b_ref, lab_ref, lse_ref, g_ref, dw_ref,
                 db_ref, dw_scr, db_scr, *, block_v, n_rows):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        dw_scr[...] = jnp.zeros_like(dw_scr)
        db_scr[...] = jnp.zeros_like(db_scr)

    j = pl.program_id(0)
    x = x_ref[...]
    w = w_ref[...]
    s = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s + b_ref[...].astype(jnp.float32)
    lse = lse_ref[:, 0]
    p = jnp.exp(s - lse[:, None])
    cols = j * block_v + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    onehot = cols == lab_ref[...]
    g = (p - jnp.where(onehot, 1.0, 0.0)) * g_ref[:, 0][:, None]
    dw_scr[...] += jax.lax.dot_general(
        x, g.astype(x.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    db_scr[...] += jnp.sum(g, axis=0, keepdims=True)

    @pl.when(i == n_rows - 1)
    def _emit():
        dw_ref[...] = dw_scr[...].astype(dw_ref.dtype)
        db_ref[...] = db_scr[...].astype(db_ref.dtype)


def _fused_bwd(bn, bv, res, dloss):
    x, w, b, labels, lse = res
    N, d = x.shape
    V = w.shape[1]
    n_chunks = V // bv
    n_rows = N // bn
    lab2 = labels.astype(jnp.int32).reshape(N, 1)
    b2 = b.reshape(1, V)
    g2 = jax.lax.broadcast_in_dim(
        dloss.astype(jnp.float32), (N, LANES), (0,))
    lse2 = jax.lax.broadcast_in_dim(lse, (N, LANES), (0,))

    dx = pl.pallas_call(
        functools.partial(_dx_kernel, block_v=bv, n_chunks=n_chunks),
        grid=(n_rows, n_chunks),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bv), lambda i, j: (0, j)),
            pl.BlockSpec((1, bv), lambda i, j: (0, j)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, LANES), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, LANES), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, d), x.dtype),
        scratch_shapes=[pltpu.VMEM((bn, d), jnp.float32)],
        compiler_params=tpu_compiler_params(
            vmem_limit_bytes=32 * 1024 * 1024),
        name="softmax_xent_dx",
        interpret=_use_interpret(),
    )(x, w, b2, lab2, lse2, g2)

    dw, db2 = pl.pallas_call(
        functools.partial(_dwdb_kernel, block_v=bv, n_rows=n_rows),
        grid=(n_chunks, n_rows),
        in_specs=[
            pl.BlockSpec((bn, d), lambda j, i: (i, 0)),
            pl.BlockSpec((d, bv), lambda j, i: (0, j)),
            pl.BlockSpec((1, bv), lambda j, i: (0, j)),
            pl.BlockSpec((bn, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((bn, LANES), lambda j, i: (i, 0)),
            pl.BlockSpec((bn, LANES), lambda j, i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((d, bv), lambda j, i: (0, j)),
            pl.BlockSpec((1, bv), lambda j, i: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((d, V), w.dtype),
            jax.ShapeDtypeStruct((1, V), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((d, bv), jnp.float32),
            pltpu.VMEM((1, bv), jnp.float32),
        ],
        # the [bn,bv] f32 logits recompute is 8MB alone at the r5 block
        # sizes (bn=1024 x bv=2048), plus the [d,bv] dW scratch and
        # double-buffered weight blocks — well past the conservative
        # 16MB scoped default; v5e has 128MB of VMEM, so all three
        # kernels in this file request 32MB rather than shrinking the
        # swept (faster) block sizes
        compiler_params=tpu_compiler_params(
            vmem_limit_bytes=32 * 1024 * 1024),
        name="softmax_xent_dwdb",
        interpret=_use_interpret(),
    )(x, w, b2, lab2, lse2, g2)

    # labels are integral: their tangent space is float0, not None
    dlab = np.zeros(labels.shape, jax.dtypes.float0)
    return dx, dw, db2[0].astype(b.dtype), dlab


# block sizes are resolved ONCE in softmax_xent_head (the tuning-table
# key is the UNPADDED (V, d); re-resolving inside the vjp would look up
# the padded vocab and could disagree with the padding bv) and ride the
# custom_vjp as static nondiff args
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _fused_head(x, w, b, labels, bn, bv):
    loss, _ = _fused_fwd(x, w, b, labels, bn, bv)
    return loss


def _fused_head_fwd(x, w, b, labels, bn, bv):
    loss, lse = _fused_fwd(x, w, b, labels, bn, bv)
    return loss, (x, w, b, labels, lse)


_fused_head.defvjp(_fused_head_fwd, _fused_bwd)


def softmax_xent_head(x, w, b, labels):
    """Per-token softmax cross-entropy of a dense head, fused.

    x: [..., d] features; w: [d, V]; b: [V]; labels: int [...] in [0, V).
    Returns per-token loss [...] (f32). Labels must be in range — mask
    ignored positions via the loss mask, not an ignore index (XLA clamps
    out-of-range gathers; here they would silently hit column V-1).
    """
    lead = x.shape[:-1]
    n = int(np.prod(lead)) if lead else 1
    loss = rows_per_device(
        _head_rows, (x.reshape(n, x.shape[-1]), labels.reshape(n)), (w, b))
    return loss.reshape(lead)


def _head_rows(xf, lf, w, b):
    """softmax_xent_head on flat rows: xf [n, d], lf [n] -> loss [n].
    Under a device-spanning jit this is the per-device body (n = this
    device's tokens; blocks resolve against the local row count)."""
    n, d = xf.shape
    V = w.shape[-1]
    n_pad = (n + 127) // 128 * 128
    if n_pad != n:
        # ragged row counts (e.g. a final partial batch): pad tokens to the
        # 128-row grid; padded rows carry label 0 over zero features, their
        # loss entries are sliced off below, and the slice's VJP gives them
        # zero cotangent so they contribute nothing to dx/dW/db
        xf = jnp.pad(xf, ((0, n_pad - n), (0, 0)))
        lf = jnp.pad(lf, (0, n_pad - n))
    # blocks resolved once against the UNPADDED vocab (the tuning-table
    # key), then the vocab padding below is a whole number of bv chunks
    # by construction
    bn, bv = autotune.xent_blocks(n_pad, d, V)
    if V % bv:
        # pad the vocab to a whole number of chunks; padded columns get
        # bias NEG_INF so exp() kills them, and their dW/db rows are
        # sliced off by the [:, :V] view of the padded weight's cotangent
        vp = (V + bv - 1) // bv * bv
        w = jnp.pad(w, ((0, 0), (0, vp - V)))
        b = jnp.pad(b, (0, vp - V), constant_values=NEG_INF)
    return _fused_head(xf, w, b, lf, bn, bv)[:n]
