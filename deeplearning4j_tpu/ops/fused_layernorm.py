"""Fused LayerNorm via Pallas — available but NOT the default.

One read + one write per pass: the forward saves per-row (mu, rstd), the
backward emits dx plus per-block dgamma/dbeta partials that sum outside.

Measured result (v5e, same-window A/B at the r4 flagship shapes — 6
blocks, d_model 256, seq 512): the fused kernel LOSES to XLA's native
lowering, 0.455 vs 0.494 MFU. XLA fuses the normalize chain INTO the
neighboring residual adds and matmul prologues; a pallas_call is a
fusion barrier, so the kernel's saved LN-local traffic is outweighed by
the materialization it forces around itself. `nn/layers/attention.
LayerNormImpl` therefore keeps the jnp form; this op remains for
compositions where LN has no fusable neighbors (e.g. standalone
normalization passes) and as the measured record of the experiment.

Envelope: feature dim C a lane-tile multiple (C % 128 == 0) and a
lane-legal row block. Interpret mode runs the same kernels on CPU for
the unit tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.ops import autotune

# row-block cap: resolved per (N, C) config through the tuning layer
# (ops/autotune.py); this name remains for the measured-default record
_ROW_BLOCK = autotune.DEFAULT_LN_ROW_BLOCK


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _pick_rows(N: int, C: int) -> int:
    """Row block via the tuning layer: a valid table entry (TPU only)
    wins, else the power-of-two divisor search up to the swept cap.
    autotune.ln_rows enforces the stat-row legality rule on tuned
    values, so fwd and bwd always agree on bn."""
    return autotune.ln_rows(N, C)


def supports(shape, dtype=None) -> bool:
    if len(shape) < 2:
        return False
    C = shape[-1]
    N = int(np.prod(shape[:-1]))
    if C % 128 == 0 and N % 8 == 0:
        bn = _pick_rows(N, C)
        # the [1, N] stat rows use (1, bn) blocks: legal only when bn is
        # a lane-tile multiple or the whole row dim
        return bn % 128 == 0 or bn == N
    return False


def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, mu_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)                     # [bn, C]
    mu = jnp.mean(x, axis=1)
    xc = x - mu[:, None]
    var = jnp.mean(xc * xc, axis=1)
    rstd = jax.lax.rsqrt(var + eps)
    g = g_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    y = xc * rstd[:, None] * g[None] + b[None]
    y_ref[...] = y.astype(y_ref.dtype)
    mu_ref[...] = mu.reshape(mu_ref.shape)
    rstd_ref[...] = rstd.reshape(rstd_ref.shape)


def _bwd_kernel(x_ref, g_ref, mu_ref, rstd_ref, dy_ref, dx_ref, dg_ref,
                db_ref):
    x = x_ref[...].astype(jnp.float32)                     # [bn, C]
    dy = dy_ref[...].astype(jnp.float32)
    bn = x.shape[0]
    mu = mu_ref[...].reshape(bn)
    rstd = rstd_ref[...].reshape(bn)
    xn = (x - mu[:, None]) * rstd[:, None]
    wdy = dy * g_ref[...].astype(jnp.float32)[None]
    m1 = jnp.mean(wdy, axis=1)
    m2 = jnp.mean(wdy * xn, axis=1)
    dx = rstd[:, None] * (wdy - m1[:, None] - xn * m2[:, None])
    dx_ref[...] = dx.astype(dx_ref.dtype)
    dg_ref[...] = jnp.sum(dy * xn, axis=0).reshape(dg_ref.shape)
    db_ref[...] = jnp.sum(dy, axis=0).reshape(db_ref.shape)


def _ln_fwd(x2d, gamma, beta, eps):
    N, C = x2d.shape
    bn = _pick_rows(N, C)
    grid = (N // bn,)
    y, mu, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, C), lambda i: (i, 0)),
            pl.BlockSpec((C,), lambda i: (0,)),
            pl.BlockSpec((C,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((bn, C), lambda i: (i, 0)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, C), x2d.dtype),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
        ],
        name="fused_layer_norm_fwd",
        interpret=_use_interpret(),
    )(x2d, gamma, beta)
    return y, mu, rstd


def _ln_bwd(x2d, gamma, mu, rstd, dy):
    N, C = x2d.shape
    bn = _pick_rows(N, C)
    grid = (N // bn,)
    dx, dgp, dbp = pl.pallas_call(
        _bwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, C), lambda i: (i, 0)),
            pl.BlockSpec((C,), lambda i: (0,)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((1, bn), lambda i: (0, i)),
            pl.BlockSpec((bn, C), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bn, C), lambda i: (i, 0)),
            # [nb, 1, C] partials: a (1, C) block over [nb, C] violates
            # the Mosaic (8,128)-or-full rule on the second-minor dim;
            # the singleton middle dim makes the last two dims (1, C) =
            # full-array (the same trick as the flash lse rows)
            pl.BlockSpec((1, 1, C), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, C), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, C), x2d.dtype),
            jax.ShapeDtypeStruct((N // bn, 1, C), jnp.float32),
            jax.ShapeDtypeStruct((N // bn, 1, C), jnp.float32),
        ],
        name="fused_layer_norm_bwd",
        interpret=_use_interpret(),
    )(x2d, gamma, mu, rstd, dy)
    return dx, dgp[:, 0].sum(0), dbp[:, 0].sum(0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fused_layer_norm(x, gamma, beta, eps=1e-5):
    """LayerNorm over the LAST axis of x (any leading shape), fused.
    Returns y with x's dtype; statistics and normalization math in f32."""
    shape = x.shape
    y, _, _ = _ln_fwd(x.reshape(-1, shape[-1]), gamma, beta, eps)
    return y.reshape(shape)


def _fln_fwd(x, gamma, beta, eps):
    shape = x.shape
    x2d = x.reshape(-1, shape[-1])
    y, mu, rstd = _ln_fwd(x2d, gamma, beta, eps)
    return y.reshape(shape), (x2d, gamma, mu, rstd, shape)


def _fln_bwd(eps, res, dy):
    x2d, gamma, mu, rstd, shape = res
    dx, dg, db = _ln_bwd(x2d, gamma, mu, rstd,
                         dy.reshape(-1, shape[-1]))
    return (dx.reshape(shape), dg.astype(gamma.dtype),
            db.astype(gamma.dtype))


fused_layer_norm.defvjp(_fln_fwd, _fln_bwd)
