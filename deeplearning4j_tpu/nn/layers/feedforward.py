"""Feed-forward layer implementations: Dense, Output, Activation, Dropout,
Embedding, AutoEncoder, RBM.

Reference impls: layers/feedforward/dense/DenseLayer.java (via BaseLayer.java
preOutput `input.mmul(W).addiRowVector(b)`:361), embedding/EmbeddingLayer.java,
autoencoder/AutoEncoder.java, rbm/RBM.java (contrastiveDivergence:101).
All backward passes come from jax.grad.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.enums import HiddenUnit, VisibleUnit
from deeplearning4j_tpu.nn.conf.layers import (
    ActivationLayer,
    AutoEncoder,
    BaseOutputLayer,
    DenseLayer,
    DropoutLayer,
    EmbeddingLayer,
    GatedDenseLayer,
    RBM,
)
from deeplearning4j_tpu.nn.layers.base import (
    LayerImpl,
    apply_dropconnect,
    apply_dropout,
    register_impl,
)
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.ops.activations import get_activation
from deeplearning4j_tpu.ops.losses import compute_loss


def _dense_init(conf, rng, dtype):
    kW, _ = jax.random.split(rng)
    W = init_weights(kW, (conf.n_in, conf.n_out), conf.weight_init, conf.dist, dtype)
    b = jnp.full((conf.n_out,), conf.bias_init or 0.0, dtype)
    return {"W": W, "b": b}, {}


def _dense_forward(conf, params, x, train, rng):
    W = params["W"]
    if getattr(conf, "drop_connect", False):
        W = apply_dropconnect(W, conf.dropout, rng, train=train)
    elif conf.dropout:
        x = apply_dropout(x, conf.dropout, rng, train=train)
    z = x @ W
    if "b" in params:
        z = z + params["b"]
    return get_activation(conf.activation)(z), z


def gated_ffn(x, w_gate, w_up, w_down, activation="silu"):
    """(act(x Wgate) * (x Wup)) Wdown: the gated feed-forward block, no
    biases. The expert layers (nn/layers/moe.py) call it per expert."""
    h = get_activation(activation)(x @ w_gate) * (x @ w_up)
    return h @ w_down


@register_impl(GatedDenseLayer)
class GatedDenseImpl(LayerImpl):
    per_position = True
    region = "ffn"

    def init(self, conf, rng, dtype):
        D, O = conf.n_in, conf.n_out or conf.n_in
        F = conf.d_hidden or 4 * D
        kg, ku, kd = jax.random.split(rng, 3)

        def w(k, shape):
            return init_weights(k, shape, conf.weight_init, conf.dist, dtype)

        return {"Wgate": w(kg, (D, F)), "Wup": w(ku, (D, F)),
                "Wdown": w(kd, (F, O))}, {}

    def apply(self, conf, params, state, x, *, train=False, rng=None, mask=None):
        if conf.dropout:
            x = apply_dropout(x, conf.dropout, rng, train=train)
        return gated_ffn(x, params["Wgate"], params["Wup"], params["Wdown"],
                         conf.activation or "silu"), state


@register_impl(DenseLayer)
class DenseImpl(LayerImpl):
    per_position = True
    region = "ffn"

    def init(self, conf, rng, dtype):
        return _dense_init(conf, rng, dtype)

    def apply(self, conf, params, state, x, *, train=False, rng=None, mask=None):
        y, _ = _dense_forward(conf, params, x, train, rng)
        return y, state


@register_impl(BaseOutputLayer)
class OutputImpl(LayerImpl):
    """Output layer: dense + activation; the container computes the loss on
    the preactivation for numeric stability (reference BaseOutputLayer
    computes the softmax/loss delta jointly)."""

    per_position = True
    region = "head"

    def init(self, conf, rng, dtype):
        params, state = _dense_init(conf, rng, dtype)
        if not conf.has_bias:
            params.pop("b")
        return params, state

    def apply(self, conf, params, state, x, *, train=False, rng=None, mask=None):
        y, z = _dense_forward(conf, params, x, train, rng)
        return y, state

    def preactivation(self, conf, params, x, *, train=False, rng=None):
        _, z = _dense_forward(conf, params, x, train, rng)
        return z

    def loss(self, conf, params, x, labels, *, train=False, rng=None,
             mask=None, per_example=False):
        """Scalar training loss; ``per_example=True`` returns one score per
        example [B] instead (reference ScoreExamplesFunction semantics)."""
        act = (conf.activation or "").lower()
        if self._use_fused_head(conf, params, x, labels, act):
            from deeplearning4j_tpu.ops.fused_softmax_xent import (
                softmax_xent_head,
            )
            from deeplearning4j_tpu.ops.losses import _finish

            if conf.dropout:
                x = apply_dropout(x, conf.dropout, rng, train=train)
            per = softmax_xent_head(x, params["W"], params["b"], labels)
            return _finish(per, mask, not per_example)
        y, z = _dense_forward(conf, params, x, train, rng)
        logits = z if act in ("softmax", "sigmoid") else None
        return compute_loss(conf.loss_function, labels, y, mask,
                            logits=logits, reduce=not per_example)

    @staticmethod
    def _use_fused_head(conf, params, x, labels, act):
        """Large-vocab sparse-label softmax/mcxent on TPU: dispatch to the
        fused Pallas head (ops/fused_softmax_xent.py) instead of
        materializing [N, V] logits."""
        from deeplearning4j_tpu.ops import fused_softmax_xent as fsx
        from deeplearning4j_tpu.ops.losses import LossFunction

        if fsx.FORCE_FUSED is False:
            return False
        loss_name = conf.loss_function
        if callable(loss_name):
            return False
        if act != "softmax" or str(loss_name).lower() not in (
                LossFunction.MCXENT, LossFunction.NEGATIVELOGLIKELIHOOD):
            return False
        if not (labels.ndim == x.ndim - 1
                and jnp.issubdtype(labels.dtype, jnp.integer)):
            return False
        if getattr(conf, "drop_connect", False) or "b" not in params:
            return False
        n = int(np.prod(x.shape[:-1]))
        d = x.shape[-1]
        v = params["W"].shape[-1]
        if not fsx.supports(n, d, v):
            return False
        return bool(fsx.FORCE_FUSED) or jax.default_backend() == "tpu"


@register_impl(ActivationLayer)
class ActivationImpl(LayerImpl):
    per_position = True

    def apply(self, conf, params, state, x, *, train=False, rng=None, mask=None):
        if conf.dropout:
            x = apply_dropout(x, conf.dropout, rng, train=train)
        return get_activation(conf.activation)(x), state


@register_impl(DropoutLayer)
class DropoutImpl(LayerImpl):
    per_position = True

    def apply(self, conf, params, state, x, *, train=False, rng=None, mask=None):
        return apply_dropout(x, conf.dropout, rng, train=train), state


@register_impl(EmbeddingLayer)
class EmbeddingImpl(LayerImpl):
    """Index lookup. The reference implements this as a select of rows of W
    (EmbeddingLayer.java); here it is jnp.take — XLA lowers it to a dynamic
    gather; grads are scatter-adds. Input: int [batch] or [batch, 1]."""

    per_position = True
    region = "embed"

    def init(self, conf, rng, dtype):
        params, _ = _dense_init(conf, rng, dtype)
        if not conf.has_bias:
            params.pop("b")
        return params, {}

    def apply(self, conf, params, state, x, *, train=False, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 2 and idx.shape[-1] == 1:
            idx = idx[..., 0]
        z = jnp.take(params["W"], idx, axis=0)
        if "b" in params:
            z = z + params["b"]
        return get_activation(conf.activation)(z), state


@register_impl(AutoEncoder)
class AutoEncoderImpl(LayerImpl):
    """Denoising autoencoder with tied decode weights W^T (reference
    AutoEncoder.java: encode/decode with corruption; pretrain minimizes
    reconstruction loss; as a frozen feed-forward layer it encodes)."""

    def init(self, conf, rng, dtype):
        params, _ = _dense_init(conf, rng, dtype)
        params["vb"] = jnp.full((conf.n_in,), conf.visible_bias_init, dtype)
        return params, {}

    def apply(self, conf, params, state, x, *, train=False, rng=None, mask=None):
        act = get_activation(conf.activation)
        return act(x @ params["W"] + params["b"]), state

    def encode(self, conf, params, x):
        return get_activation(conf.activation)(x @ params["W"] + params["b"])

    def decode(self, conf, params, h):
        return get_activation(conf.activation)(h @ params["W"].T + params["vb"])

    def pretrain_loss(self, conf, params, x, rng):
        corrupted = x
        if conf.corruption_level and rng is not None:
            keep = jax.random.bernoulli(rng, 1.0 - conf.corruption_level, x.shape)
            corrupted = jnp.where(keep, x, 0.0)
        h = self.encode(conf, params, corrupted)
        recon = self.decode(conf, params, h)
        loss = compute_loss(conf.loss_function, x, recon)
        if conf.sparsity:
            rho_hat = jnp.clip(jnp.mean(h, axis=0), 1e-6, 1 - 1e-6)
            rho = conf.sparsity
            loss = loss + jnp.sum(
                rho * jnp.log(rho / rho_hat)
                + (1 - rho) * jnp.log((1 - rho) / (1 - rho_hat))
            )
        return loss


@register_impl(RBM)
class RBMImpl(LayerImpl):
    """RBM trained by CD-k with keyed PRNG sampling inside jit (reference
    RBM.java contrastiveDivergence:101, Gibbs chain gibbhVh:149-151, unit
    types :197-205). The CD-k gradient is expressed as a surrogate loss
    (free-energy difference) whose jax.grad equals the CD update — keeping
    the no-hand-written-gradients invariant.
    """

    def init(self, conf, rng, dtype):
        params, _ = _dense_init(conf, rng, dtype)
        params["vb"] = jnp.full((conf.n_in,), conf.visible_bias_init, dtype)
        return params, {}

    def apply(self, conf, params, state, x, *, train=False, rng=None, mask=None):
        # as a stacked feed-forward layer: hidden mean activation
        h, _ = self._prop_up(conf, params, x)
        return h, state

    def _prop_up(self, conf, params, v):
        z = v @ params["W"] + params["b"]
        hu = conf.hidden_unit
        if hu == HiddenUnit.BINARY:
            return jax.nn.sigmoid(z), z
        if hu == HiddenUnit.RECTIFIED:
            return jax.nn.relu(z), z
        if hu == HiddenUnit.GAUSSIAN:
            return z, z
        if hu == HiddenUnit.SOFTMAX:
            return jax.nn.softmax(z, axis=-1), z
        raise ValueError(f"hidden unit {hu}")

    def _prop_down(self, conf, params, h):
        z = h @ params["W"].T + params["vb"]
        vu = conf.visible_unit
        if vu == VisibleUnit.BINARY:
            return jax.nn.sigmoid(z), z
        if vu in (VisibleUnit.GAUSSIAN, VisibleUnit.LINEAR):
            return z, z
        if vu == VisibleUnit.SOFTMAX:
            return jax.nn.softmax(z, axis=-1), z
        raise ValueError(f"visible unit {vu}")

    def _sample_h(self, conf, params, v, rng):
        mean, _ = self._prop_up(conf, params, v)
        if conf.hidden_unit == HiddenUnit.BINARY:
            return jax.random.bernoulli(rng, mean).astype(mean.dtype), mean
        if conf.hidden_unit == HiddenUnit.GAUSSIAN:
            return mean + jax.random.normal(rng, mean.shape, mean.dtype), mean
        return mean, mean  # rectified/softmax: mean-field

    def _sample_v(self, conf, params, h, rng):
        mean, _ = self._prop_down(conf, params, h)
        if conf.visible_unit == VisibleUnit.BINARY:
            return jax.random.bernoulli(rng, mean).astype(mean.dtype), mean
        if conf.visible_unit == VisibleUnit.GAUSSIAN:
            return mean + jax.random.normal(rng, mean.shape, mean.dtype), mean
        return mean, mean

    def free_energy(self, conf, params, v):
        """F(v) = -v·vb - sum softplus(vW+b) (binary hidden)."""
        z = v @ params["W"] + params["b"]
        fe = -(v @ params["vb"]) - jnp.sum(jax.nn.softplus(z), axis=-1)
        if conf.visible_unit == VisibleUnit.GAUSSIAN:
            fe = fe + 0.5 * jnp.sum(v * v, axis=-1)
        return fe

    def pretrain_loss(self, conf, params, x, rng):
        """CD-k surrogate: mean F(v_data) - F(v_model), with the negative
        sample treated as a constant (stop_gradient) — grad of this equals
        the CD-k update."""
        k = max(1, conf.k)
        keys = jax.random.split(rng, 2 * k)
        v = x
        for i in range(k):
            h, _ = self._sample_h(conf, params, v, keys[2 * i])
            v, _ = self._sample_v(conf, params, h, keys[2 * i + 1])
        v_neg = jax.lax.stop_gradient(v)
        return jnp.mean(self.free_energy(conf, params, x) - self.free_energy(conf, params, v_neg))
