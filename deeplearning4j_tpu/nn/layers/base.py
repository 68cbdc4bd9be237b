"""Layer implementation protocol + registry.

The reference pairs every conf class with a hand-written layer impl holding
forward AND analytic backward (nn/layers/*, e.g. BaseLayer.java:361 preOutput,
:161 backward gemm) wired through LayerFactories. Here an impl provides only:

- init(conf, rng, dtype)    -> (params pytree, state pytree)
- apply(conf, params, state, x, train, rng, mask) -> (y, new_state)

Backward is always jax.grad through apply — there is no backprop code
anywhere in this framework. `state` carries non-trained buffers (BatchNorm
running stats); layers without state return {}.

Dropout/DropConnect (reference util/Dropout.java, inverted dropout applied to
the layer input at BaseLayer) is implemented here once, with keyed PRNG.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

_IMPL_REGISTRY: dict[type, "LayerImpl"] = {}

# State-channel key for per-batch auxiliary losses (e.g. the MoE router
# load-balance loss). A layer may stash a scalar under this key in its
# returned state during training; the containers sum every such entry
# into the training loss and the key never persists into eval state.
AUX_LOSS_KEY = "__aux_loss__"


def pop_aux_losses(state):
    """Sum and REMOVE ephemeral `AUX_LOSS_KEY` scalars from a state pytree.
    Returns (total, cleaned_state). The key must not survive into the
    persisted state: it is per-batch, and leaving it in would change the
    state pytree structure between init ({}) and post-forward (breaking
    lax.scan carries and checkpoints)."""
    total = 0.0
    cleaned = {}
    for name, s in state.items():
        if isinstance(s, dict) and AUX_LOSS_KEY in s:
            total = total + s[AUX_LOSS_KEY]
            cleaned[name] = {k: v for k, v in s.items() if k != AUX_LOSS_KEY}
        else:
            cleaned[name] = s
    return total, cleaned


def register_impl(conf_cls):
    def wrap(impl_cls):
        _IMPL_REGISTRY[conf_cls] = impl_cls()
        return impl_cls

    return wrap


def get_impl(conf) -> "LayerImpl":
    for cls in type(conf).__mro__:
        impl = _IMPL_REGISTRY.get(cls)
        if impl is not None:
            return impl
    raise ValueError(f"No layer implementation registered for {type(conf).__name__}")


class LayerImpl:
    """Stateless singleton holding pure init/apply for one layer kind."""

    # True where `apply` maps each position of [B, T, ...] by itself: a
    # serving step (nn/decode.py) may then call it on any slice of a
    # sequence. A layer that has to know where its tokens stand carries
    # `apply_cached` instead (nn/layers/attention.py).
    per_position = False

    # The region of a compiled program this layer's ops lie in (one of
    # telemetry/recorder.py REGIONS): the containers' forwards and the
    # serving walk call the layer under `region_scope(region)`. None:
    # unscoped, its device time reads as `other`.
    region = None

    @staticmethod
    def rewindable(conf) -> bool:
        """False where a serving step cannot be taken out of this
        layer's cache entry again (a state, a ring of rows: not rows a
        later write hides): nn/decode.make_verify_fn refuses such a net
        (nn/layers/power_retention.py, nn/layers/gated_deltanet.py,
        nn/layers/grouped_attention.py)."""
        return True

    def init(self, conf, rng, dtype):
        return {}, {}

    def apply(self, conf, params, state, x, *, train=False, rng=None, mask=None):
        raise NotImplementedError

    # pretrain interface (AutoEncoder/RBM): returns (loss, params-grad-ready fn)
    def pretrain_loss(self, conf, params, x, rng):
        raise NotImplementedError(f"{type(self).__name__} is not a pretrain layer")


def region_scope(region):
    """`jax.named_scope(region)` around a layer's call, or no scope where
    `region` is None. A scope is HLO metadata (`op_name`): the compiled
    instructions are the same with it and without it."""
    return contextlib.nullcontext() if region is None \
        else jax.named_scope(region)


def input_region(inputs, regions):
    """The region of a vertex that is no layer (a residual add, a merge):
    that of its input computed last, so that an operation the compiler
    fuses into the vertex's (a block's last product with the residual
    add) reads as the layer that fed it. `regions`: {name: (position in
    the forward, region)} of what was computed before."""
    seen = [regions[i] for i in inputs if i in regions]
    return max(seen)[1] if seen else None


def apply_dropout(x, rate, rng, *, train):
    """Inverted dropout on the layer input (reference util/Dropout.applyDropout:31)."""
    if not train or rate in (None, 0.0) or rng is None:
        return x
    keep = 1.0 - rate
    m = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(m, x / keep, 0.0)


def apply_dropconnect(w, rate, rng, *, train):
    """DropConnect: drop weights instead of activations (reference Dropout.java)."""
    if not train or rate in (None, 0.0) or rng is None:
        return w
    keep = 1.0 - rate
    m = jax.random.bernoulli(rng, keep, w.shape)
    return jnp.where(m, w / keep, 0.0)


def l1_l2_penalty(conf, params):
    """Per-layer L1/L2 regularization on weight params only (reference
    BaseLayer calcL1/calcL2 — biases excluded)."""
    pen = 0.0
    l1 = getattr(conf, "l1", 0.0) or 0.0
    l2 = getattr(conf, "l2", 0.0) or 0.0
    if l1 == 0.0 and l2 == 0.0:
        return 0.0
    for name, p in params.items():
        if name.startswith("b") or name in ("gamma", "beta", "mean", "var"):
            continue
        if l1:
            pen = pen + l1 * jnp.sum(jnp.abs(p))
        if l2:
            pen = pen + 0.5 * l2 * jnp.sum(p * p)
    return pen
