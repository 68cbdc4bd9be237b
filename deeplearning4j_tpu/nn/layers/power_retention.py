"""Power retention: a causal sequence layer whose memory of the past is a
state of fixed size, not a row a token (`PowerRetentionLayer`,
nn/conf/layers.py; Manifest AI, "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239).

For a token u at position t (no projection has a bias but the gate's; Hq
query heads and Hk key-value heads of d; query head h reads the state of
key-value head h // (Hq / Hk)):

    q = rot(Nq(u Wq), t)   [Hq, d]     Nq, Nk: RMS norm over the d of a
    k = rot(Nk(u Wk), t)   [Hk, d]     head, one learned gain vector each
    v = u Wv               [Hk, d]
    log g = log_sigmoid(u Wg + bg)   [Hk], float32: one scalar a head
    w(t, j) = exp(G_t - G_j) (q_t . k_j)^2 / d   for j <= t,
              G_t = sum_{l <= t} log g_l
    y_t = sum_j w(t, j) v_j / (sum_j w(t, j) + sum_eps)
    out = concat_h(y) Wo

`rot` is `latent_attention.rotary` (the pairs (i, i + d / 2)). The power
is even, so a weight is never negative, and it is the inner product of
the symmetric squares of q and k (ops/power_retention.phi2), so the two
sums are a state, `s` [Hk, d, D] and `z` [Hk, D] with D = d (d / 2 + 1):

    S_t = g_t S_(t-1) + v_t phi2(k_t)^T,   z_t = g_t z_(t-1) + phi2(k_t),
    y_t = S_t phi2(q_t) / (z_t . phi2(q_t) + sum_eps)

the same y_t, term by term. `apply` (a whole sequence) and a prefill
chunk use the two together a sub-chunk at a time: `apply` through
`retention_chunk` (plain `jnp`, differentiable), a chunk through the
cache's rows (`retention_chunk_rows`: on a TPU the state's terms are a
Pallas kernel that advances the rows' states in place, no gradient); a
decode step is the recurrence, one pass over the state
(`retention_decode`, a Pallas kernel on a TPU).

THE CACHE ENTRY is the state, {"s": [B, Hk, d, D], "z": [B, Hk, D]} in
`state_dtype` (float32 unless the conf says otherwise), the same
whatever the cache's `capacity`, and billed to the slot, not to its
positions (serving/kvcache.py). `kv_dtype="int8"` quantises rows of keys
and values a page at a time; there are none here, so it leaves the state
as it is. What a running sum does not forgive, and a row a token does,
this layer takes from `nn/decode.CacheStep`:

* a row whose first position in the step is 0 starts a sequence: its
  state is ZEROED before anything is added (stale keys are hidden by a
  key limit; a stale state would be in every later token). The layer
  counts those rows (`state_resets`: the count rides the step's fetch);
* a token with `keep` 0 (the pad of a prefill bucket) adds nothing and
  decays nothing: it cannot be taken out again afterwards;
* a row the step says is not `live` leaves its state bit for bit;
* a step cannot be unwound (`rewindable(conf)` is False): a rejected draft's
  keys are overwritten, its share of a sum is not, so
  `nn/decode.make_verify_fn` refuses a net with this layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.layers import PowerRetentionLayer
from deeplearning4j_tpu.nn.layers.attention import rms_norm
from deeplearning4j_tpu.nn.layers.base import (
    LayerImpl,
    apply_dropout,
    register_impl,
)
from deeplearning4j_tpu.nn.layers.latent_attention import rotary
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.ops.activations import get_activation
from deeplearning4j_tpu.ops.power_retention import (
    retention_chunk,
    retention_chunk_rows,
    retention_decode,
    state_dim,
)


GATE_BIAS = 6.0     # a fresh layer's gates: a state fades over some 400 tokens


def _sizes(conf):
    Hq = conf.n_heads
    Hk = conf.n_kv_heads or Hq
    return Hq, Hk, conf.head_dim or conf.n_out // Hq


def _project(conf, params, x, positions):
    """x [b, T, n_in] at `positions` [b, T] -> q [b, T, Hq, d], k, v
    [b, T, Hk, d] in x's dtype, log g [b, T, Hk] float32."""
    b, T, _ = x.shape
    Hq, Hk, d = _sizes(conf)
    q = rms_norm((x @ params["Wq"]).reshape(b, T, Hq, d), params["q_norm"],
                 conf.eps)
    k = rms_norm((x @ params["Wk"]).reshape(b, T, Hk, d), params["k_norm"],
                 conf.eps)
    v = (x @ params["Wv"]).reshape(b, T, Hk, d)
    gate = jnp.einsum("btn,nh->bth", x, params["Wg"],
                      preferred_element_type=jnp.float32)
    log_g = jax.nn.log_sigmoid(gate + params["bg"].astype(jnp.float32))
    return (rotary(q, positions, conf.rope_theta),
            rotary(k, positions, conf.rope_theta), v, log_g)


def _starts(step):
    """[b] True for the rows that start a sequence in this step (their
    first position is 0): the rows whose state is zeroed first."""
    return step.positions[:, 0] == 0


def _output(conf, params, y):
    """y [b, T, Hq, d] -> [b, T, n_out]."""
    y = y.reshape(y.shape[:2] + (-1,)) @ params["Wo"]
    return get_activation(conf.activation or "identity")(y)


@register_impl(PowerRetentionLayer)
class PowerRetentionImpl(LayerImpl):
    """The token mixer of its block, in place of attention: its ops lie
    in the region `attention` (telemetry/recorder.py REGIONS), as every
    token mixer's do, so a retention step's state pass reads where a
    softmax layer's walk over its rows does."""

    region = "attention"
    counters = ("state_resets",)

    @staticmethod
    def rewindable(conf) -> bool:
        return False                # a step's share of the state stays

    @staticmethod
    def merge_counts(counts: list) -> dict:
        """Every layer of a net resets the same rows: one layer's count."""
        return counts[0]

    def init(self, conf, rng, dtype):
        Hq, Hk, d = _sizes(conf)
        if Hq % Hk or d % 2:
            raise ValueError(
                f"PowerRetentionLayer needs n_heads a multiple of "
                f"n_kv_heads and an even head_dim; got {Hq}, {Hk}, {d}")
        k = jax.random.split(rng, 5)

        def w(key, shape):
            return init_weights(key, shape, conf.weight_init, conf.dist, dtype)

        return {"Wq": w(k[0], (conf.n_in, Hq * d)),
                "Wk": w(k[1], (conf.n_in, Hk * d)),
                "Wv": w(k[2], (conf.n_in, Hk * d)),
                "Wg": w(k[3], (conf.n_in, Hk)),
                "bg": jnp.full((Hk,), GATE_BIAS, dtype),
                "q_norm": jnp.ones((d,), dtype),
                "k_norm": jnp.ones((d,), dtype),
                "Wo": w(k[4], (Hq * d, conf.n_out))}, {}

    def apply(self, conf, params, state, x, *, train=False, rng=None,
              mask=None):
        if conf.dropout:
            x = apply_dropout(x, conf.dropout, rng, train=train)
        b, T, _ = x.shape
        _, Hk, d = _sizes(conf)
        positions = jnp.broadcast_to(jnp.arange(T)[None, :], (b, T))
        q, k, v, log_g = _project(conf, params, x, positions)
        y, _, _ = retention_chunk(
            q, k, v, log_g, jnp.zeros((b, Hk, d, state_dim(d)), jnp.float32),
            jnp.zeros((b, Hk, state_dim(d)), jnp.float32), keep=mask,
            eps=conf.sum_eps)
        return _output(conf, params, y), state

    def cache_arrays(self, conf, capacity, kv_dtype, page_size, dtype):
        """The state one decode slot of this layer holds, whatever
        `capacity`, `kv_dtype` and the compute dtype: {name: (shape,
        dtype, "slot")}; the third entry says that the array is no row a
        position (serving/kvcache.py bills it to the slot)."""
        _, Hk, d = _sizes(conf)
        dt = jnp.dtype(conf.state_dtype)
        return {"s": ((Hk, d, state_dim(d)), dt, "slot"),
                "z": ((Hk, state_dim(d)), dt, "slot")}

    def apply_cached(self, conf, params, x, entry, step):
        """One serving step through this layer's state (module
        docstring: what it takes from `step`). -> (y, entry, counts)."""
        q, k, v, log_g = _project(conf, params, x, step.positions)
        first = _starts(step)
        if step.chunk:
            rows = (jnp.arange(x.shape[0]) if step.rows is None
                    else step.rows)
            y, s, z = retention_chunk_rows(
                q, k, v, log_g, entry["s"], entry["z"], rows, first,
                keep=step.keep, eps=conf.sum_eps)
            entry = {"s": s, "z": z}
        elif x.shape[1] != 1:
            raise ValueError(
                "PowerRetentionLayer decodes one token a row a step: a "
                "window of drafts cannot be unwound from its state")
        else:
            live = (jnp.ones_like(first) if step.live is None
                    else jnp.asarray(step.live, bool))
            first = first & live
            g = jnp.where(live[:, None], jnp.exp(log_g[:, 0]), 1.0)
            num, den, s, z = retention_decode(
                entry["s"], entry["z"], q[:, 0],
                jnp.where(live[:, None, None], k[:, 0], 0), v[:, 0],
                jnp.where(first[:, None], 0.0, g))
            y = (num / (den + conf.sum_eps)[..., None]).astype(x.dtype)[:, None]
            entry = {"s": s, "z": z}
        return (_output(conf, params, y), entry,
                {"state_resets": jnp.sum(first, dtype=jnp.int32)})
