"""A gated delta rule with a short convolution in front: a causal sequence
layer whose memory of the past is a state of fixed size a value head, not a
row a token (`GatedDeltaNetLayer`, nn/conf/layers.py; Yang, Kautz and
Hatamizadeh, "Gated Delta Networks", arXiv:2412.06464; the linear layers
of Qwen3-Next).

For a token u at position t (no projection has a bias; Hk key heads and
Hv value heads, dk and dv wide; value head j reads key head j // (Hv / Hk)):

    [q | k | v] = u Wqkv          Hk dk + Hk dk + Hv dv channels
    z = u Wz [Hv, dv],  b = u Wb [Hv],  a = u Wa [Hv]
    c_t = silu(sum_{i < K} w_i * [q|k|v]_{t-K+1+i})   depthwise over the
                                  channels, causal, no bias; the inputs
                                  before position 0 are zero
    q = l2n(c_q) / sqrt(dk),  k = l2n(c_k),  v = c_v
                                  l2n(x) = x * rsqrt(sum x^2 + 1e-6)
    beta = sigmoid(b),  g = -exp(A_log) * softplus(a + dt_bias)   float32
    S = exp(g_t) S;  d = beta_t (v_t - S^T k_t);  S = S + k_t d^T;
    o_t = S^T q_t                 S [dk, dv] float32 a value head, from 0
    y = RMSNorm(o) * w_n * silu(z)   per value head, one gain vector of dv
    out = concat(y) Wo

`apply` (a whole sequence) and a prefill chunk use the chunked form of
the rule (`ops/gated_delta.gated_delta_chunk`); a decode step is the
recurrence, one pass over the state (`gated_delta_decode`, a Pallas
kernel on a TPU). The convolution, the norms and the gates are `jnp`
around them; the convolution, the rule and the output norm run in
float32.

THE CACHE ENTRY is the layer's own, two arrays a slot whatever the
cache's `capacity`, both billed to the slot (serving/kvcache.py): `S`
[B, Hv, dk, dv] in `state_dtype` (float32 unless the conf says
otherwise) and `conv` [B, K - 1, Hk dk + Hk dk + Hv dv], the last K - 1
inputs of the convolution in the compute dtype, as the program had them.
A prefill chunk reads the window ahead of its own inputs and writes its
last K - 1 kept inputs back (a chunk's kept tokens are its first ones: a
bucket's pad follows them). `kv_dtype="int8"` quantises rows of keys and
values a page at a time; there are none here, so it leaves the entry as
it is. What a running state does not forgive, and a row a token does,
this layer takes from `nn/decode.CacheStep`, as
nn/layers/power_retention.py does:

* a row whose first position in the step is 0 starts a sequence: its
  state AND its window are zeroed before anything is added, and it is
  counted (`state_resets`: the count rides the step's fetch);
* a token with `keep` 0 (the pad of a bucket) adds nothing, decays
  nothing and shifts nothing into the window;
* a row the step says is not `live` leaves both arrays bit for bit;
* a step cannot be unwound (`rewindable(conf)` is False), so
  `nn/decode.make_verify_fn` refuses a net with this layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.layers import GatedDeltaNetLayer
from deeplearning4j_tpu.nn.layers.attention import rms_norm
from deeplearning4j_tpu.nn.layers.base import (
    LayerImpl,
    apply_dropout,
    register_impl,
)
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.ops.gated_delta import (
    gated_delta_chunk,
    gated_delta_decode,
)

L2_EPS = 1e-6


def _sizes(conf):
    return conf.n_k_heads, conf.n_v_heads, conf.k_head_dim, conf.v_head_dim


def _channels(conf) -> int:
    Hk, Hv, dk, dv = _sizes(conf)
    return 2 * Hk * dk + Hv * dv


def _l2n(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _conv(params, u, window):
    """The depthwise causal convolution and its SiLU over u [b, T, C]
    with the K - 1 inputs before it, window [b, K - 1, C], in float32.
    -> (c [b, T, C] float32, the inputs window and u side by side)."""
    w = params["conv"].astype(jnp.float32)                  # [K, C]
    ext = jnp.concatenate([window.astype(u.dtype), u], axis=1)
    T, K = u.shape[1], w.shape[0]
    c = sum(ext[:, i:i + T].astype(jnp.float32) * w[i] for i in range(K))
    return jax.nn.silu(c), ext


def _rule_inputs(conf, params, x, c):
    """The convolution's output c [b, T, C] and x -> q, k [b, T, Hv, dk]
    (each key head repeated for its value heads), v [b, T, Hv, dv], g,
    beta [b, T, Hv], all float32."""
    Hk, Hv, dk, dv = _sizes(conf)
    b, T, _ = x.shape
    q = _l2n(c[..., :Hk * dk].reshape(b, T, Hk, dk)) / dk ** 0.5
    k = _l2n(c[..., Hk * dk:2 * Hk * dk].reshape(b, T, Hk, dk))
    v = c[..., 2 * Hk * dk:].reshape(b, T, Hv, dv)
    q, k = (jnp.repeat(a, Hv // Hk, axis=2) for a in (q, k))
    f32 = jnp.float32
    beta = jax.nn.sigmoid((x @ params["Wb"]).astype(f32))
    a = (x @ params["Wa"]).astype(f32) + params["dt_bias"].astype(f32)
    g = -jnp.exp(params["A_log"].astype(f32)) * jax.nn.softplus(a)
    return q, k, v, g, beta


def _output(conf, params, x, o):
    """o [b, T, Hv, dv] float32 -> [b, T, n_out]: the gated per-head norm,
    then Wo."""
    _, Hv, _, dv = _sizes(conf)
    b, T = o.shape[:2]
    z = (x @ params["Wz"]).reshape(b, T, Hv, dv).astype(jnp.float32)
    y = rms_norm(o, params["norm"], conf.eps) * jax.nn.silu(z)
    return y.reshape(b, T, Hv * dv).astype(x.dtype) @ params["Wo"]


def _window_after(ext, n_kept, K):
    """The K - 1 inputs that end at each row's last kept token: ext [b,
    K - 1 + T, C] holds the window before the step and the step's inputs,
    n_kept [b] how many of those are kept (the first ones)."""
    return jax.vmap(lambda e, n: jax.lax.dynamic_slice_in_dim(
        e, n, K - 1, axis=0))(ext, n_kept)


@register_impl(GatedDeltaNetLayer)
class GatedDeltaNetImpl(LayerImpl):
    """The token mixer of its block, in place of attention: its ops lie
    in the region `attention` (telemetry/recorder.py REGIONS), as every
    token mixer's do."""

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
        Hk, Hv, dk, dv = _sizes(conf)
        if Hv % Hk or conf.conv_kernel < 1:
            raise ValueError(
                f"GatedDeltaNetLayer needs n_v_heads a multiple of n_k_heads "
                f"and a convolution of at least one tap; got {Hv}, {Hk}, "
                f"{conf.conv_kernel}")
        k = jax.random.split(rng, 7)
        C, K = _channels(conf), conf.conv_kernel

        def w(key, shape, **fans):
            return init_weights(key, shape, conf.weight_init, conf.dist, dtype,
                                **fans)

        return {"Wqkv": w(k[0], (conf.n_in, C)),
                "Wz": w(k[1], (conf.n_in, Hv * dv)),
                "Wb": w(k[2], (conf.n_in, Hv)),
                "Wa": w(k[3], (conf.n_in, Hv)),
                "conv": w(k[4], (K, C), fan_in=K, fan_out=K),
                # the published init: A uniform in [0, 16), dt_bias 1
                "A_log": jnp.log(jax.random.uniform(
                    k[5], (Hv,), jnp.float32, 1.0, 16.0)),
                "dt_bias": jnp.ones((Hv,), jnp.float32),
                "norm": jnp.ones((dv,), dtype),
                "Wo": w(k[6], (Hv * dv, conf.n_out))}, {}

    def apply(self, conf, params, state, x, *, train=False, rng=None,
              mask=None):
        if conf.dropout:
            x = apply_dropout(x, conf.dropout, rng, train=train)
        b = x.shape[0]
        _, Hv, dk, dv = _sizes(conf)
        u = x @ params["Wqkv"]
        c, _ = _conv(params, u, jnp.zeros((b, conf.conv_kernel - 1,
                                            u.shape[-1]), u.dtype))
        q, k, v, g, beta = _rule_inputs(conf, params, x, c)
        o, _ = gated_delta_chunk(
            q, k, v, g, beta, jnp.zeros((b, Hv, dk, dv), jnp.float32),
            keep=mask)
        return _output(conf, params, x, o), state

    def cache_arrays(self, conf, capacity, kv_dtype, page_size, dtype):
        """What one decode slot of this layer holds, whatever `capacity`
        and `kv_dtype`: {name: (shape, dtype, "slot")}; the third entry
        says that the array is no row a position (serving/kvcache.py
        bills it to the slot)."""
        _, Hv, dk, dv = _sizes(conf)
        return {"S": ((Hv, dk, dv), jnp.dtype(conf.state_dtype), "slot"),
                "conv": ((conf.conv_kernel - 1, _channels(conf)), dtype,
                         "slot")}

    def apply_cached(self, conf, params, x, entry, step):
        """One serving step through this layer's state and window (module
        docstring: what it takes from `step`). -> (y, entry, counts)."""
        b, T, _ = x.shape
        K = conf.conv_kernel
        first = step.positions[:, 0] == 0
        u = x @ params["Wqkv"]
        if step.chunk:
            rows = jnp.arange(b) if step.rows is None else step.rows
            keep = (jnp.ones((b, T), jnp.float32) if step.keep is None
                    else step.keep.astype(jnp.float32))
            window = jnp.where(first[:, None, None], 0, entry["conv"][rows])
            c, ext = _conv(params, u, window)
            q, k, v, g, beta = _rule_inputs(conf, params, x, c)
            o, S = gated_delta_chunk(
                q, k, v, g, beta,
                jnp.where(first[:, None, None, None], 0, entry["S"][rows]),
                keep=keep)
            n_kept = jnp.sum(keep > 0, axis=1).astype(jnp.int32)
            with jax.named_scope("cache_write"):
                entry = {"S": entry["S"].at[rows].set(S),
                         "conv": entry["conv"].at[rows].set(
                             _window_after(ext, n_kept, K))}
        elif T != 1:
            raise ValueError(
                "GatedDeltaNetLayer decodes one token a row a step: a "
                "window of drafts cannot be unwound from its state")
        else:
            live = (jnp.ones_like(first) if step.live is None
                    else jnp.asarray(step.live, bool))
            first = first & live
            window = jnp.where(first[:, None, None], 0, entry["conv"])
            c, ext = _conv(params, u, window)
            q, k, v, g, beta = _rule_inputs(conf, params, x, c)
            decay = jnp.where(first[:, None], 0.0, jnp.exp(g[:, 0]))
            o, S = gated_delta_decode(entry["S"], q[:, 0], k[:, 0], v[:, 0],
                                      decay, beta[:, 0], live)
            o = o[:, None]
            entry = {"S": S, "conv": jnp.where(live[:, None, None],
                                               ext[:, 1:], entry["conv"])}
        return (_output(conf, params, x, o), entry,
                {"state_resets": jnp.sum(first, dtype=jnp.int32)})
