"""A decoder LM whose token mixers are gated delta-rule layers and gated
full-attention layers side by side, every layer followed by an expert
layer (`hybrid_moe_lm`), via the DAG builder API like `retention_lm`.

  x -> Embedding (no position added)
    -> [ h = x + Mix_i(N1(x))
         y = h + MoE(N2(h)) ] x L          N = RMS norm, pre-norm
    -> N_f -> head (one matrix, untied, softmax)

  Mix_i, where `layer_types[i]` is
    "linear_attention": a gated delta rule with a short convolution in
      front (nn/layers/gated_deltanet.py `GatedDeltaNetLayer`), vertex
      `blk{i}_gdn`;
    "full_attention": grouped softmax attention with per-head RMS norms,
      an output gate and rotary position over the first `rotary_dim` of
      a head (nn/layers/grouped_attention.py), every earlier key, vertex
      `blk{i}_attn`.
  MoE = a dropless expert layer (nn/layers/moe.py `DroplessMoELayer`)
  routed by a softmax over its top k, with a shared expert behind a
  scalar sigmoid gate.

In a serving cache a delta-rule layer holds a state and a window of
fixed size a slot and a full layer `capacity` rows: states and rows side
by side in one net. The expert layers are told which of the router's
experts they hold (`first_expert`, `n_held`), as in `latent_moe_lm`.
"""

from __future__ import annotations

from deeplearning4j_tpu.models.retention import prenorm_lm
from deeplearning4j_tpu.nn.conf import GatedDeltaNetLayer, GroupedAttentionLayer
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers.moe import DroplessMoELayer

LAYER_TYPES = ("linear_attention", "full_attention")


def hybrid_moe_lm(vocab_size: int, d_model: int, layer_types, *,
                  n_k_heads: int, n_v_heads: int, k_head_dim: int,
                  v_head_dim: int, conv_kernel: int, n_heads: int,
                  n_kv_heads: int, head_dim: int, rotary_dim: int,
                  rope_theta: float, n_experts: int, top_k: int,
                  d_expert: int, first_expert: int = 0, n_held: int = 0,
                  n_shared: int = 1, eps: float = 1e-6,
                  state_dtype: str = "float32", seed: int = 12345,
                  learning_rate: float = 3e-4, dtype: str = "float32",
                  param_dtype: str = "float32") -> ComputationGraph:
    """One layer a entry of `layer_types`. `dtype` is the compute type,
    `param_dtype` the type the weights are held in (a server holds them
    in the compute type: no cast a step), `state_dtype` the type of the
    delta rule's state."""
    unknown = sorted(set(layer_types) - set(LAYER_TYPES))
    if unknown:
        raise ValueError(f"layer_types holds {unknown}; known: {LAYER_TYPES}")

    def mixer(i):
        if layer_types[i] == "linear_attention":
            return "gdn", GatedDeltaNetLayer(
                n_in=d_model, n_out=d_model, n_k_heads=n_k_heads,
                n_v_heads=n_v_heads, k_head_dim=k_head_dim,
                v_head_dim=v_head_dim, conv_kernel=conv_kernel, eps=eps,
                state_dtype=state_dtype, activation="identity")
        return "attn", GroupedAttentionLayer(
            n_in=d_model, n_out=d_model, n_heads=n_heads,
            n_kv_heads=n_kv_heads, head_dim=head_dim, rope_theta=rope_theta,
            rotary_dim=rotary_dim, eps=eps, activation="identity")

    return prenorm_lm(
        mixer,
        lambda i: DroplessMoELayer(
            n_in=d_model, n_out=d_model, n_experts=n_experts, top_k=top_k,
            d_hidden=d_expert, n_shared=n_shared, first_expert=first_expert,
            n_held=n_held, router="softmax", shared_gate=True,
            activation="silu"),
        vocab_size, d_model, len(layer_types), eps=eps, seed=seed,
        learning_rate=learning_rate, dtype=dtype, param_dtype=param_dtype)
