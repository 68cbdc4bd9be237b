"""A decoder LM of grouped-attention blocks with sandwich norms, window
and full attention layers side by side, leading dense layers, then
expert layers (`grouped_moe_lm`), via the DAG builder API like
`transformer_lm`.

  x -> Embedding * sqrt(d_model) (no position added)
    -> [ h = x + N2(GroupedAttention_i(N1(x)))
         y = h + N4(FF(N3(h))) ] x L        the sandwich block of
    -> N_f -> head (one matrix, softmax)     models/latent_moe.py

  GroupedAttention_i (nn/layers/grouped_attention.py): `n_heads` queries
  on `n_kv_heads` key-value heads, per-head RMS norms on query and key,
  a sigmoid gate on the output; where `layer_types[i]` is
  "sliding_attention", rotary position and a window of `window` keys;
  where it is "full_attention", NO position and every earlier key.
  FF = a gated dense block in the first `n_dense_layers` layers, a
  dropless expert layer with a shared expert and a selection bias
  (nn/layers/moe.py `DroplessMoELayer`) in the rest.

In a serving cache a window layer holds a ring of `window` rows a slot
and a full layer `capacity` rows: two kinds of row cache in one net.
The expert layers are told which of the router's experts they hold
(`first_expert`, `n_held`), as in `latent_moe_lm`.
"""

from __future__ import annotations

from deeplearning4j_tpu.models.latent_moe import sandwich_moe_lm
from deeplearning4j_tpu.nn.conf import GroupedAttentionLayer
from deeplearning4j_tpu.nn.graph import ComputationGraph

LAYER_TYPES = ("sliding_attention", "full_attention")


def grouped_moe_lm(vocab_size: int, d_model: int, n_heads: int,
                   n_kv_heads: int, head_dim: int, layer_types, window: int,
                   n_dense_layers: int, d_ff: int, n_experts: int, top_k: int,
                   d_expert: int, first_expert: int = 0, n_held: int = 0, *,
                   n_shared: int = 1, routed_scaling: float = 1.0,
                   rope_theta: float = 10000.0,
                   eps: float = 1e-5, seed: int = 12345,
                   learning_rate: float = 3e-4, dtype: str = "float32",
                   param_dtype: str = "float32") -> ComputationGraph:
    """One layer a entry of `layer_types`. `dtype` is the compute type,
    `param_dtype` the type the weights are held in (a server holds them
    in the compute type: no cast a step)."""
    unknown = sorted(set(layer_types) - set(LAYER_TYPES))
    if unknown:
        raise ValueError(f"layer_types holds {unknown}; known: {LAYER_TYPES}")

    def attention(i):
        sliding = layer_types[i] == "sliding_attention"
        return GroupedAttentionLayer(
            n_in=d_model, n_out=d_model, n_heads=n_heads,
            n_kv_heads=n_kv_heads, head_dim=head_dim,
            window=window if sliding else 0,
            rope_theta=rope_theta if sliding else 0.0, eps=eps,
            activation="identity")

    return sandwich_moe_lm(
        attention, vocab_size, d_model, len(layer_types), d_ff=d_ff,
        n_dense_layers=n_dense_layers, n_experts=n_experts, top_k=top_k,
        d_expert=d_expert, n_shared=n_shared, first_expert=first_expert,
        n_held=n_held, routed_scaling=routed_scaling,
        selection_bias=True, embed_scale=float(d_model) ** 0.5,
        eps=eps, seed=seed, learning_rate=learning_rate, dtype=dtype,
        param_dtype=param_dtype)
