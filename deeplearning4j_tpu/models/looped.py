"""A decoder LM whose whole stack runs several times a token with one set
of weights (`looped_lm`), via the DAG builder API like `grouped_moe_lm`.

  x0 -> Embedding (no position added)
  x^(t+1) = N_f(Block_{L-1}(... Block_0(x^(t))))   t = 0 .. times - 1
       Block_i: h = x + N2(Attn_i(N1(x)))          the sandwich block of
                y = h + N4(FF_i(N3(h)))            models/latent_moe.py
  logits = x^(times) W_out  (one matrix, untied, softmax)

  Attn_i (nn/layers/grouped_attention.py): `n_heads` queries on
  `n_kv_heads` key-value heads of `head_dim`, rotary over the whole head,
  every earlier key, no per-head norm and no output gate: plain
  multi-head attention where the two counts are equal. FF_i: a gated
  dense block, silu(u Wgate) * (u Wup) -> Wdown.

The blocks and the final norm are the graph's loop (`GraphBuilder.loop`,
`blk0_n1` through `norm_f`): every pass reuses every block's weights, and
the final norm closes every pass. In a serving cache every (layer, pass)
keeps its own rows: a layer's entry has a pass axis (nn/decode.py).
"""

from __future__ import annotations

from deeplearning4j_tpu.models.latent_moe import sandwich_moe_lm
from deeplearning4j_tpu.nn.conf import GroupedAttentionLayer
from deeplearning4j_tpu.nn.graph import ComputationGraph


def looped_lm(vocab_size: int, d_model: int, n_heads: int, n_layers: int,
              times: int, *, d_ff: int, n_kv_heads: int = 0,
              head_dim: int = 0, rope_theta: float = 10000.0,
              eps: float = 1e-6, seed: int = 12345,
              learning_rate: float = 3e-4, dtype: str = "float32",
              param_dtype: str = "float32") -> ComputationGraph:
    """`times` passes of `n_layers` blocks. `dtype` is the compute type,
    `param_dtype` the type the weights are held in (a server holds them
    in the compute type: no cast a step)."""
    return sandwich_moe_lm(
        lambda i: GroupedAttentionLayer(
            n_in=d_model, n_out=d_model, n_heads=n_heads,
            n_kv_heads=n_kv_heads, head_dim=head_dim, rope_theta=rope_theta,
            eps=eps, qk_norm=False, gate=False, activation="identity"),
        vocab_size, d_model, n_layers, d_ff=d_ff, n_dense_layers=n_layers,
        times=times, eps=eps, seed=seed, learning_rate=learning_rate,
        dtype=dtype, param_dtype=param_dtype)
