"""A decoder LM of latent-attention blocks with sandwich norms: leading
dense layers, then expert layers (`latent_moe_lm`), via the DAG builder
API like `transformer_lm`.

  x -> Embedding (no position added: attention carries rotary position)
    -> [ h = x + N2(LatentAttention(N1(x)))
         y = h + N4(FF(N3(h))) ] x L        N = RMS norm; four a layer,
    -> N_f -> head (one matrix, softmax)     the second and fourth on the
                                             sublayer's OUTPUT
  FF = a gated dense block in the first `n_dense_layers` layers, a
  dropless expert layer with a shared expert (nn/layers/moe.py
  `DroplessMoELayer`) in the rest.

The expert layers are told which of the router's experts they hold
(`first_expert`, `n_held`): one chip's share of an expert-parallel
deployment is built by passing its share, and a vocabulary cut to a
slice by passing the slice's size.
"""

from __future__ import annotations

from deeplearning4j_tpu.nn.conf import (
    ElementWiseVertexConf,
    EmbeddingLayer,
    GatedDenseLayer,
    InputType,
    LatentAttentionLayer,
    NeuralNetConfiguration,
    RMSNormalization,
    RnnOutputLayer,
    ScaleVertexConf,
    Updater,
)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.layers.moe import DroplessMoELayer


def sandwich_moe_lm(attention, vocab_size: int, d_model: int, n_layers: int,
                    *, d_ff: int, n_dense_layers: int, n_experts: int = 0,
                    top_k: int = 0, d_expert: int = 0, n_shared: int = 0,
                    first_expert: int = 0, n_held: int = 0,
                    routed_scaling: float = 1.0,
                    selection_bias: bool = False, embed_scale: float = 1.0,
                    times: int = 0, eps: float, seed: int,
                    learning_rate: float, dtype: str,
                    param_dtype: str) -> ComputationGraph:
    """The sandwich block around any attention: `attention(i)` gives
    layer i's attention conf (vertex `blk{i}_attn`); the feed-forward
    half, the four norms, the embedding (times `embed_scale` where that
    is not 1) and the head are this function's. `latent_moe_lm` and
    `models.grouped_moe.grouped_moe_lm` are this with their attention.
    `times` > 0 loops the stack: the blocks and the final norm (`blk0_n1`
    through `norm_f`) run `times` times a token with one set of weights
    (`GraphBuilder.loop`), and the head reads the last pass."""
    g = (
        NeuralNetConfiguration.builder()
        .seed(seed)
        .learning_rate(learning_rate)
        .updater(Updater.ADAM)
        .weight_init("xavier")
        .dtype(dtype)
        .param_dtype(param_dtype)
        .graph_builder()
        .add_inputs("tokens")
    )

    def norm(name, src):
        g.add_layer(name, RMSNormalization(n_in=d_model, n_out=d_model,
                                           eps=eps), src)
        return name

    g.add_layer("embed", EmbeddingLayer(n_in=vocab_size, n_out=d_model,
                                        activation="identity", has_bias=False),
                "tokens")
    prev = "embed"
    if embed_scale != 1.0:
        g.add_vertex("embed_scaled", ScaleVertexConf(scale=embed_scale), prev)
        prev = "embed_scaled"
    for i in range(n_layers):
        b = f"blk{i}"
        g.add_layer(f"{b}_attn", attention(i), norm(f"{b}_n1", prev))
        g.add_vertex(f"{b}_res1", ElementWiseVertexConf(op="add"),
                     prev, norm(f"{b}_n2", f"{b}_attn"))
        src = norm(f"{b}_n3", f"{b}_res1")
        if i < n_dense_layers:
            g.add_layer(f"{b}_ff", GatedDenseLayer(
                n_in=d_model, n_out=d_model, d_hidden=d_ff,
                activation="silu"), src)
        else:
            g.add_layer(f"{b}_ff", DroplessMoELayer(
                n_in=d_model, n_out=d_model, n_experts=n_experts,
                top_k=top_k, d_hidden=d_expert, n_shared=n_shared,
                first_expert=first_expert, n_held=n_held,
                routed_scaling=routed_scaling,
                selection_bias=selection_bias, activation="silu"), src)
        g.add_vertex(f"{b}_res2", ElementWiseVertexConf(op="add"),
                     f"{b}_res1", norm(f"{b}_n4", f"{b}_ff"))
        prev = f"{b}_res2"
    g.add_layer("out", RnnOutputLayer(
        n_in=d_model, n_out=vocab_size, activation="softmax",
        loss_function="mcxent", has_bias=False), norm("norm_f", prev))
    g.set_outputs("out")
    if times:
        g.loop("blk0_n1", "norm_f", times)
    g.set_input_types(tokens=InputType.recurrent(1))
    return ComputationGraph(g.build())


def latent_moe_lm(vocab_size: int, d_model: int, n_heads: int, n_layers: int,
                  *, q_rank: int, kv_rank: int, nope_dim: int, rope_dim: int,
                  v_dim: int, d_ff: int, n_dense_layers: int = 1,
                  n_experts: int = 8, top_k: int = 2, d_expert: int = 0,
                  n_shared: int = 1, first_expert: int = 0, n_held: int = 0,
                  routed_scaling: float = 1.0, rope_theta: float = 10000.0,
                  eps: float = 1e-5, seed: int = 12345,
                  learning_rate: float = 3e-4, dtype: str = "float32",
                  param_dtype: str = "float32") -> ComputationGraph:
    """`dtype` is the compute type, `param_dtype` the type the weights
    are held in (a server holds them in the compute type: no cast a
    step)."""
    return sandwich_moe_lm(
        lambda i: LatentAttentionLayer(
            n_in=d_model, n_out=d_model, n_heads=n_heads, q_rank=q_rank,
            kv_rank=kv_rank, nope_dim=nope_dim, rope_dim=rope_dim,
            v_dim=v_dim, rope_theta=rope_theta, eps=eps,
            activation="identity"),
        vocab_size, d_model, n_layers, d_ff=d_ff,
        n_dense_layers=n_dense_layers, n_experts=n_experts, top_k=top_k,
        d_expert=d_expert, n_shared=n_shared, first_expert=first_expert,
        n_held=n_held, routed_scaling=routed_scaling, eps=eps, seed=seed,
        learning_rate=learning_rate, dtype=dtype, param_dtype=param_dtype)
