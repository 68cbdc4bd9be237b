"""A decoder LM of power-retention blocks (`retention_lm`), via the DAG
builder API like `transformer_lm` and `latent_moe_lm`.

  x -> Embedding (no position added: the layer carries rotary position)
    -> [ h = x + PowerRetention(N1(x))
         y = h + GatedDense(N2(h)) ] x L      N = RMS norm, pre-norm
    -> N_f -> head (one matrix, untied, softmax)

No bias anywhere but the retention gate's. Grouped heads: `n_heads`
queries read the states of `n_kv_heads` key-value heads
(nn/layers/power_retention.py holds the equations). What a server keeps
for a sequence is a state of fixed size a layer, whatever its length.
"""

from __future__ import annotations

from deeplearning4j_tpu.nn.conf import (
    ElementWiseVertexConf,
    EmbeddingLayer,
    GatedDenseLayer,
    InputType,
    NeuralNetConfiguration,
    PowerRetentionLayer,
    RMSNormalization,
    RnnOutputLayer,
    Updater,
)
from deeplearning4j_tpu.nn.graph import ComputationGraph


def prenorm_lm(mixer, ff, vocab_size: int, d_model: int, n_layers: int, *,
               eps: float, seed: int, learning_rate: float, dtype: str,
               param_dtype: str) -> ComputationGraph:
    """The pre-norm block around any token mixer and feed-forward layer:
    `mixer(i)` gives layer i's (name, conf), its vertex `blk{i}_<name>`,
    and `ff(i)` the conf of its `blk{i}_ff`; the two norms a block, the
    embedding (nothing added for position), the final norm and the
    untied head are this function's. `retention_lm` and
    `models.hybrid_moe.hybrid_moe_lm` are this with their layers."""
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
    for i in range(n_layers):
        b = f"blk{i}"
        name, conf = mixer(i)
        g.add_layer(f"{b}_{name}", conf, norm(f"{b}_n1", prev))
        g.add_vertex(f"{b}_res1", ElementWiseVertexConf(op="add"),
                     prev, f"{b}_{name}")
        g.add_layer(f"{b}_ff", ff(i), norm(f"{b}_n2", f"{b}_res1"))
        g.add_vertex(f"{b}_res2", ElementWiseVertexConf(op="add"),
                     f"{b}_res1", f"{b}_ff")
        prev = f"{b}_res2"
    g.add_layer("out", RnnOutputLayer(
        n_in=d_model, n_out=vocab_size, activation="softmax",
        loss_function="mcxent", has_bias=False), norm("norm_f", prev))
    g.set_outputs("out")
    g.set_input_types(tokens=InputType.recurrent(1))
    return ComputationGraph(g.build())


def retention_lm(vocab_size: int, d_model: int, n_heads: int,
                 n_kv_heads: int, n_layers: int, d_ff: int, *,
                 head_dim: int = 0, rope_theta: float = 10000.0,
                 eps: float = 1e-6, sum_eps: float = 1e-6,
                 state_dtype: str = "float32",
                 seed: int = 12345, learning_rate: float = 3e-4,
                 dtype: str = "float32",
                 param_dtype: str = "float32") -> ComputationGraph:
    """`dtype` is the compute type, `param_dtype` the type the weights
    are held in (a server holds them in the compute type: no cast a
    step), `state_dtype` the type of the retention state."""
    return prenorm_lm(
        lambda i: ("ret", PowerRetentionLayer(
            n_in=d_model, n_out=d_model, n_heads=n_heads,
            n_kv_heads=n_kv_heads, head_dim=head_dim, rope_theta=rope_theta,
            eps=eps, sum_eps=sum_eps, state_dtype=state_dtype,
            activation="identity")),
        lambda i: GatedDenseLayer(n_in=d_model, n_out=d_model, d_hidden=d_ff,
                                  activation="silu"),
        vocab_size, d_model, n_layers, eps=eps, seed=seed,
        learning_rate=learning_rate, dtype=dtype, param_dtype=param_dtype)
