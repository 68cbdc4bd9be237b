"""Layer configuration dataclasses (reference conf/layers/* — 19 classes).

Each config is a declarative, JSON-serializable description; the matching
implementation (init + pure apply fn) lives in deeplearning4j_tpu/nn/layers/.
Fields left as None inherit the global defaults from the enclosing
NeuralNetConfiguration (reference Builder semantics:
NeuralNetConfiguration.java:338-373).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

from deeplearning4j_tpu.nn.conf.distributions import Distribution
from deeplearning4j_tpu.nn.conf.enums import (
    ConvolutionMode,
    HiddenUnit,
    PoolingType,
    VisibleUnit,
)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.serde import register_config


@register_config
@dataclasses.dataclass
class Layer:
    """Base layer config (reference conf/layers/Layer.java)."""

    name: Optional[str] = None
    activation: Optional[str] = None
    weight_init: Optional[str] = None
    dist: Optional[Distribution] = None
    bias_init: Optional[float] = None
    dropout: Optional[float] = None
    drop_connect: Optional[bool] = None
    l1: Optional[float] = None
    l2: Optional[float] = None
    learning_rate: Optional[float] = None
    updater: Optional[str] = None
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: Optional[float] = None

    # --- shape inference hooks (ConvolutionLayerSetup analogue) ---
    def set_n_in(self, input_type: InputType) -> None:  # noqa: B027
        """Infer and set n_in from the incoming InputType (no-op by default)."""

    def get_output_type(self, input_type: InputType) -> InputType:
        return input_type

    def is_pretrain_layer(self) -> bool:
        return False


@register_config
@dataclasses.dataclass
class FeedForwardLayer(Layer):
    """Base for layers with dense n_in→n_out params."""

    n_in: int = 0
    n_out: int = 0

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = input_type.flat_size()

    def get_output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "recurrent":
            return InputType.recurrent(self.n_out, input_type.timeseries_length)
        return InputType.feed_forward(self.n_out)


@register_config
@dataclasses.dataclass
class DenseLayer(FeedForwardLayer):
    """Fully-connected layer (reference layers/feedforward/dense/DenseLayer.java)."""


@register_config
@dataclasses.dataclass
class BaseOutputLayer(FeedForwardLayer):
    loss_function: str = "mcxent"
    has_bias: bool = True  # False: a head that is one matrix, z = x W

    def has_loss(self) -> bool:
        return True


@register_config
@dataclasses.dataclass
class OutputLayer(BaseOutputLayer):
    """Output layer with loss (reference conf/layers/OutputLayer.java)."""


@register_config
@dataclasses.dataclass
class RnnOutputLayer(BaseOutputLayer):
    """Per-timestep output layer (reference layers/recurrent/RnnOutputLayer.java).
    Input [batch, time, n_in] → output [batch, time, n_out]."""

    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timeseries_length)


@register_config
@dataclasses.dataclass
class ActivationLayer(Layer):
    """Pure activation layer (reference conf/layers/ActivationLayer.java)."""


@register_config
@dataclasses.dataclass
class DropoutLayer(Layer):
    """Standalone dropout layer (TPU-build convenience)."""


@register_config
@dataclasses.dataclass
class BasePretrainNetwork(FeedForwardLayer):
    loss_function: str = "reconstruction_crossentropy"
    visible_bias_init: float = 0.0

    def is_pretrain_layer(self) -> bool:
        return True


@register_config
@dataclasses.dataclass
class AutoEncoder(BasePretrainNetwork):
    """Denoising autoencoder (reference layers/feedforward/autoencoder/AutoEncoder.java).
    corruption_level = input corruption probability; sparsity = KL target."""

    corruption_level: float = 0.3
    sparsity: float = 0.0


@register_config
@dataclasses.dataclass
class RBM(BasePretrainNetwork):
    """Restricted Boltzmann machine trained by CD-k (reference
    layers/feedforward/rbm/RBM.java: contrastiveDivergence:101, Gibbs
    sampling gibbhVh:149-151, unit types :197-205)."""

    hidden_unit: str = HiddenUnit.BINARY
    visible_unit: str = VisibleUnit.BINARY
    k: int = 1
    sparsity: float = 0.0


@register_config
@dataclasses.dataclass
class EmbeddingLayer(FeedForwardLayer):
    """Index → vector lookup (reference layers/feedforward/embedding/EmbeddingLayer.java).
    Input is int indices [batch] or [batch, 1]; lookup is a gather (one-hot
    matmul on MXU for small vocabularies)."""

    has_bias: bool = True


@register_config
@dataclasses.dataclass
class ConvolutionLayer(FeedForwardLayer):
    """2-D convolution (reference layers/convolution/ConvolutionLayer.java).

    The reference lowers conv to im2col+gemm (ConvolutionLayer.java:120-151);
    here it is a single `lax.conv_general_dilated` in NHWC which XLA maps
    directly onto the MXU. n_in = input channels, n_out = output channels.
    """

    kernel_size: tuple = (5, 5)
    stride: tuple = (1, 1)
    padding: tuple = (0, 0)
    convolution_mode: str = ConvolutionMode.STRICT
    dilation: tuple = (1, 1)

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in == 0 and input_type.kind in ("convolutional", "convolutional_flat"):
            self.n_in = input_type.channels

    def get_output_type(self, input_type: InputType) -> InputType:
        h, w = _conv_out_hw(
            input_type.height, input_type.width, self.kernel_size, self.stride,
            self.padding, self.convolution_mode, self.dilation,
        )
        return InputType.convolutional(h, w, self.n_out)


@register_config
@dataclasses.dataclass
class SubsamplingLayer(Layer):
    """Pooling layer (reference layers/convolution/subsampling/SubsamplingLayer.java;
    PoolingType at conf/layers/SubsamplingLayer.java:29-30). Lowors to
    `lax.reduce_window`."""

    pooling_type: str = PoolingType.MAX
    kernel_size: tuple = (2, 2)
    stride: tuple = (2, 2)
    padding: tuple = (0, 0)
    convolution_mode: str = ConvolutionMode.STRICT
    pnorm: int = 2

    def get_output_type(self, input_type: InputType) -> InputType:
        h, w = _conv_out_hw(
            input_type.height, input_type.width, self.kernel_size, self.stride,
            self.padding, self.convolution_mode, (1, 1),
        )
        return InputType.convolutional(h, w, input_type.channels)


@register_config
@dataclasses.dataclass
class BatchNormalization(FeedForwardLayer):
    """Batch normalization (reference layers/normalization/BatchNormalization.java:
    batch stats :191-193, gamma/beta :176-205, cumulative inference stats
    :196-197). Running stats live in the network's mutable `state` pytree,
    not in params — the functional-JAX idiom."""

    decay: float = 0.9
    eps: float = 1e-5
    gamma: float = 1.0
    beta: float = 0.0
    lock_gamma_beta: bool = False

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in == 0:
            if input_type.kind in ("convolutional",):
                self.n_in = input_type.channels
            else:
                self.n_in = input_type.flat_size()
        if self.n_out == 0:
            self.n_out = self.n_in

    def get_output_type(self, input_type: InputType) -> InputType:
        return input_type


@register_config
@dataclasses.dataclass
class LocalResponseNormalization(Layer):
    """LRN across channels (reference layers/normalization/LocalResponseNormalization.java)."""

    k: float = 2.0
    n: float = 5.0
    alpha: float = 1e-4
    beta: float = 0.75


@register_config
@dataclasses.dataclass
class BaseRecurrentLayer(FeedForwardLayer):
    """Base for RNN layers; activations are [batch, time, features]."""

    def get_output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timeseries_length)


@register_config
@dataclasses.dataclass
class GravesLSTM(BaseRecurrentLayer):
    """LSTM with peephole connections, per Graves (2013) — reference
    layers/recurrent/GravesLSTM.java + LSTMHelpers.java (fwd :50-180, bwd
    :210+; peephole params GravesLSTMParamInitializer.java:86-87).

    The per-timestep loop is a `lax.scan`; the 4 gates are one fused
    [n_in+n_out, 4*n_out] matmul per step. Backward is jax.grad through the
    scan (no hand-written BPTT)."""

    forget_gate_bias_init: float = 1.0


@register_config
@dataclasses.dataclass
class LSTM(BaseRecurrentLayer):
    """Standard LSTM without peepholes (TPU-era staple; cuDNN-compatible)."""

    forget_gate_bias_init: float = 1.0


@register_config
@dataclasses.dataclass
class GravesBidirectionalLSTM(BaseRecurrentLayer):
    """Bidirectional Graves LSTM (reference layers/recurrent/GravesBidirectionalLSTM.java).
    Output is the sum of forward and backward passes (reference merges by sum)."""

    forget_gate_bias_init: float = 1.0


@register_config
@dataclasses.dataclass
class GRU(BaseRecurrentLayer):
    """Gated recurrent unit (reference layers/recurrent/GRU.java)."""


@register_config
@dataclasses.dataclass
class LayerNormalization(FeedForwardLayer):
    """Layer norm over the feature axis — new capability for the Transformer
    north star (no reference analogue; SURVEY.md §7 step 6)."""

    eps: float = 1e-5

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = input_type.flat_size()
        if self.n_out == 0:
            self.n_out = self.n_in

    def get_output_type(self, input_type: InputType) -> InputType:
        return input_type


@register_config
@dataclasses.dataclass
class RMSNormalization(LayerNormalization):
    """Root-mean-square norm over the feature axis:
    x * rsqrt(mean(x^2) + eps) * gamma. No mean is taken off, no offset
    is added."""


@register_config
@dataclasses.dataclass
class GatedDenseLayer(FeedForwardLayer):
    """Gated feed-forward block without biases:
    y = (act(x Wgate) * (x Wup)) Wdown, `d_hidden` wide inside (the
    default activation is silu)."""

    d_hidden: int = 0  # defaults to 4 * n_in

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = input_type.flat_size()
        if self.n_out == 0:
            self.n_out = self.n_in


@register_config
@dataclasses.dataclass
class PositionalEncodingLayer(Layer):
    """Adds positional information to [batch, time, features] — sinusoidal
    (param-free) or learned. New capability for the Transformer north star."""

    learned: bool = False
    max_length: int = 2048
    n_features: int = 0
    # inside a sequence-parallel shard_map (see SelfAttentionLayer), each
    # shard holds rows [idx*Tl, (idx+1)*Tl) of the sequence: offset the
    # encodings by the shard's global position
    seq_parallel_axis: str = ""

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_features == 0:
            self.n_features = input_type.flat_size()

    def get_output_type(self, input_type: InputType) -> InputType:
        return input_type


@register_config
@dataclasses.dataclass
class SelfAttentionLayer(BaseRecurrentLayer):
    """Multi-head self-attention over [batch, time, features] — new capability
    for the Transformer north star (SURVEY.md §7 step 6). Supports causal
    masking and optional ring-attention sequence parallelism (parallel/)."""

    n_heads: int = 8
    causal: bool = True
    attention_dropout: float = 0.0
    use_flash: bool = True  # fused Pallas kernel when the case supports it
    # when set, the layer runs INSIDE shard_map over a mesh axis of this
    # name with the time dimension sharded: attention becomes the ppermute
    # ring (parallel/ring_attention.py) so each shard only ever holds its
    # local K/V block — the sequence-parallel training path
    # (parallel/sequence_parallel.py)
    seq_parallel_axis: str = ""

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = input_type.flat_size()
        if self.n_out == 0:
            self.n_out = self.n_in


@register_config
@dataclasses.dataclass
class LatentAttentionLayer(BaseRecurrentLayer):
    """Causal multi-head attention through low-rank latents, no biases
    (nn/layers/latent_attention.py holds the equations). The query goes
    through a normalised `q_rank` latent; keys and values are expanded
    from one normalised `kv_rank` latent a token, which with the one
    rotary key slice shared by all heads is the whole cache row:
    `kv_rank + rope_dim` values a token, whatever the head count. Each
    head's query and key are a `nope_dim` part without position and a
    `rope_dim` part with rotary position; values are `v_dim` wide."""

    n_heads: int = 8
    q_rank: int = 0
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    rope_theta: float = 10000.0
    eps: float = 1e-5
    causal: bool = True

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = input_type.flat_size()
        if self.n_out == 0:
            self.n_out = self.n_in


@register_config
@dataclasses.dataclass
class PowerRetentionLayer(BaseRecurrentLayer):
    """Causal power retention of degree 2 with grouped heads, no biases
    but the gate's (nn/layers/power_retention.py holds the equations):
    `n_heads` queries of `head_dim` read the states of `n_kv_heads`
    key-value heads, `n_heads / n_kv_heads` queries a state; RMS norms
    with learned gains on each head's query and key, rotary position,
    and one scalar gate a key-value head a token. What the layer keeps
    of the past is a STATE of fixed size a sequence (`state_dtype`;
    float32: it is a sum over thousands of tokens), not a row a token:
    a serving step cannot be unwound from it."""

    n_heads: int = 8
    n_kv_heads: int = 0         # defaults to n_heads
    head_dim: int = 0           # defaults to n_out / n_heads
    rope_theta: float = 10000.0
    eps: float = 1e-6           # of the query's and the key's RMS norm
    sum_eps: float = 1e-6       # added to the sum of weights y is divided by
    state_dtype: str = "float32"

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = input_type.flat_size()
        if self.n_out == 0:
            self.n_out = self.n_in


@register_config
@dataclasses.dataclass
class GroupedAttentionLayer(BaseRecurrentLayer):
    """Causal softmax attention with grouped heads, no biases
    (nn/layers/grouped_attention.py holds the equations): `n_heads`
    queries of `head_dim` read `n_kv_heads` key-value heads,
    `n_heads / n_kv_heads` queries a head. `window` keys at most are
    seen by a query, its own among them (0: every earlier key);
    `rope_theta` turns queries and keys by their position (0: no
    position at all), and of a head only the first `rotary_dim`
    dimensions where that is not 0 (the rest pass unturned). Where
    `qk_norm`, each head's query and each head's key pass an RMS norm
    with one learned gain vector; where `gate`, the heads' output is
    multiplied by sigmoid(x Wg) before the output projection (with
    both off: plain multi-head attention). A layer with a window keeps
    a RING of `window` rows a sequence in a serving cache, whatever the
    cache's capacity."""

    n_heads: int = 8
    n_kv_heads: int = 0         # defaults to n_heads
    head_dim: int = 0           # defaults to n_out / n_heads
    window: int = 0             # 0: full attention
    rope_theta: float = 0.0     # 0: no position
    rotary_dim: int = 0         # dimensions turned; 0: the whole head
    eps: float = 1e-5           # of the query's and the key's RMS norm
    qk_norm: bool = True        # RMS norms on each head's query and key
    gate: bool = True           # sigmoid(x Wg) on the heads' output

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = input_type.flat_size()
        if self.n_out == 0:
            self.n_out = self.n_in


@register_config
@dataclasses.dataclass
class GatedDeltaNetLayer(BaseRecurrentLayer):
    """A causal gated delta rule with a short convolution in front, no
    biases (nn/layers/gated_deltanet.py holds the equations): `n_v_heads`
    value heads of `v_head_dim` each keep a state [k_head_dim,
    v_head_dim] that a per-head decay fades and the delta rule corrects
    toward each new value; `n_k_heads` key heads, `n_v_heads /
    n_k_heads` value heads a key head. A depthwise causal convolution of
    width `conv_kernel` runs over the query, key and value channels
    first. What the layer keeps of the past is that state and the
    convolution's last `conv_kernel - 1` inputs, of fixed size a
    sequence (`state_dtype`; float32: it is a sum over thousands of
    tokens): a serving step cannot be unwound from it."""

    n_k_heads: int = 16
    n_v_heads: int = 32
    k_head_dim: int = 128
    v_head_dim: int = 128
    conv_kernel: int = 4
    eps: float = 1e-6           # of the output's per-head RMS norm
    state_dtype: str = "float32"

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = input_type.flat_size()
        if self.n_out == 0:
            self.n_out = self.n_in


def _conv_out_hw(h, w, kernel, stride, padding, mode, dilation):
    kh = (kernel[0] - 1) * dilation[0] + 1
    kw = (kernel[1] - 1) * dilation[1] + 1
    if mode == ConvolutionMode.SAME or mode == "same":
        return -(-h // stride[0]), -(-w // stride[1])
    if mode == ConvolutionMode.VALID or mode == "valid":
        return (h - kh) // stride[0] + 1, (w - kw) // stride[1] + 1
    return (
        (h + 2 * padding[0] - kh) // stride[0] + 1,
        (w + 2 * padding[1] - kw) // stride[1] + 1,
    )


def validate_layer_names(layer_conf) -> None:
    """Eagerly resolve a layer conf's string-named activation / loss so a
    typo'd name fails at init() with a named ValueError instead of at first
    trace (the reference fails at conf time via its enums)."""
    from deeplearning4j_tpu.ops.activations import get_activation
    from deeplearning4j_tpu.ops.losses import validate_loss

    act = getattr(layer_conf, "activation", None)
    if act is not None:
        get_activation(act)
    loss = getattr(layer_conf, "loss_function", None)
    if loss is not None:
        validate_loss(loss)
