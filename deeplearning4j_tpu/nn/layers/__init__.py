"""Layer implementations. Importing this package registers every impl."""

from deeplearning4j_tpu.nn.layers.base import (  # noqa: F401
    LayerImpl,
    apply_dropout,
    get_impl,
    l1_l2_penalty,
    register_impl,
)
import deeplearning4j_tpu.nn.layers.feedforward  # noqa: F401
import deeplearning4j_tpu.nn.layers.convolution  # noqa: F401
import deeplearning4j_tpu.nn.layers.recurrent  # noqa: F401
import deeplearning4j_tpu.nn.layers.attention  # noqa: F401
import deeplearning4j_tpu.nn.layers.latent_attention  # noqa: F401
import deeplearning4j_tpu.nn.layers.grouped_attention  # noqa: F401
import deeplearning4j_tpu.nn.layers.moe  # noqa: F401
import deeplearning4j_tpu.nn.layers.power_retention  # noqa: F401
import deeplearning4j_tpu.nn.layers.gated_deltanet  # noqa: F401
import deeplearning4j_tpu.nn.layers.nested  # noqa: F401
