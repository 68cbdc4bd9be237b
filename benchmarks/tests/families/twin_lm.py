"""A second model family, for `test_families.py` alone: found by
`"model_type": "twin_lm"` through `spec.family_of` like any other, with
key names of its own (`hidden_size`, `num_hidden_layers`, ...) and FLOP
counts that are twice the GPT-2 family's on purpose, so that a test can
tell whose counts a reader used. Everything else it borrows from the
GPT-2 family by translating its keys: it proves the seam, it is no
pattern for a real family (a real one brings its own net, weights and
reference; `benchmarks/families/__init__.py` has the interface)."""
from harness import spec

_gpt2 = spec.family_of({"model_type": "gpt2"})
_KEYS = {"hidden_size": "n_embd", "num_attention_heads": "n_head",
         "num_hidden_layers": "n_layer", "intermediate_size": "n_inner",
         "max_position_embeddings": "n_positions",
         "norm_eps": "layer_norm_epsilon"}
# the parent's counts as they were when this module was loaded: a test
# that breaks the GPT-2 family's entry points does not break these
_train, _prefill, _decode = (_gpt2.train_flops_per_token,
                             _gpt2.prefill_flops, _gpt2.decode_flops)


def _as_gpt2(config: dict) -> dict:
    return {_KEYS.get(k, k): v for k, v in config.items()}


def dims_of(config):
    return _gpt2.dims_of(_as_gpt2(config))


def serving_net(config, seed, dims):
    return _gpt2.serving_net(_as_gpt2(config), seed, dims)


def training_net(config, seed, dims):
    return _gpt2.training_net(_as_gpt2(config), seed, dims)


give_weights = _gpt2.give_weights
served_gaps = _gpt2.served_gaps
first_moment_tree = _gpt2.first_moment_tree
program_sq_norms = _gpt2.program_sq_norms
program_projections = _gpt2.program_projections
seeded_program_tree = _gpt2.seeded_program_tree
reference_readings = _gpt2.reference_readings
decode_step_min_bytes = _gpt2.decode_step_min_bytes
kv_bytes_per_token = _gpt2.kv_bytes_per_token
count_params = _gpt2.count_params


def train_flops_per_token(dims, seq_len):
    return 2 * _train(dims, seq_len)


def prefill_flops(dims, prompt_len):
    return 2 * _prefill(dims, prompt_len)


def decode_flops(dims, context):
    return 2 * _decode(dims, context)
