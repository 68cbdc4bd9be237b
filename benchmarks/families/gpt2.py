"""The GPT-2 block family (`"model_type": "gpt2"`): what the harness knows
about this model, behind the interface of `families/__init__.py`.

Sizes from the configuration's GPT-2 keys; the program's net through
`models.transformer.transformer_lm`; the seeded weights; the plain
reference (`benchmarks/reference/gpt2_block.py`, imported here alone) for
serving and for training; the FLOP and byte counts.

The benchmark, not the program, makes the weights: the program is handed
them in its own parameter layout (`program_params`), the plain reference
makes the same numbers again in its stacked layout (`reference_params`)
once the program's copy is freed. Both call `block_weights` with
`fold_in(key, layer)`, so layer i holds the same numbers on both sides,
and neither side takes an array the other made.

The key is an argument of the jitted makers, never a constant: a new
seed must not be a new program for the compile cache.

The training count is a copy of the program's
`models/transformer.transformer_flops_per_token_executed` (sound
arithmetic; the original is listed in PERF.md for a later PR to
delete): forward + backward = 3 x forward, the attention term counted
at exactly T(T+1)/2 causal pairs, recomputation not counted.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.flops import causal_attention_factor
# `first_moment_tree` is part of the interface: the program's optimizer
# keeps this family's first moment as the shared helper reads it
from harness.weights import (first_moment_tree, fit_program_tree,  # noqa: F401
                             param_shapes, seed_key)
from reference import gpt2_block as ref

PAD = 256               # reference sequence lengths are multiples of this
BLOCK_LEAVES = ("ln1_g", "ln1_b", "Wqkv", "bqkv", "Wo", "bo",
                "ln2_g", "ln2_b", "W1", "b1", "W2", "b2")
# A configuration's "seeded_weights" group may scale the Xavier matrices
# (all 1 where it gives none). Plain Xavier makes a model that says one
# token whatever it is asked: the token embedding is a hundredth of the
# positions' sinusoid, attention is near uniform. A server's answers can
# be told from wrong ones only where they depend on the prompt:
#   embed_gain  the token embedding (Xavier's std times this)
#   qk_gain     the query and the key thirds of Wqkv (scores grow by its
#               square: attention picks out rows of the cache)
#   resid_gain  Wo and W2, what a block adds to the residual stream (under
#               1, the stream keeps the token it started from, as GPT-2's
#               own initialisation has it)
#   head_gain   the output head (the logits' spread)
GAINS = ("embed_gain", "qk_gain", "resid_gain", "head_gain")


def dims_of(config: dict) -> dict:
    """The sizes the makers and the reference need, from a configuration
    file's GPT-2 keys."""
    d = int(config["n_embd"])
    gains = config.get("seeded_weights", {})
    return {"d": d, "H": int(config["n_head"]), "L": int(config["n_layer"]),
            "F": int(config["n_inner"]), "V": int(config["vocab_size"]),
            "eps": float(config["layer_norm_epsilon"]),
            **{k: float(gains.get(k, 1.0)) for k in GAINS}}


def _xavier(key, shape, fan_in, fan_out):
    std = (2.0 / (fan_in + fan_out)) ** 0.5
    return std * jax.random.normal(key, shape, jnp.float32)


def _small(key, n):
    return 0.02 * jax.random.normal(key, (n,), jnp.float32)


def _gain(dims: dict, name: str) -> float:
    return dims.get(name, 1.0)


def block_weights(key, dims: dict) -> dict:
    d, F = dims["d"], dims["F"]
    k = jax.random.split(key, 12)
    qk, resid = _gain(dims, "qk_gain"), _gain(dims, "resid_gain")
    return {
        "ln1_g": 1.0 + _small(k[0], d), "ln1_b": _small(k[1], d),
        "Wqkv": _xavier(k[2], (d, 3 * d), d, d) * jnp.repeat(
            jnp.asarray([qk, qk, 1.0]), d),
        "bqkv": _small(k[3], 3 * d),
        "Wo": resid * _xavier(k[4], (d, d), d, d), "bo": _small(k[5], d),
        "ln2_g": 1.0 + _small(k[6], d), "ln2_b": _small(k[7], d),
        "W1": _xavier(k[8], (d, F), d, F), "b1": _small(k[9], F),
        "W2": resid * _xavier(k[10], (F, d), F, d), "b2": _small(k[11], d),
    }


def global_weights(key, dims: dict) -> dict:
    d, V = dims["d"], dims["V"]
    k = jax.random.split(key, 5)
    return {"embed": _gain(dims, "embed_gain") * _xavier(k[0], (V, d), V, d),
            "lnf_g": 1.0 + _small(k[1], d), "lnf_b": _small(k[2], d),
            "Wout": _gain(dims, "head_gain") * _xavier(k[3], (d, V), d, V),
            "bout": _small(k[4], V)}


def _layer_key(key, i):
    return jax.random.fold_in(key, i + 1)


def reference_params(key, dims: dict) -> dict:
    """{global leaves..., "blocks": {leaf: [L, ...]}} in float32."""
    out = global_weights(jax.random.fold_in(key, 0), dims)
    keys = jax.vmap(lambda i: _layer_key(key, i))(jnp.arange(dims["L"]))
    out["blocks"] = jax.vmap(lambda k: block_weights(k, dims))(keys)
    return out


def _program_layer(b: dict, i: int) -> dict:
    p = f"blk{i}"
    return {f"{p}_ln1": {"gamma": b["ln1_g"], "beta": b["ln1_b"]},
            f"{p}_attn": {"Wqkv": b["Wqkv"], "bqkv": b["bqkv"],
                          "Wo": b["Wo"], "bo": b["bo"]},
            f"{p}_ln2": {"gamma": b["ln2_g"], "beta": b["ln2_b"]},
            f"{p}_ff1": {"W": b["W1"], "b": b["b1"]},
            f"{p}_ff2": {"W": b["W2"], "b": b["b2"]}}


def program_params(key, dims: dict) -> dict:
    """The same numbers in the layout `transformer_lm` names its
    parameters by: {layer name: {param name: array}}. Made stacked and
    sliced, which compiles in a quarter of the time of a maker unrolled
    over the layers (the stacked copy is a transient of set-up)."""
    g = reference_params(key, dims)
    out = {"embed": {"W": g["embed"]}, "posenc": {},
           "ln_f": {"gamma": g["lnf_g"], "beta": g["lnf_b"]},
           "out": {"W": g["Wout"], "b": g["bout"]}}
    for i in range(dims["L"]):
        out.update(_program_layer(
            {n: x[i] for n, x in g["blocks"].items()}, i))
    return out


def program_to_reference(params: dict, dims: dict) -> dict:
    """Restack a tree in the program's layout into the reference's (used
    on norms and on small test trees, not on whole models)."""
    blocks = {n: [] for n in BLOCK_LEAVES}
    for i in range(dims["L"]):
        p = f"blk{i}"
        rows = {"ln1_g": params[f"{p}_ln1"]["gamma"],
                "ln1_b": params[f"{p}_ln1"]["beta"],
                "Wqkv": params[f"{p}_attn"]["Wqkv"],
                "bqkv": params[f"{p}_attn"]["bqkv"],
                "Wo": params[f"{p}_attn"]["Wo"], "bo": params[f"{p}_attn"]["bo"],
                "ln2_g": params[f"{p}_ln2"]["gamma"],
                "ln2_b": params[f"{p}_ln2"]["beta"],
                "W1": params[f"{p}_ff1"]["W"], "b1": params[f"{p}_ff1"]["b"],
                "W2": params[f"{p}_ff2"]["W"], "b2": params[f"{p}_ff2"]["b"]}
        for n, v in rows.items():
            blocks[n].append(v)
    return {"embed": params["embed"]["W"], "lnf_g": params["ln_f"]["gamma"],
            "lnf_b": params["ln_f"]["beta"], "Wout": params["out"]["W"],
            "bout": params["out"]["b"],
            "blocks": {n: jnp.stack(v) for n, v in blocks.items()}}


def count_params(dims: dict) -> int:
    d, F, V, L = dims["d"], dims["F"], dims["V"], dims["L"]
    block = 2 * d + d * 3 * d + 3 * d + d * d + d + 2 * d \
        + d * F + F + F * d + d
    return L * block + V * d + 2 * d + d * V + V


_PROGRAM_LEAF = {"ln1_g": ("_ln1", "gamma"), "ln1_b": ("_ln1", "beta"),
                 "Wqkv": ("_attn", "Wqkv"), "bqkv": ("_attn", "bqkv"),
                 "Wo": ("_attn", "Wo"), "bo": ("_attn", "bo"),
                 "ln2_g": ("_ln2", "gamma"), "ln2_b": ("_ln2", "beta"),
                 "W1": ("_ff1", "W"), "b1": ("_ff1", "b"),
                 "W2": ("_ff2", "W"), "b2": ("_ff2", "b")}


def program_sq_norms(params: dict, dims: dict) -> dict:
    """Squared norms of a tree in the program's layout under the names
    the reference's `sq_norms` gives its stacked tree (Wqkv and bqkv as
    their q, k and v thirds), without restacking the tree."""
    def ss(x):
        return jnp.sum(jnp.square(x.astype(jnp.float32)))

    out = {"embed": ss(params["embed"]["W"]),
           "lnf_g": ss(params["ln_f"]["gamma"]),
           "lnf_b": ss(params["ln_f"]["beta"]),
           "Wout": ss(params["out"]["W"]), "bout": ss(params["out"]["b"])}
    per = {}
    for i in range(dims["L"]):
        for leaf, (suffix, pname) in _PROGRAM_LEAF.items():
            x = params[f"blk{i}{suffix}"][pname]
            if leaf in ("Wqkv", "bqkv"):
                for tag, part in zip("qkv", jnp.split(x, 3, axis=-1)):
                    per.setdefault(leaf[0] + tag, []).append(ss(part))
            else:
                per.setdefault(leaf, []).append(ss(x))
    out.update({"blocks." + k: jnp.stack(v) for k, v in per.items()})
    return out


def program_projections(grads: dict, dims: dict, key) -> dict:
    """Each leaf's projection on the reference's fixed +-1 vector
    (`reference.gpt2_block.project`), for a gradient tree in the program's
    layout, under the reference's names ("proj.<leaf>", block leaves [L])."""
    project = ref.project
    out = {"proj.embed": project(grads["embed"]["W"], key, "embed"),
           "proj.lnf_g": project(grads["ln_f"]["gamma"], key, "lnf_g"),
           "proj.lnf_b": project(grads["ln_f"]["beta"], key, "lnf_b"),
           "proj.Wout": project(grads["out"]["W"], key, "Wout"),
           "proj.bout": project(grads["out"]["b"], key, "bout")}
    for leaf, (suffix, pname) in _PROGRAM_LEAF.items():
        out["proj.blocks." + leaf] = jnp.stack([
            project(grads[f"blk{i}{suffix}"][pname], key, leaf, i)
            for i in range(dims["L"])])
    return out


def seeded_program_tree(key, dims: dict, like: dict) -> dict:
    """The seeded weights in exactly the tree the program built (`like`:
    names, shapes and dtypes)."""
    return fit_program_tree(program_params(key, dims), like)


def serving_net(config: dict, seed: int, dims: dict):
    """The program's net for `GenerationEngine`, holding the seeded weights
    and no optimizer state."""
    from deeplearning4j_tpu.models.transformer import transformer_lm

    net = transformer_lm(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"], max_length=config["n_positions"],
        seed=int(seed) & 0x7FFFFFFF, dtype=config["compute_dtype"])
    like = param_shapes(net)
    give_weights(net, seed, dims, like)
    net.state = {n: {} for n in like}
    return net


def training_net(config: dict, seed: int, dims: dict):
    """The program's net as `fit` wants it: built and initialised by the
    program, then given the seeded weights in place of its own."""
    from deeplearning4j_tpu.models.transformer import transformer_lm

    tr = config["training"]
    if tr["updater"] != "adam" or abs(config["layer_norm_epsilon"] - 1e-5) > 0:
        raise ValueError("the program's transformer_lm is Adam with "
                         "LayerNorm eps 1e-5; the configuration asks otherwise")
    net = transformer_lm(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"], max_length=config["n_positions"],
        dropout=tr.get("dropout", 0.0), seed=int(seed) & 0x7FFFFFFF,
        learning_rate=tr["learning_rate"], dtype=config["compute_dtype"],
        remat=bool(tr.get("remat", False)))
    g = net.conf.conf
    g.adam_mean_decay, g.adam_var_decay = tr["adam_b1"], tr["adam_b2"]
    g.epsilon = tr["adam_eps"]
    net.init()
    give_weights(net, seed, dims)
    return net


def give_weights(net, seed: int, dims: dict, like=None) -> None:
    """Replace the net's parameters by the benchmark's seeded ones, in the
    tree `like` (shapes and dtypes; the net's own parameters by default)."""
    like = net.params if like is None else like
    net.params = jax.jit(lambda k: seeded_program_tree(k, dims, like))(
        seed_key(seed))


def served_gaps(sample, prompts, seed, dims, lowprec=False):
    """For each sampled request, the gap by which each served token's
    reference logit lies below the reference's best, as one array per
    request — or, for the control (`lowprec`), the gap of the token the
    float8 reference puts first at each of the same positions. One jitted
    program per padded length. The float32 weights of this family fit the
    chip beside nothing else, so they are made all at once."""
    W = jax.jit(lambda k: reference_params(k, dims))(seed_key(seed))
    served_fn = jax.jit(lambda W, t, at, s, v: ref.served_gap(W, t, at, s, v, dims))
    low_fn = jax.jit(lambda W, t, at, v: ref.lowprec_gap(W, t, at, v, dims))
    out = []
    for r in sample:
        prompt = prompts[r["id"].split(".")[0]]
        served = list(r["tokens"])
        L, n = len(prompt), len(served)
        T = -(-(L + n) // PAD) * PAD
        seq = np.zeros(T, np.int32)
        seq[:L] = prompt
        seq[L:L + n - 1] = served[:-1]
        at = np.zeros(PAD, np.int32)
        at[:n] = np.arange(L - 1, L - 1 + n)
        valid = np.arange(PAD) < n
        tok = np.zeros(PAD, np.int32)
        tok[:n] = served
        if lowprec:
            g = low_fn(W, jnp.asarray(seq), jnp.asarray(at), jnp.asarray(valid))
        else:
            g = served_fn(W, jnp.asarray(seq), jnp.asarray(at),
                          jnp.asarray(tok), jnp.asarray(valid))
        out.append(np.asarray(g, np.float64)[:n])
    del W
    return out


def projection_norms(grad_sq: dict) -> dict:
    """The gradient's squared norms under the projections' names: the fused
    Wqkv / bqkv are compared as their q, k and v thirds and projected
    whole."""
    whole = dict(grad_sq)
    for w in ("W", "b"):
        whole[f"blocks.{w}qkv"] = sum(
            np.asarray(whole.pop(f"blocks.{w}{t}"), np.float64) for t in "qkv")
    return whole


def reference_readings(seed, dims, hp, batches, proj_key, lowprec=False,
                       rows=None):
    """The plain reference's three steps on the same batches from the
    same seeded weights: {"losses", "grad_sq", "grad_proj", "proj_sq",
    "change_sq"} as numpy. `lowprec` computes every product in float8 (the
    control), `rows` keeps the first rows of every batch (the half-batch
    fault)."""
    key = seed_key(seed)
    make = jax.jit(lambda k: reference_params(k, dims))
    dev = [(jnp.asarray(t), jnp.asarray(l)) for t, l in batches]
    losses, g1, ch = ref.train_steps(
        lambda: make(key), dev, dims, hp, proj_key,
        mm=ref.mm_fp8 if lowprec else ref.mm_highest, rows=rows)
    g1 = jax.tree.map(np.asarray, g1)
    grad_sq = {k: v for k, v in g1.items() if not k.startswith("proj.")}
    return {"losses": [float(l) for l in losses], "grad_sq": grad_sq,
            "grad_proj": {k: v for k, v in g1.items() if k.startswith("proj.")},
            "proj_sq": projection_norms(grad_sq),
            "change_sq": jax.tree.map(np.asarray, ch)}


def forward_flops_per_token(dims: dict, context: float) -> float:
    """Forward FLOPs of one token that attends to `context` keys: the
    four [d, d] projections, the two feed-forward products, qk^T and
    attention x v over the context, and the output head."""
    d, F, V, L = dims["d"], dims["F"], dims["V"], dims["L"]
    per_layer = 4 * 2 * d * d + 2 * 2 * d * F + 2 * 2 * context * d
    return L * per_layer + 2 * d * V


def train_flops_per_token(dims: dict, seq_len: int) -> int:
    """Forward + backward FLOPs per trained token at sequence length
    seq_len under a causal mask (mean context (T+1)/2)."""
    return int(3 * forward_flops_per_token(
        dims, causal_attention_factor(seq_len) * seq_len))


def prefill_flops(dims: dict, prompt_len: int) -> float:
    """Forward FLOPs of a whole prompt; the head runs on its last row
    only (a server needs no other logits)."""
    d, V = dims["d"], dims["V"]
    body = forward_flops_per_token(dims, (prompt_len + 1) / 2.0) - 2 * d * V
    return prompt_len * body + 2 * d * V


def decode_flops(dims: dict, context: float) -> float:
    """Forward FLOPs of one generated token against `context` keys."""
    return forward_flops_per_token(dims, context)


def matmul_param_count(dims: dict) -> int:
    """Parameters a decode step has to read: every block's matrices and
    vectors and the head. The embedding table is gathered by row, not
    read."""
    d, F, V, L = dims["d"], dims["F"], dims["V"], dims["L"]
    block = 2 * d + d * 3 * d + 3 * d + d * d + d + 2 * d + d * F + F \
        + F * d + d
    return L * block + 2 * d + d * V + V


def kv_bytes_per_token(dims: dict, bytes_per_value: int = 2) -> int:
    """Bytes of keys and values one cached token holds over all layers."""
    return dims["L"] * 2 * dims["d"] * bytes_per_value


def decode_step_min_bytes(dims: dict, live_tokens: float,
                          bytes_per_value: int = 2) -> float:
    """The least a decode step moves: the weights once at the stated
    compute precision and every live cached row once."""
    return (matmul_param_count(dims) * bytes_per_value
            + live_tokens * kv_bytes_per_token(dims, bytes_per_value))
