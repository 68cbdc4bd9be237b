"""Seeded weights of the GPT-2 block family, made on the device.

The benchmark, not the program, makes the weights: the program is handed
them in its own parameter layout (`program_params`), the plain reference
makes the same numbers again in its stacked layout (`reference_params`)
once the program's copy is freed. Both call `block_weights` with
`fold_in(key, layer)`, so layer i holds the same numbers on both sides,
and neither side takes an array the other made.

The key is an argument of the jitted makers, never a constant: a new
seed must not be a new program for the compile cache.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

BLOCK_LEAVES = ("ln1_g", "ln1_b", "Wqkv", "bqkv", "Wo", "bo",
                "ln2_g", "ln2_b", "W1", "b1", "W2", "b2")
GLOBAL_LEAVES = ("embed", "lnf_g", "lnf_b", "Wout", "bout")
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


def seed_key(seed: int):
    """A PRNG key from any whole number the driver may pass (more than
    32 signed bits hold)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


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


def fit_program_tree(made: dict, like: dict) -> dict:
    """`made` laid into exactly the tree the program built (`like`): the
    same layer and parameter names, shapes and dtypes, or an error that
    names the first difference."""
    out = {}
    for layer, leaves in like.items():
        if layer not in made:
            raise KeyError(f"the program has layer {layer!r}; the "
                           f"benchmark's weights have none of that name")
        out[layer] = {}
        for name, leaf in leaves.items():
            if name not in made[layer]:
                raise KeyError(f"no weight for {layer}/{name}")
            w = made[layer][name]
            if tuple(w.shape) != tuple(leaf.shape):
                raise ValueError(f"{layer}/{name}: program {leaf.shape}, "
                                 f"benchmark {w.shape}")
            out[layer][name] = w.astype(leaf.dtype)
    extra = set(made) - set(like)
    if extra:
        raise KeyError(f"the program lacks layers {sorted(extra)}")
    return out


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


def first_moment_tree(opt_state, params):
    """Adam's first moment out of the program's optimizer state, in the
    parameters' tree. The program keeps it either as such a tree or as
    one flat float32 vector of all leaves in `jax.tree.leaves` order
    (row-major; a matrix whose last axis is under 128 is stored with
    that axis first)."""
    holders = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
    if not holders:
        raise ValueError("no Adam first moment in the optimizer state")
    mu = holders[0].mu
    leaves, treedef = jax.tree.flatten(params)
    if jax.tree.structure(mu) == treedef:
        return mu
    total = sum(l.size for l in leaves)
    if not (hasattr(mu, "ndim") and mu.ndim == 1 and mu.size == total):
        raise ValueError("optimizer state layout not understood: first "
                         f"moment {jax.tree.structure(mu)}")
    outs, off = [], 0
    for l in leaves:
        seg = mu[off:off + l.size]
        if l.ndim >= 2 and l.shape[-1] < 128:
            rot = (l.shape[-1],) + tuple(l.shape[:-1])
            outs.append(jnp.moveaxis(seg.reshape(rot), 0, -1))
        else:
            outs.append(seg.reshape(l.shape))
        off += l.size
    return jax.tree.unflatten(treedef, outs)


def program_projections(grads: dict, dims: dict, key) -> dict:
    """Each leaf's projection on the reference's fixed +-1 vector
    (`reference.gpt2_block.project`), for a gradient tree in the program's
    layout, under the reference's names ("proj.<leaf>", block leaves [L])."""
    from reference.gpt2_block import project

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
