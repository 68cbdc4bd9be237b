"""What any family's seeded weights share (`benchmarks/families/`): a PRNG
key from the run's seed, the program's parameter tree as shapes, the
seeded weights laid into exactly that tree, and Adam's first moment read
out of the program's optimizer state. Which leaves a model has, and what
numbers they hold, is the family's."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from any whole number the driver may pass (more than
    32 signed bits hold)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def param_shapes(net):
    """The parameter tree `net.init()` would build, as shapes: serving
    needs no optimizer, and init() would allocate Adam's two moments
    (11 GB at 1.4 B parameters) beside the weights."""
    def build(key):
        out = {}
        for name in sorted(net.layer_vertices):
            out[name] = net.impls[name].init(
                net.layer_vertices[name].layer, key, net.param_dtype)
        return out

    both = jax.eval_shape(build, jax.random.PRNGKey(0))
    for name, (_p, s) in both.items():
        if s:
            raise ValueError(f"layer {name} keeps state; the benchmark's "
                             f"weights know only stateless layers")
    return {n: p for n, (p, _s) in both.items()}


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
