"""Both configurations' real-width programs compiled for a described v5e
(no chip attached): the train step at the cell's batch, the server's
largest prefill bucket and its decode step, and the reference's step. Each
has to fit the chip's 16 GB by `memory_analysis()`, so that a later PR
finds an over-full cell before it spends chip time. Slow (minutes): not
in the repo's tier-1 run.

The nets are the configuration's family's own (`serving_net`,
`training_net`), built under `jax.eval_shape`, so that they hold shapes
and nothing is allocated here.

The topology is described inside a fixture, never at import, and every
compile runs in this process (on-chip-measurement guide, section 2).
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest
from harness import spec
from jax.sharding import SingleDeviceSharding
from presets import ROOT

HBM = 15.75 * 2**30         # what the v5e's compiler allows a program


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def load(name):
    with open(os.path.join(ROOT, "benchmarks", name)) as fh:
        return json.load(fh)


def on(chip, tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=chip), tree)


def built(config, builder):
    """(family, dims, the net `builder` names, its parameters and optimizer
    state as shapes): the family's builder traced, never run."""
    family = spec.family_of(config)
    dims, held = family.dims_of(config), {}

    def traced():
        net = held["net"] = getattr(family, builder)(config, 0, dims)
        return net.params, getattr(net, "opt_state", None)

    params, opt = jax.eval_shape(traced)
    net = held["net"]
    net.params = net.opt_state = None       # the tracers it was built on
    return family, dims, net, params, opt


def total(mem):
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


def test_train_step_fits(one_chip):
    config = load("configs/cerebras-gpt-590m.json")
    mix = load("traffic/train_seq2048_b4.json")
    _family, _dims, net, params, opt = built(config, "training_net")
    state = {n: {} for n in params}
    B, T = mix["batch"], mix["seq_len"]
    tok = jax.ShapeDtypeStruct((B, T), jnp.int32)
    batch = {"features": (tok,), "labels": (tok,)}
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    mem = net._get_train_step().lower(
        *on(one_chip, (params, opt, state, key, batch))).compile() \
        .memory_analysis()
    assert total(mem) < HBM, mem


def test_reference_step_fits(one_chip):
    from reference import gpt2_block as ref

    config = load("configs/cerebras-gpt-590m.json")
    mix = load("traffic/train_seq2048_b4.json")
    family = spec.family_of(config)
    dims, hp = family.dims_of(config), config["training"]
    W = jax.eval_shape(lambda k: family.reference_params(k, dims),
                       jax.random.PRNGKey(0))
    step, _change = ref._train_programs(
        tuple(sorted(dims.items())),
        tuple((k, float(hp[k])) for k in ("learning_rate", "adam_b1",
                                          "adam_b2", "adam_eps")),
        ref.mm_highest)
    tok = jax.ShapeDtypeStruct((mix["batch"], mix["seq_len"]), jnp.int32)
    t = jax.ShapeDtypeStruct((), jnp.float32)
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    mem = step.lower(*on(one_chip, (W, W, W, tok, tok, t, key))).compile() \
        .memory_analysis()
    assert total(mem) < HBM, mem


def test_server_programs_fit(one_chip):
    """The decode step and the largest prefill bucket as `_GenWorker` jits
    them (serving/engine.py): the greedy token and the cache come back, and
    the cache argument is donated, so a step holds one cache, not two."""
    config = load("configs/cerebras-gpt-1.3b.json")
    dep = config["deployment"]
    _family, _dims, net, params, _opt = built(config, "serving_net")
    state = {n: {} for n in params}
    page = dep["page_size"]
    cap = -(-(max(dep["prefill_seq_lens"]) + dep["max_new_tokens"]) // page) * page
    cache = jax.eval_shape(lambda: net.init_kv_cache(
        dep["slots"], cap, dep["kv_dtype"], page))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))

    def greedy(raw):
        def step(params, state, cache, *rest):
            probs, cache = raw(params, state, cache, *rest)
            return jnp.argmax(probs, axis=-1).astype(jnp.int32), cache
        return jax.jit(step, donate_argnums=2)

    decode = greedy(net.incremental_decode_fn(dep["kv_dtype"], page))
    mem = decode.lower(*on(one_chip, (params, state, cache,
                                      i32(dep["slots"]), i32(dep["slots"])))
                       ).compile().memory_analysis()
    assert mem.alias_size_in_bytes == cache_bytes, mem      # donation engaged
    assert total(mem) < HBM, mem
    Tb = max(dep["prefill_seq_lens"])
    prefill = greedy(net.prefill_fn(dep["kv_dtype"], page))
    mem = prefill.lower(*on(one_chip, (
        params, state, cache, i32(1, Tb),
        jax.ShapeDtypeStruct((1, Tb), jnp.float32), i32(1), i32(1), i32(1)))
    ).compile().memory_analysis()
    assert mem.alias_size_in_bytes == cache_bytes, mem
    assert total(mem) < HBM, mem
