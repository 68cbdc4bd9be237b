"""Both configurations' real-width programs compiled for a described v5e
(no chip attached): the train step at the cell's batch, the server's
largest prefill bucket and its decode step, and the reference's step. Each
has to fit the chip's 16 GB by `memory_analysis()`, so that a later PR
finds an over-full cell before it spends chip time. Slow (minutes): not
in the repo's tier-1 run.

The topology is described inside a fixture, never at import, and every
compile runs in this process (on-chip-measurement guide, section 2).
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest
from harness import serve_driver, weights
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


def total(mem):
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


def test_train_step_fits(one_chip):
    config = load("configs/cerebras-gpt-590m.json")
    mix = load("traffic/train_seq2048_b4.json")
    from deeplearning4j_tpu.models.transformer import transformer_lm

    net = transformer_lm(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"], max_length=config["n_positions"],
        dtype=config["compute_dtype"], remat=config["training"]["remat"])
    params = serve_driver.param_shapes(net)
    from deeplearning4j_tpu.nn.updater import build_optimizer

    net.tx = build_optimizer(net.conf.conf, {
        n: v.layer for n, v in net.layer_vertices.items()}, params=params)
    opt = jax.eval_shape(net.tx.init, params)
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
    dims, hp = weights.dims_of(config), config["training"]
    W = jax.eval_shape(lambda k: weights.reference_params(k, dims),
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
    config = load("configs/cerebras-gpt-1.3b.json")
    dep = config["deployment"]
    from deeplearning4j_tpu.models.transformer import transformer_lm

    net = transformer_lm(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], n_layers=config["n_layer"],
        d_ff=config["n_inner"], max_length=config["n_positions"],
        dtype=config["compute_dtype"])
    params = serve_driver.param_shapes(net)
    state = {n: {} for n in params}
    page = dep["page_size"]
    cap = -(-(max(dep["prefill_seq_lens"]) + dep["max_new_tokens"]) // page) * page
    cache = jax.eval_shape(lambda: net.init_kv_cache(
        dep["slots"], cap, dep["kv_dtype"], page))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    weights_and_cache = sum(x.size * x.dtype.itemsize
                            for x in jax.tree.leaves((params, cache)))
    decode = jax.jit(net.incremental_decode_fn(dep["kv_dtype"], page))
    mem = decode.lower(*on(one_chip, (params, state, cache,
                                      i32(dep["slots"]), i32(dep["slots"])))
                       ).compile().memory_analysis()
    # nothing is donated: a whole second cache is the step's output
    assert weights_and_cache + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < HBM, mem
    Tb = max(dep["prefill_seq_lens"])
    prefill = jax.jit(net.prefill_fn(dep["kv_dtype"], page))
    mem = prefill.lower(*on(one_chip, (
        params, state, cache, i32(1, Tb),
        jax.ShapeDtypeStruct((1, Tb), jnp.float32), i32(1), i32(1), i32(1)))
    ).compile().memory_analysis()
    assert weights_and_cache + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < HBM, mem
