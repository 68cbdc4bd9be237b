"""The control, kept at a size a test run can hold: the plain reference
computed in float8 (`mm_fp8`), put in the program's place, reads worse
against the reference than the program does, on every number that was
given a limit for it on the chip (PERF.md has those readings)."""
import jax.numpy as jnp
import numpy as np
import presets
import pytest
from harness import compare, serve_driver, spec, train_driver, weights
from reference import gpt2_block as ref

gpt2 = spec.family_of(presets.GPT2)

LOOSE = dict.fromkeys(presets.TRAIN_LIMITS, 1e9)


@pytest.mark.parametrize("seed", [100, 101, 103])
def test_training_control_reads_above_the_program(seed):
    config, mix = presets.TINY_TRAIN, presets.TRAIN_MIX
    dims, hp = gpt2.dims_of(config), config["training"]
    net = gpt2.training_net(config, seed, dims)
    feed = train_driver.StepFeed(seed, mix["batch"], mix["seq_len"], dims["V"])
    prog = train_driver.program_readings(gpt2, net, feed, seed, dims, hp)
    pk = train_driver.proj_key(seed)
    r = gpt2.reference_readings(seed, dims, hp, feed.kept, pk)
    low = gpt2.reference_readings(seed, dims, hp, feed.kept, pk, lowprec=True)
    half = gpt2.reference_readings(seed, dims, hp, feed.kept, pk,
                                   rows=mix["batch"] // 2)
    p = compare.train_checks(prog, r, LOOSE)
    c = compare.train_checks(low, r, LOOSE)
    h = compare.train_checks(half, r, LOOSE)
    assert compare.verdict(compare.train_checks(prog, r, presets.TRAIN_LIMITS))
    assert not compare.verdict(compare.train_checks(low, r, presets.TRAIN_LIMITS))
    assert not compare.verdict(compare.train_checks(half, r, presets.TRAIN_LIMITS))
    assert c["grad_proj"][0] > 3 * p["grad_proj"][0]
    assert h["grad_norm"][0] > 10 * p["grad_norm"][0]


@pytest.mark.parametrize("seed", [5, 6, 2**31 + 9])
def test_serving_control_is_not_correct(seed):
    """At every position of one sequence, through the run's own
    `serve_checks`: a server that returns the reference's own choice is
    correct; the float8 reference's choices are not, and neither are the
    reference's own choices with four rows of the context altered (stale
    rows of the cache; at the cells' 24 layers of 16 heads one row does)."""
    dims = gpt2.dims_of(presets.TINY_SERVE)
    rng = np.random.default_rng(seed)
    P, T = 32, 96
    tokens = rng.integers(0, dims["V"], T)
    W = gpt2.reference_params(weights.seed_key(seed), dims)
    at, valid = jnp.arange(P - 1, T - 1), jnp.ones(T - P, bool)

    def choices(row, mm=ref.mm_highest):
        return jnp.argmax(ref.served_logits(
            W, jnp.asarray(row, jnp.int32), at, dims, mm), axis=-1)

    for i in range(P, T):       # the reference's own greedy continuation
        tokens[i] = int(choices(tokens)[i - P])
    assert len(set(tokens[P:].tolist())) > (T - P) // 2     # it says things
    stale = tokens.copy()
    stale[P // 2:P // 2 + 4] = (stale[P // 2:P // 2 + 4] + 1) % dims["V"]

    def checks(served):
        gap = ref.served_gap(W, jnp.asarray(tokens, jnp.int32), at, served,
                             valid, dims)
        sample = [{"tokens": [0] * (T - P), "max_new": T - P, "error": None}]
        return serve_driver.serve_checks(sample, [np.asarray(gap)], 0,
                                         presets.SERVE_LIMITS)

    assert compare.verdict(checks(choices(tokens)))
    assert not compare.verdict(checks(choices(tokens, ref.mm_fp8)))
    assert not compare.verdict(checks(choices(stale)))
