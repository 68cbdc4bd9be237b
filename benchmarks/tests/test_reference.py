"""The plain reference against itself: the backpropagation written out in
`train_step` is `jax.grad` of `loss`; both weight layouts hold the same
numbers; the float8 control is a different computation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from harness import spec, weights
from presets import GPT2
from reference import gpt2_block as ref

gpt2 = spec.family_of(GPT2)

DIMS = {"d": 64, "H": 2, "L": 3, "F": 128, "V": 97, "eps": 1e-5}
HP = {"learning_rate": 3e-4, "adam_b1": 0.9, "adam_b2": 0.999,
      "adam_eps": 1e-8}


@pytest.fixture(scope="module")
def setup():
    key = weights.seed_key(2**31 + 7)
    W = gpt2.reference_params(key, DIMS)
    rng = np.random.default_rng(0)
    tok = jnp.asarray(rng.integers(0, 97, (3, 16)), jnp.int32)
    lab = jnp.asarray(rng.integers(0, 97, (3, 16)), jnp.int32)
    return key, W, tok, lab


def test_written_out_gradient_is_jax_grad(setup):
    _key, W, tok, lab = setup
    l, g = jax.value_and_grad(ref.loss)(W, tok, lab, DIMS)
    z = jax.tree.map(jnp.zeros_like, W)
    W2, _m, _v, l2, norms = ref.train_step(W, z, z, tok, lab, 1.0, DIMS, HP)
    assert not any(k.startswith("proj.") for k in norms)
    assert float(l) == pytest.approx(float(l2), rel=1e-6)
    want = ref.sq_norms(g)
    assert set(want) == set(norms)
    for k in want:
        if k == "blocks.bk":        # nought to rounding under softmax
            assert float(jnp.max(want[k])) < 1e-12
            continue
        np.testing.assert_allclose(norms[k], want[k], rtol=1e-4)
    Wa, _, _ = ref.adam_update(W, z, z, g, 1.0, HP)
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), Wa, W2)
    moved["blocks"].pop("bqkv")     # its k third moves by round-off alone
    assert max(jax.tree.leaves(moved)) < 1e-4


def test_layouts_hold_the_same_numbers(setup):
    key, W, _tok, _lab = setup
    back = gpt2.program_to_reference(gpt2.program_params(key, DIMS), DIMS)
    assert all(bool(jnp.array_equal(a, b)) for a, b in
               zip(jax.tree.leaves(back), jax.tree.leaves(W)))
    assert sum(x.size for x in jax.tree.leaves(W)) == gpt2.count_params(DIMS)
    p = gpt2.program_sq_norms(gpt2.program_params(key, DIMS), DIMS)
    r = ref.sq_norms(W)
    for k in r:
        np.testing.assert_allclose(p[k], r[k], rtol=1e-5)


def test_big_seed_is_a_different_seed():
    a = gpt2.reference_params(weights.seed_key(5), DIMS)["Wout"]
    b = gpt2.reference_params(weights.seed_key(5 + 2**31), DIMS)["Wout"]
    assert not bool(jnp.array_equal(a, b))


def test_first_moment_from_flat_state():
    import optax
    from deeplearning4j_tpu.nn.updater import _flatten_leaves

    params = gpt2.program_params(weights.seed_key(1), DIMS)
    flat = _flatten_leaves(params)      # the program's own flat layout
    state = optax.adam(1e-3).init(flat)
    state = (state[0]._replace(mu=flat * 2.0),) + tuple(state[1:])
    mu = gpt2.first_moment_tree(state, params)
    for a, b in zip(jax.tree.leaves(mu), jax.tree.leaves(params)):
        np.testing.assert_allclose(a, 2.0 * b)


def test_float8_control_is_another_computation(setup):
    _key, W, tok, _lab = setup
    at = jnp.arange(8)
    hi = ref.served_logits(W, tok[0], at, DIMS)
    lo = ref.served_logits(W, tok[0], at, DIMS, ref.mm_fp8)
    err = float(jnp.max(jnp.abs(hi - lo)))
    assert 1e-3 < err < 1.0
