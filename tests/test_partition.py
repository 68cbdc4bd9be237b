"""ops/partition.py — Pallas kernels per device under a device-spanning
jit. On the virtual CPU mesh the kernels are interpreted (plain XLA), so
these check the wrapper's semantics: same values and gradients as the
plain call, rows split over the axes that divide them. That the chip's
compiler needs the wrapper at all is tests/test_chip_compile.py's case."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import fused_softmax_xent as fsx
from deeplearning4j_tpu.ops import partition
from deeplearning4j_tpu.ops.flash_attention import flash_attention_qkv
from deeplearning4j_tpu.parallel.mesh import make_mesh


@pytest.fixture
def mesh():
    return make_mesh({"data": 2, "model": 2}, devices=jax.devices()[:4])


def test_without_a_mesh_it_is_the_plain_call():
    calls = []

    def fn(a, b):
        calls.append((a.shape, b.shape))
        return a + b

    out = partition.rows_per_device(fn, (jnp.ones((4, 2)),),
                                    (jnp.ones(2),))
    assert calls == [((4, 2), (2,))]
    np.testing.assert_allclose(np.asarray(out), 2.0)


def test_one_device_mesh_is_inactive():
    one = make_mesh({"data": 1}, devices=jax.devices()[:1])
    with partition.kernel_mesh(one):
        assert getattr(partition._active, "mesh", None) is None
    with partition.kernel_mesh(None):
        assert getattr(partition._active, "mesh", None) is None


@pytest.mark.parametrize("rows,axes", [
    (32, ("data", "model")),   # divisible by 4: every axis shares rows
    (2, ("data",)),            # only the first axis divides
    (3, ()),                   # nothing divides: computed redundantly
])
def test_row_axes_are_the_mesh_axes_that_divide(mesh, rows, axes):
    assert partition._row_axes(mesh, rows) == axes


def test_rows_split_and_context_restored(mesh):
    seen = []

    def fn(a, w):
        seen.append(a.shape)
        return a * w

    a = jnp.arange(16.0).reshape(8, 2)
    with partition.kernel_mesh(mesh):
        out = jax.jit(lambda a, w: partition.rows_per_device(
            fn, (a,), (w,)))(a, jnp.float32(3.0))
    assert seen == [(2, 2)]                      # 8 rows over 4 devices
    np.testing.assert_allclose(np.asarray(out), np.asarray(a) * 3.0)
    assert getattr(partition._active, "mesh", None) is None


def test_disagreeing_row_counts_are_refused(mesh):
    with partition.kernel_mesh(mesh):
        with pytest.raises(ValueError, match="disagree"):
            partition.rows_per_device(
                lambda a, b: a, (jnp.ones((4, 2)), jnp.ones((8, 2))))


def test_flash_qkv_under_mesh_matches_plain(mesh):
    rng = np.random.default_rng(0)
    qkv = jnp.asarray(rng.standard_normal((4, 512, 3 * 128)) * 0.3,
                      jnp.float32)

    def loss(x):
        return (flash_attention_qkv(x, 1, causal=True) ** 2).sum()

    def loss_mesh(x):
        with partition.kernel_mesh(mesh):
            return loss(x)

    want, gwant = jax.value_and_grad(loss)(qkv)
    got, ggot = jax.jit(jax.value_and_grad(loss_mesh))(qkv)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(ggot), np.asarray(gwant),
                               rtol=1e-4, atol=1e-5)


def test_xent_head_under_mesh_matches_plain_with_replicated_weight_grads(
        mesh):
    rng = np.random.default_rng(1)
    n, d, v = 512, 128, 2048
    x = jnp.asarray(rng.standard_normal((n, d)) * 0.5, jnp.float32)
    w = jnp.asarray(rng.standard_normal((d, v)) * 0.1, jnp.float32)
    b = jnp.zeros((v,), jnp.float32)
    lab = jnp.asarray(rng.integers(0, v, n), jnp.int32)

    def loss(x, w, b):
        return fsx.softmax_xent_head(x, w, b, lab).mean()

    def loss_mesh(x, w, b):
        with partition.kernel_mesh(mesh):
            return loss(x, w, b)

    want, gwant = jax.value_and_grad(loss, argnums=(0, 1, 2))(x, w, b)
    got, ggot = jax.jit(jax.value_and_grad(
        loss_mesh, argnums=(0, 1, 2)))(x, w, b)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for g, gw in zip(ggot, gwant):
        # dW/db: each device contributes its tokens' share, summed
        np.testing.assert_allclose(np.asarray(g), np.asarray(gw),
                                   rtol=1e-4, atol=1e-6)


def test_gspmd_train_step_wraps_kernels_only_under_a_mesh(mesh):
    """make_train_step names its mesh while tracing: the jaxpr of the
    mesh step holds shard_maps around the kernels, the one-device step
    holds none."""
    from deeplearning4j_tpu.datasets.api import DataSet
    from deeplearning4j_tpu.models.transformer import transformer_lm

    def jaxpr_for(use_mesh):
        net = transformer_lm(vocab_size=2048, d_model=128, n_heads=1,
                             n_layers=1, d_ff=256, max_length=512)
        net.init()
        if use_mesh:
            net.set_mesh(mesh, axes={"data": "data", "model": "model"})
        toks = np.zeros((4, 512), np.int32)
        batch = net._batch_dict(net._to_mds(DataSet(toks, toks)))
        return str(jax.make_jaxpr(net._get_train_step())(
            net.params, net.opt_state, net.state, jax.random.PRNGKey(0),
            batch))

    prev = fsx.FORCE_FUSED
    fsx.FORCE_FUSED = True
    try:
        assert "shard_map" not in jaxpr_for(False)
        assert "shard_map" in jaxpr_for(True)
    finally:
        fsx.FORCE_FUSED = prev
