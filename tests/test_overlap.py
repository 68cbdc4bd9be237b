"""Bucketed async gradient allreduce (ISSUE 7 tentpole): the
`parallel/overlap.py` bucket planner and the
`make_train_step(..., overlap=BucketPlan)` path.

Coverage contract (the ISSUE's bucket-planning satellite):
- partition DETERMINISM across ranks (the plan is pure structure — the
  same under simulated process_index 0 vs 1, so every rank issues the
  identical per-bucket collective sequence);
- EXACT COVER of the grads pytree (no leaf dropped or duplicated, sizes
  add up, reverse layer order);
- NUMERICAL EQUIVALENCE of bucketed vs monolithic reduction (both
  reduce modes), and of the full overlap train step vs the unbucketed
  GSPMD step at tight atol — including composed with ZeRO-1.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.analysis import collective_audit
from deeplearning4j_tpu.datasets.api import DataSet
from deeplearning4j_tpu.parallel.mesh import make_mesh
from deeplearning4j_tpu.parallel.overlap import (
    bucketed_reduce,
    plan_buckets,
    reduce_gradients,
)
from deeplearning4j_tpu.util.compat import shard_map
from tests.cluster_worker import build_net, full_data

N_DEV = 8


def _tree(seed=0):
    """A layered grads-shaped pytree with mixed dtypes and sizes."""
    rng = np.random.default_rng(seed)
    return {
        "layer_0": {"W": rng.standard_normal((6, 8)).astype(np.float32),
                    "b": rng.standard_normal(8).astype(np.float32)},
        "layer_1": {"W": rng.standard_normal((8, 16)).astype(np.float32),
                    "b": rng.standard_normal(16).astype(np.float32)},
        "layer_2": {"W": rng.standard_normal((16, 3)).astype(np.float32),
                    "b": rng.standard_normal(3).astype(np.float32)},
    }


LAYERS = ["layer_0", "layer_1", "layer_2"]


# ---------------------------------------------------------------- planning

def test_plan_exactly_covers_the_tree():
    tree = _tree()
    plan = plan_buckets(tree, bucket_bytes=128, layer_order=LAYERS)
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    all_paths = sorted(jax.tree_util.keystr(p) for p, _ in flat)
    # no leaf dropped or duplicated
    assert sorted(plan.leaf_paths()) == all_paths
    assert plan.n_leaves == len(flat)
    assert plan.n_elements == sum(l.size for _, l in flat)
    # per-bucket byte accounting at the f32 reduction dtype
    for b in plan.buckets:
        assert b.n_bytes == b.n_elements * 4


def test_plan_is_reverse_layer_ordered_and_size_targeted():
    tree = _tree()
    plan = plan_buckets(tree, bucket_bytes=128, layer_order=LAYERS)
    # the FIRST bucket holds the LAST layer's gradients (they finish
    # backward first, so they reduce first)
    assert all("layer_2" in p for p in plan.buckets[0].paths)
    last = [p for p in plan.buckets[-1].paths]
    assert all("layer_0" in p for p in last)
    # size target respected except single oversized leaves
    for b in plan.buckets:
        assert b.n_bytes <= 128 or len(b.paths) == 1
    # one giant bucket when the target exceeds the model
    assert len(plan_buckets(tree, bucket_bytes=1 << 30,
                            layer_order=LAYERS).buckets) == 1


def test_plan_is_deterministic_across_simulated_ranks():
    tree = _tree()
    plans = []
    for pid in (0, 1):
        with collective_audit.simulated_process_index(pid):
            plans.append(plan_buckets(tree, bucket_bytes=96,
                                      layer_order=LAYERS))
    assert plans[0] == plans[1]
    assert plans[0] == plan_buckets(tree, bucket_bytes=96,
                                    layer_order=LAYERS)


def test_plan_rejects_bad_inputs():
    with pytest.raises(ValueError, match="mode"):
        plan_buckets(_tree(), mode="allreduce")
    with pytest.raises(ValueError, match="positive"):
        plan_buckets(_tree(), bucket_bytes=0)
    with pytest.raises(ValueError, match="empty"):
        plan_buckets({})


def test_plan_summary_is_telemetry_ready():
    plan = plan_buckets(_tree(), bucket_bytes=128, layer_order=LAYERS)
    s = plan.summary()
    assert s["n_buckets"] == len(plan.buckets) and s["mode"] == "psum"
    assert [b["index"] for b in s["buckets"]] == list(range(s["n_buckets"]))
    assert sum(b["bytes"] for b in s["buckets"]) == plan.n_elements * 4


# --------------------------------------------------------------- reduction

def _reduce_on_mesh(tree, plan, mesh):
    """Run bucketed_reduce under shard_map with each replica holding
    `tree * (rank+1)` — the expected mean is tree * mean(1..n)."""
    def body(t):
        r = jax.lax.axis_index("data").astype(jnp.float32) + 1.0
        scaled = jax.tree.map(lambda l: l * r.astype(l.dtype), t)
        return bucketed_reduce(scaled, plan, axis_name="data")

    fn = shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                   check_vma=False, axis_names={"data"})
    return jax.jit(fn)(tree)


@pytest.mark.parametrize("mode", ["psum", "psum_scatter"])
@pytest.mark.parametrize("bucket_bytes", [64, 96, 1 << 30])
def test_bucketed_reduce_matches_monolithic_mean(mode, bucket_bytes):
    mesh = make_mesh({"data": N_DEV})
    tree = _tree()
    plan = plan_buckets(tree, bucket_bytes=bucket_bytes,
                        layer_order=LAYERS, mode=mode)
    got = _reduce_on_mesh(tree, plan, mesh)
    scale = np.mean(np.arange(1, N_DEV + 1))
    want = jax.tree.map(lambda l: l * scale, tree)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), w, atol=1e-5, rtol=1e-5)


def test_bucketed_reduce_rejects_mismatched_plan():
    mesh = make_mesh({"data": N_DEV})
    tree = _tree()
    plan = plan_buckets({"other": {"W": np.zeros((4, 4), np.float32)}})
    with pytest.raises(ValueError, match="does not cover"):
        _reduce_on_mesh(tree, plan, mesh)


def test_reduce_gradients_is_a_whole_tree_pmean():
    """The unbucketed blessed helper (sequence_parallel's routing) is a
    tree-level pmean: under jax 0.9.0 that binds one psum eqn per leaf,
    in tree order (XLA's all-reduce combiner merges them) — the frozen
    SP collective signature records exactly this sequence."""
    mesh = make_mesh({"data": N_DEV})
    tree = _tree()

    def body(t):
        return reduce_gradients(t, "data")

    fn = shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                   check_vma=False, axis_names={"data"})
    closed = jax.make_jaxpr(fn)(tree)
    sig = collective_audit.jaxpr_collectives(closed)
    assert (len([s for s in sig if s.startswith("psum@data")])
            == len(jax.tree.leaves(tree)))
    got = jax.jit(fn)(tree)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(tree)):
        np.testing.assert_allclose(np.asarray(g), w, atol=1e-6)


def test_bucket_collective_sequence_is_one_psum_per_bucket():
    """The jaxpr-visible contract behind the stage-3 entry: the overlap
    reduction issues exactly len(buckets) gradient psums, in plan
    order, each over the bucket's flat f32 vector."""
    mesh = make_mesh({"data": N_DEV})
    tree = _tree()
    plan = plan_buckets(tree, bucket_bytes=128, layer_order=LAYERS)

    def body(t):
        return bucketed_reduce(t, plan, axis_name="data")

    fn = shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                   check_vma=False, axis_names={"data"})
    sig = collective_audit.jaxpr_collectives(jax.make_jaxpr(fn)(tree))
    psums = [s for s in sig if s.startswith("psum@data")]
    assert len(psums) == len(plan.buckets)
    sizes = [int(s.split("[")[1].rstrip("]")) for s in psums]
    assert sizes == [b.n_elements for b in plan.buckets]


# ------------------------------------------------------- train-step parity

def _one_step(net, overlap=None, zero1=False):
    mesh = make_mesh({"data": N_DEV})
    net.set_mesh(mesh, zero1=zero1, overlap=overlap)
    x, y = full_data()
    net.fit(DataSet(x, y))
    return np.asarray(net.params_flat())


@pytest.mark.parametrize("bucket_bytes", [128, 1 << 30])
def test_overlap_step_matches_monolithic_step(bucket_bytes):
    """Bucketed-vs-unbucketed numerical equivalence through the REAL
    set_mesh/fit path: same seed, same batch, one step each — params
    agree at tight atol (f32 reduction-order freedom only)."""
    ref = _one_step(build_net().init())
    got = _one_step(build_net().init(), overlap=bucket_bytes)
    np.testing.assert_allclose(got, ref, atol=1e-5)


def test_overlap_step_is_deterministic():
    a = _one_step(build_net().init(), overlap=128)
    b = _one_step(build_net().init(), overlap=128)
    assert np.array_equal(a, b)


def test_overlap_composes_with_zero1():
    """overlap + zero1: the bucketed reduction runs in shard_map, the
    sharded weight update stays with GSPMD — same params as the
    monolithic zero1 step."""
    ref = _one_step(build_net().init(), zero1=True)
    got = _one_step(build_net().init(), overlap=128, zero1=True)
    np.testing.assert_allclose(got, ref, atol=1e-5)


def test_overlap_rides_the_scanned_fit_path():
    """fit_scanned reuses _get_train_step, so the overlap step must
    scan: one fused epoch over two batches."""
    net = build_net().init()
    net.set_mesh(make_mesh({"data": N_DEV}), overlap=128)
    x, y = full_data()
    net.fit_scanned([DataSet(x[:16], y[:16]), DataSet(x[16:], y[16:])],
                    epochs=2)
    assert net.iteration_count == 4
    assert np.isfinite(net.score_value)


def test_overlap_rejects_non_data_roles_and_tbptt():
    net = build_net().init()
    mesh = make_mesh({"data": 4, "model": 2})
    with pytest.raises(ValueError, match="'data' role only"):
        net.set_mesh(mesh, axes={"data": "data", "model": "model"},
                     overlap=True)
    with pytest.raises(ValueError, match="requires a mesh"):
        net.set_mesh(None, overlap=True)

    from deeplearning4j_tpu.nn.conf import (
        NeuralNetConfiguration,
        RnnOutputLayer,
    )
    from deeplearning4j_tpu.nn.conf.layers import LSTM
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    conf = (NeuralNetConfiguration.builder().seed(1).list()
            .layer(LSTM(n_in=3, n_out=4))
            .layer(RnnOutputLayer(n_in=4, n_out=2, activation="softmax",
                                  loss_function="mcxent"))
            .backprop_type("truncated_bptt")
            .t_bptt_forward_length(2).t_bptt_backward_length(2)
            .build())
    tb = MultiLayerNetwork(conf).init()
    with pytest.raises(ValueError, match="TRUNCATED_BPTT"):
        tb.set_mesh(make_mesh({"data": N_DEV}), overlap=True)


def test_trainer_overlap_arm_matches_reference():
    """The bench's overlap arm end-to-end: DataParallelTrainer(...,
    overlap=...) over sharded batches equals the single-device
    full-batch step (gradient linearity, same seed)."""
    from deeplearning4j_tpu.parallel.data_parallel import DataParallelTrainer

    x, y = full_data()
    net = build_net().init()
    DataParallelTrainer(net, make_mesh({"data": N_DEV}),
                        overlap=128).fit(DataSet(x, y))
    ref = build_net().init()
    ref.fit(DataSet(x, y))
    np.testing.assert_allclose(np.asarray(net.params_flat()),
                               np.asarray(ref.params_flat()), atol=1e-5)
