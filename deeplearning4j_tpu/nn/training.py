"""Jitted training step assembly.

The reference's inner optimization block (SURVEY.md §3.1: computeGradientAndScore
→ updater → stepFunction.step) becomes ONE donated-buffer XLA computation:
loss+grad via jax.value_and_grad, gradient normalization, optax update,
parameter application. The host keeps only the minibatch loop.

Data parallelism: when a `mesh` is given, the step is jitted with batch
inputs sharded over the mesh's 'data' axis and params replicated — XLA
inserts the gradient allreduce over ICI automatically (the BASELINE.json
"param-avg → ICI allreduce" goal; replaces
SparkDl4jMultiLayer.runIteration's broadcast/accumulator round-trip,
reference spark/impl/multilayer/SparkDl4jMultiLayer.java:365-452).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import optax

from deeplearning4j_tpu.nn.updater import normalize_gradients
from deeplearning4j_tpu.ops.partition import kernel_mesh


def zero1_opt_shardings(opt_state, mesh, axis: str = "data"):
    """Cross-replica weight-update sharding (ZeRO stage 1; the XLA
    formulation is arXiv:2004.13336 "Automatic Cross-Replica Sharding of
    Weight Update in Data-Parallel Training"): optimizer-state leaves
    shard their leading dim over the data axis when divisible, so each
    replica stores and updates only 1/n of the Adam moments — GSPMD turns
    the gradient allreduce into reduce-scatter + sharded update +
    all-gather of the new params."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.shape[axis]
    repl = NamedSharding(mesh, P())

    def leaf(x):
        shape = getattr(x, "shape", ())
        if len(shape) >= 1 and shape[0] >= n and shape[0] % n == 0:
            return NamedSharding(mesh, P(axis, *([None] * (len(shape) - 1))))
        return repl

    return jax.tree.map(leaf, opt_state)


def _make_overlap_core(loss_fn, mesh, plan, data_axis):
    """The shard_map heart of the overlap train step: per-shard backward
    on the local batch slice, then the bucketed per-bucket collectives of
    `parallel/overlap.bucketed_reduce` in reverse layer order. Each
    bucket's psum depends only on its own grad leaves, so XLA's
    async-collective scheduler overlaps reduction with the remaining
    backward + the already-reduced buckets' update dataflow — the
    arXiv:1810.11112 design, with XLA as the progress engine."""
    from jax.sharding import PartitionSpec as P

    from deeplearning4j_tpu.parallel.overlap import (
        bucketed_reduce,
        pmean_float_leaves,
    )
    from deeplearning4j_tpu.util.compat import shard_map

    def local_grads(params, state, rng, batch):
        # decorrelate per-shard dropout streams (same idiom as the SP
        # step); dropout-free steps are unaffected — their parity with
        # the monolithic formulation is the test_overlap contract
        rng = jax.random.fold_in(rng, jax.lax.axis_index(data_axis))
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, state, rng, batch
        )
        new_state, _extras = aux if isinstance(aux, tuple) else (aux, {})
        grads = bucketed_reduce(grads, plan, axis_name=data_axis)
        loss = jax.lax.pmean(loss, data_axis)
        # per-shard mutable state (BatchNorm running stats over the local
        # batch slice) leaves the step as the cross-replica average
        new_state = pmean_float_leaves(new_state, data_axis)
        return loss, grads, new_state

    return shard_map(
        local_grads, mesh=mesh,
        in_specs=(P(), P(), P(), P(data_axis)),
        out_specs=(P(), P(), P()),
        check_vma=False, axis_names={data_axis})


def make_train_step(loss_fn, tx, layer_confs_by_name, mesh=None,
                    donate=True, zero1_opt_state=None, data_axis="data",
                    param_sharding=None, overlap=None):
    """loss_fn(params, state, rng, batch) -> (loss, (new_state, extras)).

    batch is a dict pytree {features, labels, features_mask?, labels_mask?,
    carries?}; extras carries auxiliary outputs (e.g. RNN carries for TBPTT).
    Returns step(params, opt_state, state, rng, batch) -> (params, opt_state,
    state, loss, extras).

    zero1_opt_state: pass the CURRENT opt_state (with `mesh`) to shard the
    optimizer state over the data axis (see zero1_opt_shardings).

    data_axis: mesh axis name the batch shards over (None: replicated —
    e.g. a pure tensor-parallel mesh). param_sharding: a pytree of
    NamedShardings for the params (TP/EP placement from
    parallel/tensor_parallel.py) — optimizer-state moments then inherit
    their committed placement instead of being forced replicated.

    overlap: a `parallel/overlap.BucketPlan` — gradients are computed
    per-shard under shard_map and reduced bucket-by-bucket (reverse
    layer order) instead of through GSPMD's single end-of-backward
    allreduce, letting XLA overlap the collectives with the remaining
    backward/update compute. Pure-DP only (the `set_mesh(overlap=...)`
    entry validates roles); composes with zero1_opt_state — the
    optimizer update stays in the enclosing jit, so the reduce-scatter
    weight-update placement is unchanged. The overlap step does not
    thread TBPTT carries (extras is always empty).
    """
    def update(grads, params, opt_state):
        # the forward and backward lie under `loss` (each layer in its
        # own region inside it), the update under `optimizer`
        with jax.named_scope("optimizer"):
            grads = normalize_gradients(grads, layer_confs_by_name)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state

    if overlap is not None:
        if mesh is None:
            raise ValueError("overlap=BucketPlan requires a mesh")
        if param_sharding is not None:
            raise ValueError(
                "overlap composes with the 'data' role only; TP/EP "
                "param placement keeps the GSPMD step")
        if not data_axis or data_axis not in mesh.axis_names:
            raise ValueError(
                f"overlap needs data_axis bound to a mesh axis (got "
                f"{data_axis!r}; mesh has {mesh.axis_names})")
        core = _make_overlap_core(loss_fn, mesh, overlap, data_axis)

        def step(params, opt_state, state, rng, batch):
            with jax.named_scope("loss"):
                loss, grads, new_state = core(params, state, rng, batch)
            params, opt_state = update(grads, params, opt_state)
            return params, opt_state, new_state, loss, {}
    else:
        def step(params, opt_state, state, rng, batch):
            # GSPMD cannot partition a Pallas kernel: the kernels run
            # per device over this mesh (ops/partition.py)
            with kernel_mesh(mesh), jax.named_scope("loss"):
                (loss, aux), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, state, rng, batch)
            new_state, extras = aux if isinstance(aux, tuple) else (aux, {})
            params, opt_state = update(grads, params, opt_state)
            return params, opt_state, new_state, loss, extras

    donate_argnums = (0, 1, 2) if donate else ()
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        repl = NamedSharding(mesh, P())
        data = (NamedSharding(mesh, P(data_axis))
                if data_axis and data_axis in mesh.axis_names else repl)
        p_sh = param_sharding if param_sharding is not None else repl
        if zero1_opt_state is not None:
            opt_in = opt_out = zero1_opt_shardings(
                zero1_opt_state, mesh, axis=data_axis)
        elif param_sharding is not None:
            # moments were committed alongside the params; None lets jit
            # respect (in) and propagate (out) that placement
            opt_in = opt_out = None
        else:
            opt_in = opt_out = repl
        # sharding pytree prefixes: one sharding per argument applies to all
        # its leaves — batch leaves are sharded on the data mesh axis
        return jax.jit(
            step,
            donate_argnums=donate_argnums,
            in_shardings=(p_sh, opt_in, repl, repl, data),
            out_shardings=(p_sh, opt_out, repl, repl, repl),
        )
    return jax.jit(step, donate_argnums=donate_argnums)


def make_scanned_fit(step):
    """Wrap a train step into a whole-epoch jitted scan.

    All minibatches live on device stacked on a leading axis; one dispatch
    runs the entire epoch (the fit()-path MFU mode: no per-batch host
    round-trips — on a remote-device link the per-dispatch latency
    otherwise dominates small steps). Returns
    run(params, opt_state, state, rng, batches, n_epochs) ->
    (params, opt_state, state, losses [n_epochs, n_batches]).
    """

    def run(params, opt_state, state, rng, batches, *, n_epochs):
        def epoch(carry, _):
            params, opt_state, state, rng = carry

            def one(carry, batch):
                params, opt_state, state, rng = carry
                rng, k = jax.random.split(rng)
                params, opt_state, state, loss, _ = step(
                    params, opt_state, state, k, batch)
                return (params, opt_state, state, rng), loss

            carry, losses = jax.lax.scan(
                one, (params, opt_state, state, rng), batches)
            return carry, losses

        (params, opt_state, state, _), losses = jax.lax.scan(
            epoch, (params, opt_state, state, rng), None, length=n_epochs)
        return params, opt_state, state, losses

    return jax.jit(partial(run), static_argnames=("n_epochs",))


def stack_batches(batch_dicts):
    """Stack per-batch dicts (uniform shapes) on a new leading axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *batch_dicts)


def fused_fit(net, batches, epochs):
    """Shared fit_scanned engine for both network containers.

    Guards against config modes the fused scan cannot honor (fit()'s
    dispatch would route them elsewhere), checks batch uniformity on full
    tree structure + every leaf shape, runs the scan, and updates
    iteration/epoch counters and listeners per epoch with that epoch's
    mean score.
    """
    from deeplearning4j_tpu.nn.conf.enums import (
        BackpropType,
        OptimizationAlgorithm,
    )

    conf = net.conf
    g = conf.conf
    if conf.pretrain:
        raise ValueError("fit_scanned does not support layerwise "
                         "pretraining — call pretrain()/fit() first")
    if not conf.backprop:
        raise ValueError("fit_scanned needs backprop=True")
    if str(g.optimization_algo) != str(
            OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT):
        raise ValueError(
            f"fit_scanned supports SGD-family training only; "
            f"{g.optimization_algo!r} routes through the Solver path — "
            "use fit()")
    if str(conf.backprop_type) in (str(BackpropType.TRUNCATED_BPTT),
                                   "truncated_bptt"):
        raise ValueError("fit_scanned does not implement TBPTT — use fit()")
    if getattr(g, "iterations", 1) > 1:
        raise ValueError("fit_scanned runs one optimizer pass per batch; "
                         "iterations>1 needs fit()")
    if not batches:
        return net
    structs = {jax.tree.structure(b) for b in batches}
    shapes = {tuple(l.shape for l in jax.tree.leaves(b)) for b in batches}
    if len(structs) > 1 or len(shapes) > 1:
        raise ValueError(
            "fit_scanned needs uniform batch shapes — drop or pad the "
            "ragged tail batch, or use fit()")
    stacked = stack_batches(batches)
    first_dispatch = net._scan_fit is None
    if first_dispatch:
        net._scan_fit = make_scanned_fit(net._get_train_step())
    # telemetry span around the scan dispatch: the FIRST dispatch blocks
    # on trace+compile (the "compile" span — the wall-clock XProf can't
    # cheaply give); later dispatches enqueue asynchronously, so their
    # "step_scan" span measures dispatch, not execution. A NullRecorder
    # (telemetry disabled — the default) makes this a no-op.
    from deeplearning4j_tpu.telemetry import get_default as _telemetry

    rec = _telemetry()
    rng = net._next_rng()
    with rec.span("compile" if first_dispatch else "step_scan",
                  what="fit_scanned", epochs=epochs,
                  n_batches=len(batches)):
        net.params, net.opt_state, net.state, losses = net._scan_fit(
            net.params, net.opt_state, net.state, rng, stacked,
            n_epochs=epochs)
    if first_dispatch:
        # compiled-cost harvest, warmup-only: lower() AFTER the warm
        # dispatch is a jaxpr-cache hit (no retrace); the shapes match
        # because the scan returned same-shaped trees
        from deeplearning4j_tpu.telemetry.costbook import CostBook

        book = getattr(net, "_cost_book", None)
        if book is None or book.recorder is not rec:
            book = CostBook(rec)
            try:
                net._cost_book = book
            except Exception:
                pass
        book.record("fit_scanned", [int(epochs), len(batches)],
                    net._scan_fit,
                    (net.params, net.opt_state, net.state, rng, stacked),
                    kwargs={"n_epochs": epochs})
    per_epoch = losses.mean(axis=1)
    nb = len(batches)
    if net.listeners:
        # counters advance WITH the callbacks so listeners that read model
        # state (per-epoch checkpointers keyed on iteration_count) see the
        # running values; per_epoch[e] device indexing happens only when
        # someone is listening — a bare fit_scanned stays one dispatch
        for e in range(epochs):
            net.iteration_count += nb
            if hasattr(net, "epoch_count"):
                net.epoch_count += 1
            net.score_value = per_epoch[e]
            for lst in net.listeners:
                lst.iteration_done(net, net.iteration_count)
    else:
        net.iteration_count += epochs * nb
        if hasattr(net, "epoch_count"):
            net.epoch_count += epochs
    net.score_value = losses[-1, -1]
    net._epoch_losses = per_epoch
    # one ledger-annotated memory event per fused dispatch when the env
    # cadence is on — the whole scan is one batch boundary
    from deeplearning4j_tpu.telemetry.memstat import sampler_for_net

    mem = sampler_for_net(net, rec)
    if mem.mem_every > 0:
        mem.sample("fit", iteration=net.iteration_count)
    return net


def fit_steps(net, batch_for_step, total_steps, *, on_step=None):
    """Global-step training loop — the elastic-recovery engine.

    Unlike epoch-oriented ``fit``, progress here is a single continuous
    step counter (``net.iteration_count``) that survives process death:
    a restored net resumes at its checkpointed step and
    ``batch_for_step(step)`` (1-based) regenerates the SAME global batch
    any fleet size would see for that step, so an interrupted-and-resumed
    run optimizes the identical sequence as an uninterrupted one.

    ``on_step(step)`` fires after each completed step — where
    `distributed/elastic.py` hangs its checkpoint cadence and the fault
    harness its kill/hang triggers (between one finished collective and
    the next, the same spot a real preemption lands). Emits one
    telemetry ``step`` event per COMPLETED step: dispatch is
    asynchronous, so the step's params are waited on first (no
    transfer — the device score is not read here) and a collective
    that failed on a dead peer raises here, before the step is
    declared done.
    """
    from deeplearning4j_tpu.telemetry import get_default as _telemetry

    rec = _telemetry()
    while net.iteration_count < total_steps:
        step = net.iteration_count + 1
        net.fit(batch_for_step(step))
        # fit() advances iteration_count by the batches it consumed; one
        # DataSet per call keeps the counter == the global step
        if net.iteration_count != step:
            raise ValueError(
                f"batch_for_step({step}) yielded "
                f"{net.iteration_count - step + 1} optimizer passes — "
                "fit_steps needs exactly one DataSet per step (check "
                "`iterations` in the net config)")
        jax.block_until_ready(net.params)
        rec.step(step)
        if on_step is not None:
            on_step(step)
    return net


def mesh_shardings(mesh, data_axis: str = "data"):
    """(replicated, data-sharded) NamedShardings for a mesh data axis."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P()), NamedSharding(mesh, P(data_axis))


def pad_batch_to_multiple(tree, n):
    """Pad every leaf's batch dim to a multiple of n by repeating row 0;
    returns (padded_tree, pad). Sharded inference requires batch % n == 0;
    callers slice the pad rows back off the output."""
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return tree, 0
    B = leaves[0].shape[0]
    pad = (-B) % n
    if pad == 0:
        return tree, 0
    return jax.tree.map(
        lambda v: jnp.concatenate([v, jnp.repeat(v[:1], pad, axis=0)]),
        tree), pad


def make_eval_step(output_fn):
    """output_fn(params, state, features, mask) -> activations."""
    return jax.jit(partial(output_fn))


def tree_cast(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree,
    )
