"""ComputationGraph — the DAG container for multi-input/multi-output nets.

Reference: nn/graph/ComputationGraph.java (~2,500 LoC): topological
sort:235,458-483, init:219-231, fit:545-672, forward over topo order:886,
backprop:958-977; vertex impls under graph/vertex/impl/*.

TPU-native: the topo-order forward IS the traced jaxpr (SURVEY.md §3.2);
vertices are pure functions; backward is jax.grad of the summed output
losses; the whole step is one jitted donated computation.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu.datasets.api import DataSet, MultiDataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.nn.conf.graph_conf import (
    ComputationGraphConfiguration,
    DuplicateToTimeSeriesVertexConf,
    ElementWiseVertexConf,
    LastTimeStepVertexConf,
    LayerVertexConf,
    MergeVertexConf,
    PreprocessorVertexConf,
    ScaleVertexConf,
    StackVertexConf,
    SubsetVertexConf,
    UnstackVertexConf,
    loop_span,
)
from deeplearning4j_tpu.nn.conf.enums import BackpropType, OptimizationAlgorithm
from deeplearning4j_tpu.nn.conf.layers import (
    BaseOutputLayer,
    BaseRecurrentLayer,
    validate_layer_names,
)
from deeplearning4j_tpu.nn.layers import get_impl, l1_l2_penalty
from deeplearning4j_tpu.nn.layers.base import (input_region,
                                                pop_aux_losses,
                                                region_scope)
from deeplearning4j_tpu.nn.training import make_train_step, tree_cast
from deeplearning4j_tpu.nn.updater import build_optimizer

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float64": jnp.float64}


class ComputationGraph:
    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.topo = conf.topological_order()
        # (entry, span, times) of the graph's loop, or None
        self.loop = loop_span(conf)
        self.layer_vertices = {
            name: v for name, v in conf.vertices.items() if isinstance(v, LayerVertexConf)
        }
        self.impls = {name: get_impl(v.layer) for name, v in self.layer_vertices.items()}
        self.output_layer_names = [
            n for n in conf.network_outputs
            if n in self.layer_vertices
            and isinstance(self.layer_vertices[n].layer, BaseOutputLayer)
        ]
        self.params = None
        self.state = None
        self.opt_state = None
        self.tx = None
        self.listeners = []
        self.iteration_count = 0
        self.score_value = float("nan")
        self._train_step = None
        self._scan_fit = None
        self._output_jit = None
        self._score_examples_jit = {}
        self._rng = None
        self._mesh = None
        self._zero1 = False
        self._multiprocess = False
        self._rnn_carries = None  # streaming inference state (rnn_time_step)
        self._rnn_jit = None

    @property
    def compute_dtype(self):
        return _DTYPES[self.conf.conf.dtype]

    @property
    def param_dtype(self):
        return _DTYPES[self.conf.conf.param_dtype]

    def init(self, seed: Optional[int] = None):
        g = self.conf.conf
        key = jax.random.PRNGKey(g.seed if seed is None else seed)
        self._rng = jax.random.fold_in(key, 1)
        params, state = {}, {}
        names = sorted(self.layer_vertices)
        keys = jax.random.split(key, max(len(names), 1))
        for name, k in zip(names, keys):
            v = self.layer_vertices[name]
            validate_layer_names(v.layer)
            p, s = self.impls[name].init(v.layer, k, self.param_dtype)
            params[name] = p
            state[name] = s
        self.params = params
        self.state = state
        self.tx = build_optimizer(
            g, {n: v.layer for n, v in self.layer_vertices.items()},
            params=params)
        self.opt_state = self.tx.init(params)
        return self

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)

    def set_mesh(self, mesh, zero1: bool = False, axes=None,
                 n_microbatches=None, tp_rules=None, overlap=None):
        """Single distributed entry point: axes maps parallelism roles
        ("data"/"model"/"pipe"/"expert") to mesh axis names — see
        parallel/placement.py. Without axes: round-1 pure DP over 'data'.
        overlap: True / bucket bytes / a BucketPlan — bucketed gradient
        allreduce with compute/communication overlap (parallel/overlap.py;
        pure DP only, composes with zero1)."""
        from deeplearning4j_tpu.parallel.placement import configure_mesh

        return configure_mesh(self, mesh, zero1=zero1, axes=axes,
                              n_microbatches=n_microbatches,
                              tp_rules=tp_rules, overlap=overlap)

    def _canonical_params(self):
        """Params in the per-layer layout regardless of an active pipeline
        restructure (read paths: output/score/serialization/flat views)."""
        if getattr(self, "_pp_plan", None) is not None:
            return self._pp_plan.to_canonical(self.params)
        return self.params

    def set_optimizer(self, tx):
        self.tx = tx
        self.opt_state = tx.init(self.params)
        self._train_step = None
        self._scan_fit = None

    def _next_rng(self):
        self._rng, k = jax.random.split(self._rng)
        return k

    # --------------------------------------------------------------- forward
    def _vertex_forward(self, name, vconf, inputs, params, state, train, rng,
                        masks, acts):
        """Non-layer vertex semantics (reference graph/vertex/impl/*)."""
        if isinstance(vconf, MergeVertexConf):
            return jnp.concatenate(inputs, axis=-1)
        if isinstance(vconf, ElementWiseVertexConf):
            op = vconf.op
            out = inputs[0]
            for x in inputs[1:]:
                if op == "add":
                    out = out + x
                elif op == "subtract":
                    out = out - x
                elif op == "product":
                    out = out * x
                elif op == "max":
                    out = jnp.maximum(out, x)
                elif op == "average":
                    out = out + x
                else:
                    raise ValueError(f"elementwise op {op}")
            if op == "average":
                out = out / len(inputs)
            return out
        if isinstance(vconf, SubsetVertexConf):
            return inputs[0][..., vconf.from_idx:vconf.to_idx + 1]
        if isinstance(vconf, PreprocessorVertexConf):
            return vconf.preprocessor.pre_process(inputs[0])
        if isinstance(vconf, ScaleVertexConf):
            return inputs[0] * vconf.scale
        if isinstance(vconf, LastTimeStepVertexConf):
            x = inputs[0]  # [B, T, f]
            mask = masks.get(vconf.mask_input) if vconf.mask_input else None
            if mask is None:
                return x[:, -1, :]
            idx = jnp.maximum(jnp.sum(mask.astype(jnp.int32), axis=1) - 1, 0)
            return x[jnp.arange(x.shape[0]), idx, :]
        if isinstance(vconf, DuplicateToTimeSeriesVertexConf):
            ref = acts[vconf.reference_input]
            T = ref.shape[1]
            return jnp.broadcast_to(
                inputs[0][:, None, :], (inputs[0].shape[0], T, inputs[0].shape[1]))
        if isinstance(vconf, StackVertexConf):
            return jnp.concatenate(inputs, axis=0)
        if isinstance(vconf, UnstackVertexConf):
            return jnp.split(inputs[0], vconf.stack_size, axis=0)[vconf.from_idx]
        raise ValueError(f"Unhandled vertex type {type(vconf).__name__} for '{name}'")

    def _time_preserving(self, vconf, T):
        """Whether a vertex maps [B, T, f] -> [B, T, f'] keeping the time
        axis: elementwise/merge/scale vertices by construction; layer
        vertices by their declared InputType mapping (recurrent in ->
        recurrent out of the same length)."""
        if isinstance(vconf, (MergeVertexConf, ElementWiseVertexConf,
                              ScaleVertexConf)):
            return True
        if isinstance(vconf, LayerVertexConf):
            from deeplearning4j_tpu.nn.conf.inputs import InputType

            lc = vconf.layer
            try:
                ot = lc.get_output_type(
                    InputType.recurrent(getattr(lc, "n_in", 0) or 0, T))
            except Exception:
                return False
            return (ot.kind == "recurrent"
                    and ot.timeseries_length == T)
        return False

    def _forward(self, params, state, input_dict, *, train, rng, masks=None,
                 collect=False, carries=None):
        """The topo-order forward. A loop (`LoopConf`) runs its span
        `times` times in one `lax.fori_loop`, its body traced once, each
        vertex with its one set of parameters; pass t + 1 reads the last
        vertex's output of pass t where pass 0 read the entry, a layer's
        state is carried from pass to pass, and of the span only the last
        vertex's activation is left in `acts`. A loop of 1 is the
        unlooped forward."""
        masks = dict(masks) if masks else {}
        acts = {}
        cdtype = self.compute_dtype
        for k, v in input_dict.items():
            v = jnp.asarray(v)
            if jnp.issubdtype(v.dtype, jnp.floating):
                v = v.astype(cdtype)
            acts[k] = v
        new_state = {}
        new_carries = {}
        names = [n for n in self.topo if n not in self.conf.network_inputs]
        rngs = (jax.random.split(rng, max(len(names), 1)) if rng is not None
                else [None] * len(names))
        regions = {}
        loop = self.loop
        looped = () if loop is None or loop[2] == 1 else loop[1]
        if looped and carries is not None:
            raise ValueError("a looped graph keeps no recurrent carries")

        def visit(at, name, k, acts, state_in, new_state):
            vconf = self.conf.vertices[name]
            inputs = [acts[i] for i in self.conf.vertex_inputs[name]]
            # each layer's ops (and, through transpose(jvp(...)), its
            # backward) under its impl's region; a vertex under its
            # latest input's
            region = (self.impls[name].region
                      if isinstance(vconf, LayerVertexConf)
                      else input_region(self.conf.vertex_inputs[name],
                                        regions))
            regions[name] = (at, region)
            with region_scope(region):
                if isinstance(vconf, LayerVertexConf):
                    x = inputs[0]
                    if vconf.preprocessor is not None:
                        x = vconf.preprocessor.pre_process(x)
                    p = params.get(name, {})
                    if cdtype != self.param_dtype:
                        p = tree_cast(p, cdtype)
                    in_mask = masks.get(self.conf.vertex_inputs[name][0])
                    want_carry = (
                        carries is not None
                        and isinstance(vconf.layer, BaseRecurrentLayer)
                        and hasattr(self.impls[name], "initial_carry"))
                    kw = ({"initial_carry": carries.get(name),
                           "return_carry": True} if want_carry else {})

                    def run(p_, s_, x_, _impl=self.impls[name],
                            _lc=vconf.layer, _rng=k, _mask=in_mask, _kw=kw):
                        return _impl.apply(_lc, p_, s_, x_, train=train,
                                           rng=_rng, mask=_mask, **_kw)

                    if self.conf.conf.remat:
                        # jax.checkpoint per vertex: activations inside
                        # the vertex are recomputed in the backward instead
                        # of living in HBM for the whole step — the
                        # long-context lever (seq-16k at batch 16 OOMs a
                        # 16GB chip without it; the MultiLayerNetwork
                        # container has the same per-layer policy at
                        # multilayer.py:169)
                        run = jax.checkpoint(run)
                    out = run(p, state_in.get(name, {}), x)
                    if want_carry:
                        y, s, carry = out
                        new_carries[name] = carry
                    else:
                        y, s = out
                    acts[name] = y
                    new_state[name] = s
                else:
                    acts[name] = self._vertex_forward(
                        name, vconf, inputs, params, state, train, k, masks,
                        acts)
            # propagate time masks along the DAG (reference
            # setLayerMaskArrays/feedForwardMaskArrays semantics): a
            # time-preserving vertex carries its first input's mask so
            # downstream recurrent/attention layers see the padding.
            # Gated on vertex SEMANTICS (declared time-preserving kinds /
            # recurrent-output layers), not just output shape — a vertex
            # permuting axes to [B, C, T'] with C == T must not inherit a
            # time mask (ADVICE r3)
            m = masks.get(self.conf.vertex_inputs[name][0])
            y_out = acts[name]
            if (m is not None and hasattr(y_out, "ndim") and y_out.ndim == 3
                    and y_out.shape[0] == m.shape[0]
                    and y_out.shape[1] == m.shape[1]
                    and self._time_preserving(vconf, m.shape[1])):
                masks[name] = m

        def run_loop():
            entry, span, times = loop
            first = names.index(span[0])
            held = [n for n in span if n in self.layer_vertices]

            def body(t, carry):
                x, st = carry
                inner = dict(acts, **{entry: x})
                out_state = {}
                for at in range(first, first + len(span)):
                    k = rngs[at]
                    if k is not None:
                        k = jax.random.fold_in(k, t)
                    visit(at, names[at], k, inner, st, out_state)
                return inner[span[-1]], {n: out_state[n] for n in held}

            with region_scope("loop"):
                x, st = jax.lax.fori_loop(
                    0, times, body,
                    (acts[entry], {n: state.get(n, {}) for n in held}))
            acts[span[-1]] = x
            new_state.update(st)

        for at, (name, k) in enumerate(zip(names, rngs)):
            if name in looped:
                if name == looped[0]:
                    run_loop()
                continue
            visit(at, name, k, acts, state, new_state)
        for n in self.layer_vertices:
            new_state.setdefault(n, state.get(n, {}))
        if collect:
            return acts, new_state, new_carries
        return [acts[o] for o in self.conf.network_outputs], new_state, new_carries

    def _loss(self, params, state, rng, batch, train=True):
        """Sum of output-layer losses + L1/L2 (reference
        computeGradientAndScore:816)."""
        input_dict = dict(zip(self.conf.network_inputs, batch["features"]))
        masks = {}
        if batch.get("features_masks") is not None:
            masks = {k: m for k, m in zip(self.conf.network_inputs,
                                          batch["features_masks"]) if m is not None}
        n_out = len(self.conf.network_outputs)
        if rng is not None:
            keys = jax.random.split(rng, n_out + 1)
            k_body, k_outs = keys[0], keys[1:]
        else:
            k_body, k_outs = None, [None] * n_out
        acts, new_state, new_carries = self._forward(
            params, state, input_dict, train=train, rng=k_body, masks=masks,
            collect=True, carries=batch.get("carries"))
        loss = 0.0
        labels_list = batch["labels"]
        lmasks = batch.get("labels_masks") or [None] * len(labels_list)
        cdtype = self.compute_dtype
        for out_name, labels, lmask, k_out in zip(
                self.conf.network_outputs, labels_list, lmasks, k_outs):
            vconf = self.conf.vertices[out_name]
            if not isinstance(vconf, LayerVertexConf) or not isinstance(
                    vconf.layer, BaseOutputLayer):
                raise ValueError(f"Output '{out_name}' is not an output layer")
            x = acts[self.conf.vertex_inputs[out_name][0]]
            if vconf.preprocessor is not None:
                x = vconf.preprocessor.pre_process(x)
            # cast output-layer params to the compute dtype like _forward
            # does for every other layer — otherwise a bf16 model streams
            # its [d, V] LM-head weight through the loss kernels in f32
            # (2x the HBM traffic of the declared policy; profiled r3)
            p_out = params[out_name]
            if cdtype != self.param_dtype:
                p_out = tree_cast(p_out, cdtype)
            with region_scope(self.impls[out_name].region):
                loss = loss + self.impls[out_name].loss(
                    vconf.layer, p_out, x, labels, train=train, rng=k_out,
                    mask=lmask)
        for name, v in self.layer_vertices.items():
            loss = loss + l1_l2_penalty(v.layer, params[name])
        aux, new_state = pop_aux_losses(new_state)
        if train:
            loss = loss + aux
        extras = ({"carries": new_carries} if batch.get("carries") is not None
                  else {})
        return loss, (new_state, extras)

    # ------------------------------------------------------------------- fit
    @staticmethod
    def _to_mds(ds):
        if isinstance(ds, MultiDataSet):
            return ds
        return MultiDataSet([ds.features], [ds.labels],
                            None if ds.features_mask is None else [ds.features_mask],
                            None if ds.labels_mask is None else [ds.labels_mask])

    def _batch_dict(self, mds: MultiDataSet):
        b = {
            "features": tuple(jnp.asarray(f) for f in mds.features),
            "labels": tuple(jnp.asarray(l) for l in mds.labels),
        }
        if mds.features_masks is not None:
            b["features_masks"] = tuple(
                None if m is None else jnp.asarray(m) for m in mds.features_masks)
        if mds.labels_masks is not None:
            b["labels_masks"] = tuple(
                None if m is None else jnp.asarray(m) for m in mds.labels_masks)
        return self._globalize_batch(b)

    def _globalize_batch(self, b):
        """Process-spanning mesh: assemble this process's local batch
        shard into global arrays (distributed/global_mesh.py); identity
        on single-process meshes."""
        if not getattr(self, "_multiprocess", False):
            return b
        from deeplearning4j_tpu.distributed.global_mesh import globalize_batch

        axes = getattr(self, "_mesh_axes", None)
        return globalize_batch(b, self._mesh,
                               (axes or {}).get("data", "data"))

    def resume_from(self, checkpoint_dir: str, step=None, *,
                    target_mesh=None, target_axes=None):
        """Elastic-recovery resume entry (same contract as
        `MultiLayerNetwork.resume_from`, including the `reshard/`
        target-mesh routing): restore the latest (or given) Orbax
        checkpoint into this graph, returning the restored step — 0
        when the directory holds no checkpoint yet."""
        from deeplearning4j_tpu.util.orbax_checkpoint import (
            ShardedCheckpointer,
        )

        try:
            ShardedCheckpointer(checkpoint_dir).restore(
                self, step=step, target_mesh=target_mesh,
                target_axes=target_axes)
        except FileNotFoundError:
            if step is not None:  # a NAMED step missing is a real error
                raise
            return 0
        return self.iteration_count

    def fit(self, data, labels=None, epochs: int = 1):
        """Train (reference ComputationGraph.fit:545-672, incl. the
        pretrain:165-equivalent, tbptt branch, and Solver dispatch)."""
        if self.params is None:
            self.init()
        if labels is not None:
            data = DataSet(data, labels)
        single_batch = isinstance(data, (DataSet, MultiDataSet))
        if single_batch:
            # single batch: the pipeline's synchronous fallback skips
            # the per-call producer thread (fit_steps lands here)
            data = ListDataSetIterator([data])
        it = data
        if self.conf.pretrain:
            self.pretrain(it)
            it.reset()
        if not self.conf.backprop:
            return self
        g = self.conf.conf
        if str(g.optimization_algo) != str(
                OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT):
            return self._fit_with_solver(it, epochs)
        self._get_train_step()
        tbptt = self.conf.backprop_type in (BackpropType.TRUNCATED_BPTT,
                                            "truncated_bptt")

        def convert(ds):
            # prefetch-thread work (data/pipeline.py): MultiDataSet
            # coercion + device conversion + globalization overlap the
            # step. batch None = a TBPTT sequence (per-window conversion
            # happens on the step thread).
            mds = self._to_mds(ds)
            if tbptt and self._needs_tbptt(mds):
                return mds, None
            return mds, self._batch_dict(mds)

        from deeplearning4j_tpu.data.pipeline import iter_prefetched
        from deeplearning4j_tpu.telemetry import get_default as _telemetry
        from deeplearning4j_tpu.telemetry.memstat import sampler_for_net

        # batch-boundary memory sampling: one modulo per iteration unless
        # DL4J_TPU_MEM_EVERY enables the cadence (memstat.on_step)
        mem = sampler_for_net(self, _telemetry())

        for _ in range(epochs):
            it.reset()
            for _ds, (mds, batch) in iter_prefetched(
                    it, convert, depth=0 if single_batch else None):
                if batch is None:
                    self._fit_tbptt(mds)
                    continue
                for _i in range(max(1, g.iterations)):
                    self.params, self.opt_state, self.state, loss, _ = self._train_step(
                        self.params, self.opt_state, self.state, self._next_rng(),
                        batch)
                    self.score_value = loss
                    self.iteration_count += 1
                    for lst in self.listeners:
                        lst.iteration_done(self, self.iteration_count)
                    mem.on_step(self.iteration_count)
        return self


    # score_value is lazily materialized: the jitted step returns a DEVICE
    # scalar, and converting it eagerly would force a host sync every
    # iteration and stall the dispatch pipeline. The
    # setter accepts device scalars; the getter pays the sync on first
    # read (listeners that read every iteration opt into that cost).
    @property
    def score_value(self):
        v = getattr(self, "_score_raw", float("nan"))
        if not isinstance(v, float):
            v = float(v)
            self._score_raw = v
        return v

    @score_value.setter
    def score_value(self, v):
        self._score_raw = v

    def _get_train_step(self):
        """Jitted donated train step (same contract as MLN._get_train_step)."""
        if self._train_step is None:
            axes_map = getattr(self, "_mesh_axes", None) or {}
            # seq WITH pipe routes through the PP schedule (its shard_map
            # is manual over the seq axis too); seq alone takes the SP step
            if "seq" in axes_map and "pipe" not in axes_map:
                from deeplearning4j_tpu.parallel.sequence_parallel import (
                    make_sp_train_step,
                )

                sp = make_sp_train_step(self, self._mesh,
                                        seq_axis=axes_map["seq"],
                                        data_axis=axes_map.get("data"))

                def step(params, opt_state, state, rng, batch):
                    masks = list(batch.get("features_masks") or []) + list(
                        batch.get("labels_masks") or [])
                    if any(m is not None for m in masks):
                        raise ValueError(
                            "masks are not supported under sequence "
                            "parallelism — pad to full length")
                    p, o, s, loss = sp(params, opt_state, state, rng,
                                       batch["features"][0],
                                       batch["labels"][0])
                    return p, o, s, loss, {}

                self._train_step = step
            elif getattr(self, "_pp_plan", None) is not None:
                from deeplearning4j_tpu.parallel.pipeline import (
                    make_pp_train_step,
                )

                self._train_step = make_pp_train_step(
                    self, self._pp_plan, self._mesh, self._mesh_axes,
                    self._pp_microbatches, self._resolved_rules)
            else:
                confs = {n: v.layer for n, v in self.layer_vertices.items()}
                axes = getattr(self, "_mesh_axes", None)
                self._train_step = make_train_step(
                    self._loss, self.tx, confs, mesh=self._mesh,
                    zero1_opt_state=(self.opt_state if self._zero1 else None),
                    data_axis=(axes or {}).get("data", "data"),
                    param_sharding=getattr(self, "_param_sh", None),
                    overlap=getattr(self, "_overlap_plan", None))
        return self._train_step

    def fit_scanned(self, data, labels=None, epochs: int = 1):
        """Whole-epoch fused training for DAG networks — see
        MultiLayerNetwork.fit_scanned (same engine, nn/training.fused_fit;
        same guards and per-epoch listener contract)."""
        from deeplearning4j_tpu.nn.training import fused_fit

        if self.params is None:
            self.init()
        if labels is not None:
            data = DataSet(data, labels)
        if isinstance(data, (DataSet, MultiDataSet)):
            data = ListDataSetIterator([data])
        batches = [self._batch_dict(self._to_mds(ds)) for ds in data]
        return fused_fit(self, batches, epochs)

    def _fit_with_solver(self, it, epochs: int):
        """CG/LBFGS/line-GD path (reference Solver dispatch — the graph
        delegates per-minibatch optimization exactly like MLN does)."""
        from deeplearning4j_tpu.optimize.solvers import Solver

        if self.conf.backprop_type in (BackpropType.TRUNCATED_BPTT,
                                       "truncated_bptt"):
            raise ValueError(
                "TRUNCATED_BPTT requires STOCHASTIC_GRADIENT_DESCENT; "
                "second-order solvers would differentiate the full sequence")
        solver = Solver(self)

        from deeplearning4j_tpu.data.pipeline import iter_prefetched

        for _ in range(epochs):
            it.reset()
            for _ds, batch in iter_prefetched(
                    it, lambda ds: self._batch_dict(self._to_mds(ds))):
                solver.optimize(batch, rng=self._next_rng())
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration_count)
        return self

    def _needs_tbptt(self, mds) -> bool:
        L = self.conf.tbptt_fwd_length
        return any(np.asarray(f).ndim == 3 and f.shape[1] > L
                   for f in mds.features)

    def _initial_carries(self, batch_size):
        """Zero carries for every recurrent layer vertex."""
        carries = {}
        for name, v in self.layer_vertices.items():
            impl = self.impls[name]
            if isinstance(v.layer, BaseRecurrentLayer) and hasattr(
                    impl, "initial_carry"):
                carries[name] = impl.initial_carry(v.layer, batch_size,
                                                   self.compute_dtype)
        return carries

    @staticmethod
    def _slice_time(arrs, t0, L):
        """Window [t0, t0+L) of every 3-D array; 2-D pass through unchanged
        (static inputs broadcast to all segments, as the reference's
        rnn-to-ff mixed graphs do)."""
        return tuple(None if a is None
                     else (a[:, t0:t0 + L] if np.asarray(a).ndim >= 3 else a)
                     for a in arrs)

    def _fit_tbptt(self, mds: MultiDataSet):
        """Truncated BPTT over the DAG (reference ComputationGraph fit tbptt
        branch): slide a tbptt_fwd_length window over time; recurrent-vertex
        carries thread between segments through the jitted step, gradients
        stop at segment boundaries."""
        T = max(f.shape[1] for f in mds.features if np.asarray(f).ndim == 3)
        L = self.conf.tbptt_fwd_length
        B = mds.features[0].shape[0]
        for lab in mds.labels:
            if np.asarray(lab).ndim != 3:
                raise ValueError(
                    "TRUNCATED_BPTT needs time-distributed labels "
                    f"[batch, time, n_out]; got shape {np.asarray(lab).shape}. "
                    "A per-sequence label would be counted once per segment "
                    "against mid-sequence activations — train with standard "
                    "BPTT (or a LastTimeStep head on full sequences) instead")
        carries = self._initial_carries(B)

        def mask_slice(masks, t0):
            if masks is None:
                return None
            return tuple(None if m is None
                         else (m[:, t0:t0 + L] if np.asarray(m).ndim >= 2
                               and m.shape[1] == T else m)
                         for m in masks)

        for t0 in range(0, T, L):
            sub = MultiDataSet(
                self._slice_time(mds.features, t0, L),
                self._slice_time(mds.labels, t0, L),
                mask_slice(mds.features_masks, t0),
                mask_slice(mds.labels_masks, t0),
            )
            batch = self._batch_dict(sub)
            batch["carries"] = carries
            self.params, self.opt_state, self.state, loss, extras = self._train_step(
                self.params, self.opt_state, self.state, self._next_rng(), batch)
            carries = extras.get("carries", carries)
            self.score_value = loss
            self.iteration_count += 1
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration_count)

    # -------------------------------------------------------------- pretrain
    def pretrain(self, it, epochs: int = 1):
        """Greedy layer-wise pretraining over the DAG (reference
        ComputationGraph.pretrain): for each pretrain-capable layer vertex in
        topological order, train its params on the activations feeding it."""
        if getattr(self, "_pp_plan", None) is not None:
            raise ValueError("pretrain is not supported while a pipeline "
                             "mesh is active — set_mesh(None) first")
        if self.params is None:
            self.init()
        if isinstance(it, (DataSet, MultiDataSet)):
            it = ListDataSetIterator([it])
        for name in self.topo:
            v = self.conf.vertices.get(name)
            if not isinstance(v, LayerVertexConf) or not v.layer.is_pretrain_layer():
                continue
            impl = self.impls[name]
            lc = v.layer
            tx = build_optimizer(self.conf.conf, {name: lc})
            # the optimizer's per-layer lr/updater overrides key on layer
            # names, so feed it {name: params} — not the bare inner dict
            opt = tx.init({name: self.params[name]})
            src = self.conf.vertex_inputs[name][0]
            is_input = src in self.conf.network_inputs

            @jax.jit
            def featurize(params, state, input_dict, _src=src, _v=v):
                acts, _, _ = self._forward(params, state, input_dict,
                                           train=False, rng=None, collect=True)
                x = acts[_src]
                if _v.preprocessor is not None:
                    x = _v.preprocessor.pre_process(x)
                return x

            @jax.jit
            def pstep(p, opt_state, rng, x, _impl=impl, _lc=lc, _tx=tx,
                      _name=name):
                loss, grads = jax.value_and_grad(
                    lambda q: _impl.pretrain_loss(_lc, q[_name], x, rng))(
                        {_name: p})
                updates, opt_state = _tx.update(grads, opt_state, {_name: p})
                return (optax.apply_updates({_name: p}, updates)[_name],
                        opt_state, loss)

            for _ in range(epochs):
                it.reset()
                while it.has_next():
                    mds = self._to_mds(it.next())
                    input_dict = dict(zip(self.conf.network_inputs,
                                          [jnp.asarray(f) for f in mds.features]))
                    if is_input and v.preprocessor is None:
                        x = jnp.asarray(input_dict[src], self.compute_dtype)
                    else:
                        x = featurize(self.params, self.state, input_dict)
                    p_new, opt, loss = pstep(self.params[name], opt,
                                             self._next_rng(), x)
                    self.params = dict(self.params, **{name: p_new})
                    self.score_value = loss
        return self

    # ------------------------------------------------------------- inference
    def output(self, *inputs, train: bool = False):
        """Outputs for given inputs (reference output). Returns a list (one
        per network output), or the single array if one output."""
        input_dict = dict(zip(self.conf.network_inputs, inputs))
        axes = getattr(self, "_mesh_axes", None)
        data_axis = (axes or {}).get("data", "data")
        has_data = (self._mesh is not None
                    and data_axis in self._mesh.axis_names)
        if self._output_jit is None:
            def _out(params, state, input_dict):
                if getattr(self, "_pp_plan", None) is not None:
                    # pipelined layout at rest: slice back to per-layer
                    # params inside the jit (free data movement)
                    params = self._pp_plan.to_canonical(params)
                ys, _, _ = self._forward(params, state, input_dict, train=False,
                                         rng=None)
                return ys
            if has_data:
                # distributed evaluation: batch sharded over the data axis
                # (reference EvaluateFlatMapFunction + Evaluation.merge)
                from deeplearning4j_tpu.nn.training import mesh_shardings

                repl, data = mesh_shardings(self._mesh, data_axis)
                # committed TP/PP params keep their placement (None);
                # plain-DP params are explicitly replicated
                p_in = (None if (getattr(self, "_pp_plan", None) is not None
                                 or getattr(self, "_param_sh", None)
                                 is not None) else repl)
                # process-spanning mesh: replicated output (a data-sharded
                # result spans non-addressable devices — unfetchable)
                out_sh = (repl if getattr(self, "_multiprocess", False)
                          else data)
                self._output_jit = jax.jit(
                    _out, in_shardings=(p_in, repl, data),
                    out_shardings=out_sh)
            else:
                self._output_jit = jax.jit(_out)
        input_dict = {k: jnp.asarray(v) for k, v in input_dict.items()}
        pad = 0
        if has_data:
            # pad batch to a multiple of the data axis, slice back below
            from deeplearning4j_tpu.nn.training import pad_batch_to_multiple

            input_dict, pad = pad_batch_to_multiple(
                input_dict, self._mesh.shape[data_axis])
            if getattr(self, "_multiprocess", False):
                # inference takes the FULL batch on every process (unlike
                # fit's per-process shards): globalize it data-sharded
                from deeplearning4j_tpu.distributed.global_mesh import (
                    globalize_full,
                )

                input_dict = {k: globalize_full(v, self._mesh, data_axis)
                              for k, v in input_dict.items()}
        ys = self._output_jit(self.params, self.state, input_dict)
        if pad:
            ys = [y[:-pad] for y in ys]
        return ys[0] if len(ys) == 1 else ys

    def predict(self, *inputs):
        out = self.output(*inputs)
        if isinstance(out, list):
            return [np.asarray(jnp.argmax(o, axis=-1)) for o in out]
        return np.asarray(jnp.argmax(out, axis=-1))

    def inference_fn(self):
        """A pure ``(params, state, x, mask=None) -> y`` inference-mode
        forward for external jit owners (the serving engine) — the DAG
        twin of MultiLayerNetwork.inference_fn. Serving dispatches on
        ONE padded input/output pair, so multi-input/multi-output graphs
        are rejected here rather than silently dropping streams."""
        ins = self.conf.network_inputs
        outs = self.conf.network_outputs
        if len(ins) != 1 or len(outs) != 1:
            raise ValueError(
                f"serving needs a single-input/single-output graph; this "
                f"one has inputs {list(ins)} and outputs {list(outs)}")
        name = ins[0]

        def fwd(params, state, x, mask=None):
            if getattr(self, "_pp_plan", None) is not None:
                params = self._pp_plan.to_canonical(params)
            masks = {} if mask is None else {name: mask}
            ys, _, _ = self._forward(params, state, {name: x},
                                     train=False, rng=None, masks=masks)
            return ys[0]
        return fwd

    def incremental_decode_fn(self, kv_dtype: str = "f32",
                              page_size: int = 16):
        """A pure jitted-step body ``(params, state, cache, token, pos)
        -> (probs, cache)`` — autoregressive decode with the KV cache as
        explicit threaded state (nn/decode.py). The productionized
        `rnn_time_step` contract for attention stacks: one new token per
        cache row at its own position, single-query attention against
        the cache, step cost independent of prompt length. External jit
        owners (serving/engine.py GenerationEngine) control the compile
        cache, exactly like `inference_fn`. kv_dtype="int8" reads/writes
        the quantized paged cache."""
        from deeplearning4j_tpu.nn.decode import make_decode_fn

        return make_decode_fn(self, kv_dtype, page_size)

    def prefill_fn(self, kv_dtype: str = "f32", page_size: int = 16):
        """The chunked-prefill twin of `incremental_decode_fn`:
        ``(params, state, cache, tokens, kmask, rows, start, last_idx)
        -> (probs_last, cache)`` fills cache rows from a bucket-shaped
        prompt chunk, reusing the autotuned flash kernels for the
        within-chunk attention (nn/decode.py)."""
        from deeplearning4j_tpu.nn.decode import make_prefill_fn

        return make_prefill_fn(self, kv_dtype, page_size)

    def verify_decode_fn(self, kv_dtype: str = "f32",
                         page_size: int = 16):
        """The speculative verification step ``(params, state, cache,
        tokens [B, K], pos) -> (probs [B, K, V], cache)`` — K candidate
        tokens per row checked in ONE fixed-shape call
        (nn/decode.make_verify_fn)."""
        from deeplearning4j_tpu.nn.decode import make_verify_fn

        return make_verify_fn(self, kv_dtype, page_size)

    def init_kv_cache(self, batch: int, capacity: int,
                      kv_dtype: str = "f32", page_size: int = 16):
        """Zeroed decode cache for `batch` rows of `capacity` key slots
        (nn/decode.init_cache)."""
        from deeplearning4j_tpu.nn.decode import init_cache

        return init_cache(self, batch, capacity, kv_dtype, page_size)

    def kv_cache_specs(self, capacity: int, kv_dtype: str = "f32",
                       page_size: int = 16) -> dict:
        """{layer: {array: (shape of one slot, dtype name)}}: each
        attention layer's own cache spec (nn/decode.cache_specs), what
        `init_kv_cache` allocates and the serving allocator bills."""
        from deeplearning4j_tpu.nn.decode import cache_specs

        return cache_specs(self, capacity, kv_dtype, page_size)

    def score(self, ds=None, training: bool = False):
        if ds is None:
            return self.score_value
        mds = self._to_mds(ds)
        loss, _ = self._loss(self._canonical_params(), self.state, None,
                             self._batch_dict(mds), train=training)
        return float(loss)

    def score_examples(self, ds, add_regularization: bool = False):
        """One score PER EXAMPLE [batch] over the DAG — summed across all
        output layers like score() (reference spark
        computationgraph/scoring/ScoreExamplesFunction.java). Inference-
        mode forward; `add_regularization` adds the network L1/L2 penalty
        to each example. With a mesh set, shards over the 'data' axis."""
        mds = self._to_mds(ds)
        batch = self._batch_dict(mds)
        key = bool(add_regularization)
        if key not in self._score_examples_jit:
            def _scores(params, state, batch):
                input_dict = dict(zip(self.conf.network_inputs,
                                      batch["features"]))
                masks = {}
                if batch.get("features_masks") is not None:
                    masks = {k: m for k, m in zip(self.conf.network_inputs,
                                                  batch["features_masks"])
                             if m is not None}
                acts, _, _ = self._forward(params, state, input_dict,
                                           train=False, rng=None,
                                           masks=masks, collect=True)
                per = 0.0
                labels_list = batch["labels"]
                lmasks = (batch.get("labels_masks")
                          or [None] * len(labels_list))
                cdtype = self.compute_dtype
                for out_name, labels, lmask in zip(
                        self.conf.network_outputs, labels_list, lmasks):
                    vconf = self.conf.vertices[out_name]
                    x = acts[self.conf.vertex_inputs[out_name][0]]
                    if vconf.preprocessor is not None:
                        x = vconf.preprocessor.pre_process(x)
                    p_out = params[out_name]
                    if cdtype != self.param_dtype:
                        p_out = tree_cast(p_out, cdtype)
                    per = per + self.impls[out_name].loss(
                        vconf.layer, p_out, x, labels, train=False,
                        rng=None, mask=lmask, per_example=True)
                if add_regularization:
                    reg = 0.0
                    for name, v in self.layer_vertices.items():
                        reg = reg + l1_l2_penalty(v.layer, params[name])
                    per = per + reg
                return per

            axes = getattr(self, "_mesh_axes", None)
            data_axis = (axes or {}).get("data", "data")
            if (self._mesh is not None
                    and data_axis in self._mesh.axis_names):
                from deeplearning4j_tpu.nn.training import mesh_shardings

                repl, data = mesh_shardings(self._mesh, data_axis)
                p_in = (None if (getattr(self, "_pp_plan", None) is not None
                                 or getattr(self, "_param_sh", None)
                                 is not None) else repl)
                batch_sh = jax.tree.map(lambda _: data, batch)
                self._score_examples_jit[key] = jax.jit(
                    _scores, in_shardings=(p_in, repl, batch_sh),
                    out_shardings=data)
            else:
                self._score_examples_jit[key] = jax.jit(_scores)
        axes = getattr(self, "_mesh_axes", None)
        data_axis = (axes or {}).get("data", "data")
        params = self._canonical_params()
        if self._mesh is not None and data_axis in self._mesh.axis_names:
            from deeplearning4j_tpu.nn.training import pad_batch_to_multiple

            B = np.asarray(mds.features[0]).shape[0]
            batch, pad = pad_batch_to_multiple(
                batch, self._mesh.shape[data_axis])
            per = self._score_examples_jit[key](params, self.state, batch)
            return np.asarray(per)[:B]
        return np.asarray(
            self._score_examples_jit[key](params, self.state, batch))

    def evaluate(self, it, top_n: int = 1):
        from deeplearning4j_tpu.eval.evaluation import Evaluation

        ev = Evaluation(top_n=top_n)
        if isinstance(it, (DataSet, MultiDataSet)):
            it = ListDataSetIterator([it])
        it.reset()
        while it.has_next():
            ds = it.next()
            mds = self._to_mds(ds)
            out = self.output(*mds.features)
            outs = out if isinstance(out, list) else [out]
            ev.eval(mds.labels[0], np.asarray(outs[0]),
                    mask=None if mds.labels_masks is None else mds.labels_masks[0])
        from deeplearning4j_tpu.telemetry import get_default as _telemetry

        _telemetry().eval(ev, top_n=top_n)  # no-op unless telemetry is on
        return ev

    # ------------------------------------------------- streaming RNN inference
    def rnn_clear_previous_state(self):
        self._rnn_carries = None

    def rnn_time_step(self, *inputs):
        """Stateful single/multi-step inference over the DAG (reference
        ComputationGraph.rnnTimeStep). Each input: [batch, n_in] (one step)
        or [batch, time, n_in] — ranks must agree across inputs; recurrent-
        vertex carries persist between calls so long sequences stream in
        chunks. Raises for layers that cannot stream causally (bidirectional
        LSTM, self-attention — the reference throws
        UnsupportedOperationException for these)."""
        if getattr(self, "_pp_plan", None) is not None:
            raise ValueError("rnn_time_step is not supported while a "
                             "pipeline mesh is active — set_mesh(None) first")
        for name, v in self.layer_vertices.items():
            if isinstance(v.layer, BaseRecurrentLayer) and not hasattr(
                    self.impls[name], "initial_carry"):
                raise ValueError(
                    f"rnn_time_step: layer '{name}' "
                    f"({type(v.layer).__name__}) cannot stream causally — it "
                    "needs the full sequence (reference throws "
                    "UnsupportedOperationException)")
        cdtype = self.compute_dtype
        ranks = {jnp.asarray(x).ndim for x in inputs}
        if len(ranks) > 1:
            raise ValueError(
                f"rnn_time_step: mixed input ranks {sorted(ranks)} — pass all "
                "inputs as [batch, n_in] or all as [batch, time, n_in]")
        single = ranks == {2}
        arrs = []
        for x in inputs:
            x = jnp.asarray(x)
            if jnp.issubdtype(x.dtype, jnp.floating):
                x = x.astype(cdtype)
            arrs.append(x[:, None, :] if single else x)
        carries = self._rnn_carries
        if carries is None:
            carries = self._initial_carries(arrs[0].shape[0])
        input_dict = dict(zip(self.conf.network_inputs, arrs))
        if self._rnn_jit is None:
            def _step(params, state, input_dict, carries):
                return self._forward(params, state, input_dict, train=False,
                                     rng=None, carries=carries)
            self._rnn_jit = jax.jit(_step)
        ys, _, new_carries = self._rnn_jit(self.params, self.state, input_dict,
                                           carries)
        self._rnn_carries = {**carries, **new_carries}
        outs = [y[:, -1, :] if single and y.ndim == 3 else y for y in ys]
        return outs[0] if len(outs) == 1 else outs

    def rnn_activate_using_stored_state(self, *inputs,
                                        training: bool = False,
                                        store_last_for_tbptt: bool = False):
        """Full-sequence activations from the STORED streaming state
        (reference rnnActivateUsingStoredState semantics on the graph):
        recurrent vertices resume from the rnn_time_step state; the stored
        state only advances when store_last_for_tbptt=True. Returns the
        acts dict {vertex_name: activation}."""
        cdtype = self.compute_dtype
        arrs = []
        for x in inputs:
            x = jnp.asarray(x)
            if jnp.issubdtype(x.dtype, jnp.floating):
                x = x.astype(cdtype)
            if x.ndim != 3:
                raise ValueError("rnn_activate_using_stored_state expects "
                                 f"[batch, time, n_in]; got {x.shape}")
            arrs.append(x)
        carries = self._rnn_carries
        if carries is None:
            carries = self._initial_carries(arrs[0].shape[0])
        input_dict = dict(zip(self.conf.network_inputs, arrs))
        acts, _, new_carries = self._forward(
            self.params, self.state, input_dict,
            train=training, rng=self._next_rng() if training else None,
            collect=True, carries=carries)
        if store_last_for_tbptt:
            self._rnn_carries = {**carries, **new_carries}
        return acts

    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(self.params))

    def params_flat(self):
        leaves = jax.tree.leaves(self._canonical_params())
        return (np.concatenate([np.asarray(l).ravel() for l in leaves])
                if leaves else np.zeros(0))

    def set_params_flat(self, flat):
        canonical = self._canonical_params()
        leaves, treedef = jax.tree.flatten(canonical)
        out, off = [], 0
        for l in leaves:
            n = int(np.prod(l.shape))
            out.append(jnp.asarray(flat[off:off + n], l.dtype).reshape(l.shape))
            off += n
        params = jax.tree.unflatten(treedef, out)
        if getattr(self, "_pp_plan", None) is not None:
            self.params = self._pp_plan.to_pipelined(params)
        else:
            self.params = params
