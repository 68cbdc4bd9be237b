"""MultiLayerNetwork — the sequential-stack container.

Reference: nn/multilayer/MultiLayerNetwork.java (2,367 LoC): init:349,
fit(DataSetIterator):1011, pretrain:165, feedForward:614, backprop:1065,
computeGradientAndScore:1781, doTruncatedBPTT, rnnTimeStep:2147,
evaluate:2311, output:1500-1582, setLayerMaskArrays.

TPU-native redesign:
- params/state/opt_state are pytrees keyed by layer name (the reference's
  flat 1×N view vector with per-layer views is replaced by the pytree
  idiom; `params_flat`/`set_params_flat` provide the flat view for
  parameter-averaging parity and serialization)
- forward/backward/update is ONE jitted donated XLA computation
  (SURVEY.md §3.1 TPU mapping); jax.grad replaces calcBackpropGradients
- fit rides the async input pipeline (data/pipeline.iter_prefetched):
  batch conversion + device placement run on a prefetch thread feeding
  a bounded queue of device-resident batches, replacing the reference's
  AsyncDataSetIterator wrap (MultiLayerNetwork.fit:1014) with
  conversion overlap, not just host-IO overlap
- TBPTT runs the jitted step per truncation segment with explicit RNN
  carries (stop-gradient between segments)
- rnnTimeStep keeps a carry pytree on the host between calls
"""

from __future__ import annotations

import copy
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu.datasets.api import DataSet
from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
from deeplearning4j_tpu.nn.conf.enums import BackpropType, OptimizationAlgorithm
from deeplearning4j_tpu.nn.conf.layers import (
    BaseOutputLayer,
    BaseRecurrentLayer,
    RnnOutputLayer,
    validate_layer_names,
)
from deeplearning4j_tpu.nn.conf.neural_net_configuration import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers import get_impl, l1_l2_penalty
from deeplearning4j_tpu.nn.layers.base import pop_aux_losses
from deeplearning4j_tpu.nn.training import make_train_step, tree_cast
from deeplearning4j_tpu.nn.updater import build_optimizer

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16, "float64": jnp.float64,
           "float16": jnp.float16}


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layer_confs = list(conf.layers)
        self.layer_names = [
            lc.name if lc.name else f"layer_{i}" for i, lc in enumerate(self.layer_confs)
        ]
        self.impls = [get_impl(lc) for lc in self.layer_confs]
        self.params = None
        self.state = None
        self.opt_state = None
        self.tx = None
        self.listeners = []
        self.iteration_count = 0
        self.epoch_count = 0
        self._train_step = None
        self._scan_fit = None
        self._output_jit = None
        self._score_examples_jit = {}
        self._rng = None
        self._rnn_carries = None  # streaming inference state
        self._rnn_jit = None
        self._mesh = None
        self._zero1 = False
        self._multiprocess = False
        self.score_value = float("nan")

    # ------------------------------------------------------------------ init
    @property
    def param_dtype(self):
        return _DTYPES[self.conf.conf.param_dtype]

    @property
    def compute_dtype(self):
        return _DTYPES[self.conf.conf.dtype]

    def init(self, seed: Optional[int] = None):
        """Allocate parameters (reference init:349)."""
        g = self.conf.conf
        key = jax.random.PRNGKey(g.seed if seed is None else seed)
        self._rng = jax.random.fold_in(key, 1)
        params, state = {}, {}
        for lc in self.layer_confs:
            validate_layer_names(lc)
        keys = jax.random.split(key, max(len(self.layer_confs), 1))
        for name, lc, impl, k in zip(self.layer_names, self.layer_confs, self.impls, keys):
            p, s = impl.init(lc, k, self.param_dtype)
            params[name] = p
            state[name] = s
        self.params = params
        self.state = state
        self.tx = build_optimizer(g, dict(zip(self.layer_names, self.layer_confs)),
                                  params=params)
        self.opt_state = self.tx.init(params)
        return self

    def set_optimizer(self, tx: optax.GradientTransformation):
        """Custom updater hook (reference Updater.CUSTOM)."""
        self.tx = tx
        self.opt_state = tx.init(self.params)
        self._train_step = None
        self._scan_fit = None
        self._output_jit = None
        self._score_examples_jit = {}

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)

    def set_mesh(self, mesh, zero1: bool = False, axes=None,
                 n_microbatches=None, tp_rules=None, overlap=None):
        """Enable distributed training over a jax.sharding.Mesh (replaces
        the Spark parameter-averaging master). axes maps parallelism roles
        ("data"/"model"/"expert"; "pipe" needs the graph container) to mesh
        axis names — see parallel/placement.py. Without axes: pure DP over
        a 'data' axis. overlap: True / bucket bytes / a BucketPlan —
        bucketed gradient allreduce with compute/communication overlap
        (parallel/overlap.py; pure DP only, composes with zero1)."""
        from deeplearning4j_tpu.parallel.placement import configure_mesh

        return configure_mesh(self, mesh, zero1=zero1, axes=axes,
                              n_microbatches=n_microbatches,
                              tp_rules=tp_rules, overlap=overlap)

    # --------------------------------------------------------------- forward
    def _next_rng(self):
        self._rng, k = jax.random.split(self._rng)
        return k

    def _forward(self, params, state, x, *, train, rng, mask=None,
                 carries=None, collect=False, to_layer=None):
        """Walk the stack (reference feedForwardToLayer:637). Returns
        (activations list if collect else final activation, new_state,
        new_carries)."""
        g = self.conf.conf
        cdtype = self.compute_dtype
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
            x = jnp.asarray(x, cdtype)
        acts = []
        new_state = {}
        new_carries = {}
        n_layers = len(self.layer_confs) if to_layer is None else to_layer
        rngs = (jax.random.split(rng, max(n_layers, 1)) if rng is not None
                else [None] * n_layers)
        for i in range(n_layers):
            name, lc, impl = self.layer_names[i], self.layer_confs[i], self.impls[i]
            proc = self.conf.get_preprocessor(i)
            if proc is not None:
                x = proc.pre_process(x)
            p = params.get(name, {})
            if cdtype != self.param_dtype:
                p = tree_cast(p, cdtype)
            want_carry = (carries is not None and isinstance(lc, BaseRecurrentLayer)
                          and hasattr(impl, "initial_carry"))

            def run(p_, s_, x_, _impl=impl, _lc=lc, _rng=rngs[i], _wc=want_carry,
                    _carry=(carries.get(name) if want_carry else None)):
                kw = {"initial_carry": _carry, "return_carry": True} if _wc else {}
                return _impl.apply(_lc, p_, s_, x_, train=train, rng=_rng,
                                   mask=mask, **kw)

            if g.remat:
                run = jax.checkpoint(run)
            out = run(p, state.get(name, {}), x)
            if want_carry:
                x, s, carry = out
                new_carries[name] = carry
            else:
                x, s = out
            new_state[name] = s
            if collect:
                acts.append(x)
        # passthrough state for layers beyond to_layer
        for j in range(n_layers, len(self.layer_confs)):
            new_state[self.layer_names[j]] = state.get(self.layer_names[j], {})
        if collect:
            return acts, new_state, new_carries
        return x, new_state, new_carries

    def _loss(self, params, state, rng, batch, train=True):
        """Forward to the output layer's loss + L1/L2 (reference
        computeGradientAndScore:1781). Returns (loss, (new_state, extras));
        extras holds RNN carries when batch supplies `carries` (TBPTT)."""
        x = batch["features"]
        labels = batch["labels"]
        fmask = batch.get("features_mask")
        lmask = batch.get("labels_mask")
        carries = batch.get("carries")
        out_conf = self.layer_confs[-1]
        if not isinstance(out_conf, BaseOutputLayer):
            raise ValueError("Last layer must be an OutputLayer to compute a score")
        n = len(self.layer_confs)
        k_body, k_out = (jax.random.split(rng) if rng is not None else (None, None))
        h, new_state, new_carries = self._forward(
            params, state, x, train=train, rng=k_body, mask=fmask,
            carries=carries, to_layer=n - 1)
        proc = self.conf.get_preprocessor(n - 1)
        if proc is not None:
            h = proc.pre_process(h)
        out_impl = self.impls[-1]
        out_name = self.layer_names[-1]
        mask = lmask if lmask is not None else (
            fmask if isinstance(out_conf, RnnOutputLayer) else None)
        # cast output-layer params to the compute dtype like _forward does
        # for the body — a bf16 model must not stream its head weight in
        # f32 through the loss kernels (2x HBM traffic; profiled r3)
        p_out = params[out_name]
        cdtype = self.compute_dtype
        if cdtype != self.param_dtype:
            p_out = tree_cast(p_out, cdtype)
        loss = out_impl.loss(out_conf, p_out, h, labels, train=train,
                             rng=k_out, mask=mask)
        new_state[out_name] = state.get(out_name, {})
        # L1/L2 (reference BaseLayer calcL1/calcL2 summed into score)
        for name, lc in zip(self.layer_names, self.layer_confs):
            loss = loss + l1_l2_penalty(lc, params[name])
        aux, new_state = pop_aux_losses(new_state)
        if train:
            loss = loss + aux
        extras = {"carries": new_carries} if carries is not None else {}
        return loss, (new_state, extras)

    # ------------------------------------------------------------------- fit

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
        if self._train_step is None:
            confs = dict(zip(self.layer_names, self.layer_confs))
            axes = getattr(self, "_mesh_axes", None)
            self._train_step = make_train_step(
                self._loss, self.tx, confs, mesh=self._mesh,
                zero1_opt_state=(self.opt_state if self._zero1 else None),
                data_axis=(axes or {}).get("data", "data"),
                param_sharding=getattr(self, "_param_sh", None),
                overlap=getattr(self, "_overlap_plan", None))
        return self._train_step

    def _batch_dict(self, ds: DataSet):
        b = {"features": jnp.asarray(ds.features), "labels": jnp.asarray(ds.labels)}
        if ds.features_mask is not None:
            b["features_mask"] = jnp.asarray(ds.features_mask)
        if ds.labels_mask is not None:
            b["labels_mask"] = jnp.asarray(ds.labels_mask)
        return self._globalize_batch(b)

    def _globalize_batch(self, b):
        """Process-spanning mesh: this process's batch is its LOCAL shard
        of the global batch — assemble the global arrays (see
        distributed/global_mesh.py). Single-process meshes pass through
        (the jitted step's in_shardings place the batch)."""
        if not getattr(self, "_multiprocess", False):
            return b
        from deeplearning4j_tpu.distributed.global_mesh import globalize_batch

        axes = getattr(self, "_mesh_axes", None)
        return globalize_batch(b, self._mesh,
                               (axes or {}).get("data", "data"))

    def fit_scanned(self, data, labels=None, epochs: int = 1):
        """Whole-epoch fused training: every minibatch is staged on device
        and each epoch runs as ONE jitted lax.scan dispatch (the fit-path
        MFU mode — BASELINE's "end-to-end MFU via fit()"). Identical
        training math to fit() for plain SGD-family runs on uniform
        batches (rng streams differ, which only matters under dropout);
        unsupported config modes (solvers, TBPTT, pretraining,
        iterations>1) raise instead of silently diverging. Listeners fire
        once per epoch with the epoch-mean score. The staged batches must
        fit in device memory; fit() remains the streaming path.
        """
        from deeplearning4j_tpu.nn.training import fused_fit

        if self.params is None:
            self.init()
        if labels is not None:
            data = DataSet(data, labels)
        if isinstance(data, DataSet):
            data = ListDataSetIterator([data])
        return fused_fit(self, [self._batch_dict(ds) for ds in data], epochs)

    def resume_from(self, checkpoint_dir: str, step=None, *,
                    target_mesh=None, target_axes=None):
        """Elastic-recovery resume entry: restore params / optimizer
        state / step counter from an Orbax checkpoint directory
        (`util/orbax_checkpoint.ShardedCheckpointer` layout) INTO this
        net, keeping its runtime configuration (mesh, listeners).
        Returns the restored step (0 when the directory has no
        checkpoint yet: a cold start, not an error).

        target_mesh/target_axes route the restore through the portable
        resharding engine (`reshard/`): the checkpoint may have been
        written under ANY mesh shape / axis roles / process count, and
        each process reads only the shard slices its target placement
        needs. Without a target mesh, call before `set_mesh` when
        rejoining a re-formed fleet — the restored host values ride
        jit's replicated placement on the next `fit`."""
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
        """Train (reference fit(DataSetIterator):1011). Accepts a
        DataSetIterator, a DataSet, or (features, labels) arrays."""
        if self.params is None:
            self.init()
        if labels is not None:
            data = DataSet(data, labels)
        single_batch = isinstance(data, DataSet)
        if single_batch:
            # nothing to prefetch ahead of one batch: the pipeline's
            # synchronous fallback skips the per-call producer thread
            # (fit_steps — the elastic engine — lands here every step)
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
        step = self._get_train_step()
        tbptt_on = self.conf.backprop_type in (BackpropType.TRUNCATED_BPTT,
                                               "truncated_bptt")

        def convert(ds):
            # runs on the input-pipeline prefetch thread: host->device
            # conversion + process-spanning globalization overlap step
            # compute (data/pipeline.py). None = a TBPTT sequence, which
            # converts per truncation window on the step thread instead.
            if (tbptt_on and np.asarray(ds.features).ndim == 3
                    and ds.features.shape[1] > self.conf.tbptt_fwd_length):
                return None
            return self._batch_dict(ds)

        from deeplearning4j_tpu.data.pipeline import iter_prefetched
        from deeplearning4j_tpu.telemetry import get_default as _telemetry
        from deeplearning4j_tpu.telemetry.memstat import sampler_for_net

        # batch-boundary memory sampling: one modulo per iteration unless
        # DL4J_TPU_MEM_EVERY enables the cadence (memstat.on_step)
        mem = sampler_for_net(self, _telemetry())

        for _ in range(epochs):
            it.reset()
            for ds, batch in iter_prefetched(
                    it, convert, depth=0 if single_batch else None):
                if batch is None:
                    self._fit_tbptt(ds, step)
                    continue
                # reference runs `iterations` optimizer passes per minibatch
                # (StochasticGradientDescent.java:55)
                for _i in range(max(1, g.iterations)):
                    self.params, self.opt_state, self.state, loss, _ = step(
                        self.params, self.opt_state, self.state,
                        self._next_rng(), batch)
                    self.score_value = loss
                    self.iteration_count += 1
                    for lst in self.listeners:
                        lst.iteration_done(self, self.iteration_count)
                    mem.on_step(self.iteration_count)
            self.epoch_count += 1
        return self

    def _fit_with_solver(self, it, epochs: int):
        """Second-order / line-search training path (reference Solver.java
        dispatch on OptimizationAlgorithm — CG/LBFGS/line-GD run multiple
        line-searched passes per minibatch instead of the fused SGD step)."""
        from deeplearning4j_tpu.optimize.solvers import Solver

        tbptt = self.conf.backprop_type in (BackpropType.TRUNCATED_BPTT,
                                            "truncated_bptt")
        solver = Solver(self)

        def convert(ds):
            # mirror the SGD path's condition: TBPTT only engages for
            # 3-D sequences longer than the truncation window (the
            # pipeline re-raises this on the step thread)
            if (tbptt and np.asarray(ds.features).ndim == 3
                    and ds.features.shape[1] > self.conf.tbptt_fwd_length):
                raise ValueError(
                    "TRUNCATED_BPTT requires "
                    "STOCHASTIC_GRADIENT_DESCENT; second-order solvers "
                    "would differentiate the full sequence")
            return self._batch_dict(ds)

        from deeplearning4j_tpu.data.pipeline import iter_prefetched

        for _ in range(epochs):
            it.reset()
            for _ds, batch in iter_prefetched(it, convert):
                solver.optimize(batch, rng=self._next_rng())
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration_count)
            self.epoch_count += 1
        return self

    def _initial_carries(self, batch_size):
        """Zero carries for every recurrent layer (keyed by layer name)."""
        carries = {}
        for name, lc, impl in zip(self.layer_names, self.layer_confs, self.impls):
            if isinstance(lc, BaseRecurrentLayer) and hasattr(impl, "initial_carry"):
                carries[name] = impl.initial_carry(lc, batch_size, self.compute_dtype)
        return carries

    def _fit_tbptt(self, ds: DataSet, step):
        """Truncated BPTT (reference doTruncatedBPTT): slide a window of
        tbptt_fwd_length over time. RNN carries flow between segments
        (threaded through the jitted step as batch inputs/extras) but
        gradients do not — each segment is one jitted step, so the gradient
        truncation length equals the forward window (the reference's default
        fwdLen == backLen configuration)."""
        T = ds.features.shape[1]
        L = self.conf.tbptt_fwd_length
        if np.asarray(ds.labels).ndim != 3:
            raise ValueError(
                "TRUNCATED_BPTT needs time-distributed labels "
                f"[batch, time, n_out]; got shape {np.asarray(ds.labels).shape}. "
                "A per-sequence label would be counted once per segment "
                "against mid-sequence activations — train with standard BPTT "
                "instead")
        carries = self._initial_carries(ds.features.shape[0])
        for t0 in range(0, T, L):
            sub = DataSet(
                ds.features[:, t0:t0 + L],
                ds.labels[:, t0:t0 + L],
                None if ds.features_mask is None else ds.features_mask[:, t0:t0 + L],
                None if ds.labels_mask is None else ds.labels_mask[:, t0:t0 + L],
            )
            batch = self._batch_dict(sub)
            batch["carries"] = carries
            self.params, self.opt_state, self.state, loss, extras = step(
                self.params, self.opt_state, self.state, self._next_rng(), batch)
            carries = extras.get("carries", carries)
            self.score_value = loss
            self.iteration_count += 1
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration_count)

    # -------------------------------------------------------------- pretrain
    def pretrain(self, it, epochs: int = 1):
        """Greedy layer-wise pretraining (reference pretrain:165): for each
        pretrain layer (RBM/AutoEncoder), train on the activations of the
        stack below it."""
        if self.params is None:
            self.init()
        if isinstance(it, DataSet):
            it = ListDataSetIterator([it])
        for i, (name, lc, impl) in enumerate(
                zip(self.layer_names, self.layer_confs, self.impls)):
            if not lc.is_pretrain_layer():
                continue
            tx = build_optimizer(self.conf.conf, {name: lc})
            # the optimizer's per-layer lr/updater overrides key on layer
            # names, so feed it {name: params} — not the bare inner dict
            opt = tx.init({name: self.params[name]})

            @jax.jit
            def pstep(p, opt_state, rng, x, _impl=impl, _lc=lc, _tx=tx,
                      _name=name):
                loss, grads = jax.value_and_grad(
                    lambda q: _impl.pretrain_loss(_lc, q[_name], x, rng))(
                        {_name: p})
                updates, opt_state = _tx.update(grads, opt_state, {_name: p})
                return (optax.apply_updates({_name: p}, updates)[_name],
                        opt_state, loss)

            featurize = None
            if i > 0:
                # one compile per LAYER (to_layer=i is baked into the
                # traced program), reused across the whole epoch loop
                featurize = jax.jit(  # graftlint: disable=G005
                    lambda p, s, x: self._forward(p, s, x, train=False, rng=None,
                                                  to_layer=i)[0])
            for _ in range(epochs):
                it.reset()
                while it.has_next():
                    ds = it.next()
                    x = jnp.asarray(ds.features, self.compute_dtype)
                    if featurize is not None:
                        x = featurize(self.params, self.state, x)
                    p_new, opt, loss = pstep(self.params[name], opt, self._next_rng(), x)
                    self.params = dict(self.params, **{name: p_new})
                    self.score_value = loss
        return self

    # ------------------------------------------------------------- inference
    def feed_forward(self, x, train: bool = False):
        """All layer activations (reference feedForward:614)."""
        acts, _, _ = self._forward(self.params, self.state, jnp.asarray(x),
                                   train=train, rng=self._next_rng() if train else None,
                                   collect=True)
        return acts

    def output(self, x, train: bool = False, mask=None):
        """Network output (reference output:1500-1582). With a mesh set,
        inference shards the batch over the 'data' axis — the distributed-
        evaluation path (reference EvaluateFlatMapFunction + merge)."""
        axes = getattr(self, "_mesh_axes", None)
        data_axis = (axes or {}).get("data", "data")
        has_data = (self._mesh is not None
                    and data_axis in self._mesh.axis_names)
        if self._output_jit is None:
            def _out(params, state, x, mask):
                y, _, _ = self._forward(params, state, x, train=False, rng=None,
                                        mask=mask)
                return y
            if has_data:
                from deeplearning4j_tpu.nn.training import mesh_shardings

                repl, data = mesh_shardings(self._mesh, data_axis)
                p_in = (None if getattr(self, "_param_sh", None) is not None
                        else repl)
                # process-spanning mesh: the result must come back fully
                # replicated (a data-sharded output spans non-addressable
                # devices and cannot be fetched host-side)
                out_sh = (repl if getattr(self, "_multiprocess", False)
                          else data)
                self._output_jit = jax.jit(
                    _out, in_shardings=(p_in, repl, data, None),
                    out_shardings=out_sh)
            else:
                self._output_jit = jax.jit(_out)
        if train:
            y, _, _ = self._forward(self.params, self.state, jnp.asarray(x),
                                    train=True, rng=self._next_rng(), mask=mask)
            return y
        x = jnp.asarray(x)
        if has_data:
            # sharded inference needs batch % mesh == 0: pad-and-slice
            # (EvaluateFlatMapFunction handles uneven shards semantically)
            from deeplearning4j_tpu.nn.training import pad_batch_to_multiple

            B = x.shape[0]
            bundle = (x,) if mask is None else (x, mask)
            bundle, pad = pad_batch_to_multiple(bundle,
                                                self._mesh.shape[data_axis])
            x = bundle[0]
            mask = bundle[1] if mask is not None else None
            if getattr(self, "_multiprocess", False):
                # inference takes the FULL batch on every process (unlike
                # fit's per-process shards): globalize it data-sharded
                from deeplearning4j_tpu.distributed.global_mesh import (
                    globalize_full,
                )

                x = globalize_full(x, self._mesh, data_axis)
                if mask is not None:
                    mask = globalize_full(mask, self._mesh, data_axis)
            if pad:
                return self._output_jit(self.params, self.state, x, mask)[:B]
        return self._output_jit(self.params, self.state, x, mask)

    def predict(self, x):
        """Class indices (reference predict)."""
        return np.asarray(jnp.argmax(self.output(x), axis=-1))

    def inference_fn(self):
        """A pure ``(params, state, x, mask=None) -> y`` inference-mode
        forward for external jit owners — the serving engine
        (serving/engine.py) wraps this per replica so IT controls the
        compile cache (one trace per padding bucket, zero retraces after
        warmup), which `output()`'s internal jit cannot promise. No rng,
        no state mutation: inference forwards are row-independent, the
        property the serving padding proof relies on."""
        def fwd(params, state, x, mask=None):
            y, _, _ = self._forward(params, state, x, train=False,
                                    rng=None, mask=mask)
            return y
        return fwd

    def incremental_decode_fn(self, kv_dtype: str = "f32",
                              page_size: int = 16):
        """A pure jitted-step body ``(params, state, cache, token, pos)
        -> (probs, cache)`` — autoregressive decode with the KV cache as
        explicit threaded state (nn/decode.py; same contract as
        ComputationGraph.incremental_decode_fn). This is the
        productionized rnnTimeStep:2147 for attention stacks, which
        `rnn_time_step` rejects as unable to stream causally.
        kv_dtype="int8" reads/writes the quantized paged cache."""
        from deeplearning4j_tpu.nn.decode import make_decode_fn

        return make_decode_fn(self, kv_dtype, page_size)

    def prefill_fn(self, kv_dtype: str = "f32", page_size: int = 16):
        """The chunked-prefill twin of `incremental_decode_fn`:
        ``(params, state, cache, tokens, kmask, rows, start, last_idx)
        -> (probs_last, cache)`` — see nn/decode.make_prefill_fn."""
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

    def score(self, dataset: DataSet = None, training: bool = False):
        """Loss on a dataset (reference score()). training=False uses
        inference-mode forward (BatchNorm running stats, no dropout)."""
        if dataset is None:
            return self.score_value
        batch = self._batch_dict(dataset)
        loss, _ = self._loss(self.params, self.state, None, batch, train=training)
        return float(loss)

    def score_examples(self, dataset, add_regularization: bool = False):
        """One score PER EXAMPLE [batch] — the ranking/anomaly-scoring API
        (reference spark ScoreExamplesFunction / scoreExamples:1969).
        Inference-mode forward; `add_regularization` adds the network's
        L1/L2 penalty to every example's score like the reference's
        addRegularizationTerms. With a mesh set, the batch shards over the
        'data' axis like output()."""
        batch = self._batch_dict(dataset)
        key = bool(add_regularization)
        if key not in self._score_examples_jit:
            def _scores(params, state, batch):
                x = batch["features"]
                fmask = batch.get("features_mask")
                lmask = batch.get("labels_mask")
                out_conf = self.layer_confs[-1]
                if not isinstance(out_conf, BaseOutputLayer):
                    raise ValueError(
                        "Last layer must be an OutputLayer to score")
                n = len(self.layer_confs)
                h, _, _ = self._forward(params, state, x, train=False,
                                        rng=None, mask=fmask,
                                        to_layer=n - 1)
                proc = self.conf.get_preprocessor(n - 1)
                if proc is not None:
                    h = proc.pre_process(h)
                mask = lmask if lmask is not None else (
                    fmask if isinstance(out_conf, RnnOutputLayer) else None)
                p_out = params[self.layer_names[-1]]
                if self.compute_dtype != self.param_dtype:
                    p_out = tree_cast(p_out, self.compute_dtype)
                per = self.impls[-1].loss(
                    out_conf, p_out, h, batch["labels"], train=False,
                    rng=None, mask=mask, per_example=True)
                if add_regularization:
                    reg = 0.0
                    for name, lc in zip(self.layer_names, self.layer_confs):
                        reg = reg + l1_l2_penalty(lc, params[name])
                    per = per + reg
                return per

            axes = getattr(self, "_mesh_axes", None)
            data_axis = (axes or {}).get("data", "data")
            if (self._mesh is not None
                    and data_axis in self._mesh.axis_names):
                from deeplearning4j_tpu.nn.training import mesh_shardings

                repl, data = mesh_shardings(self._mesh, data_axis)
                p_in = (None if getattr(self, "_param_sh", None) is not None
                        else repl)
                batch_sh = jax.tree.map(lambda _: data, batch)
                self._score_examples_jit[key] = jax.jit(
                    _scores, in_shardings=(p_in, repl, batch_sh),
                    out_shardings=data)
            else:
                self._score_examples_jit[key] = jax.jit(_scores)
        axes = getattr(self, "_mesh_axes", None)
        data_axis = (axes or {}).get("data", "data")
        if self._mesh is not None and data_axis in self._mesh.axis_names:
            from deeplearning4j_tpu.nn.training import pad_batch_to_multiple

            B = np.asarray(dataset.features).shape[0]
            batch, pad = pad_batch_to_multiple(
                batch, self._mesh.shape[data_axis])
            per = self._score_examples_jit[key](self.params, self.state,
                                                batch)
            return np.asarray(per)[:B]
        return np.asarray(
            self._score_examples_jit[key](self.params, self.state, batch))

    def evaluate(self, it, top_n: int = 1):
        """Classification evaluation (reference evaluate:2311); top_n > 1
        additionally tracks top-N accuracy (Evaluation.topNAccuracy)."""
        from deeplearning4j_tpu.eval.evaluation import Evaluation

        ev = Evaluation(top_n=top_n)
        if isinstance(it, DataSet):
            it = ListDataSetIterator([it])
        it.reset()
        while it.has_next():
            ds = it.next()
            out = self.output(ds.features)
            ev.eval(ds.labels, np.asarray(out), mask=ds.labels_mask)
        from deeplearning4j_tpu.telemetry import get_default as _telemetry

        _telemetry().eval(ev, top_n=top_n)  # no-op unless telemetry is on
        return ev

    # ------------------------------------------------- streaming RNN inference
    def rnn_clear_previous_state(self):
        self._rnn_carries = None

    def rnn_time_step(self, x):
        """Stateful single/multi-step inference (reference rnnTimeStep:2147).
        x: [batch, n_in] (one step) or [batch, time, n_in]. Raises for layers
        that cannot stream causally (bidirectional LSTM, self-attention —
        the reference throws UnsupportedOperationException)."""
        for name, lc, impl in zip(self.layer_names, self.layer_confs, self.impls):
            if isinstance(lc, BaseRecurrentLayer) and not hasattr(
                    impl, "initial_carry"):
                raise ValueError(
                    f"rnn_time_step: layer '{name}' ({type(lc).__name__}) "
                    "cannot stream causally — it needs the full sequence "
                    "(reference throws UnsupportedOperationException)")
        x = jnp.asarray(x, self.compute_dtype)
        single = x.ndim == 2
        if single:
            x = x[:, None, :]
        carries = self._rnn_carries
        if carries is None:
            carries = self._initial_carries(x.shape[0])
        if self._rnn_jit is None:
            def _step(params, state, x, carries):
                return self._forward(params, state, x, train=False, rng=None,
                                     carries=carries)
            self._rnn_jit = jax.jit(_step)
        y, _, new_carries = self._rnn_jit(self.params, self.state, x, carries)
        self._rnn_carries = {**carries, **new_carries}
        return y[:, -1, :] if single and y.ndim == 3 else y

    def rnn_activate_using_stored_state(self, x, *, training: bool = False,
                                        store_last_for_tbptt: bool = False):
        """Full-sequence activations starting from the STORED streaming
        state (reference rnnActivateUsingStoredState,
        MultiLayerNetwork.java:2203): unlike feed_forward, recurrent layers
        resume from the rnn_time_step/TBPTT state map instead of zeros;
        unlike rnn_time_step, the stored state is NOT advanced unless
        store_last_for_tbptt=True. Returns the list of layer activations
        (one per layer, like feed_forward)."""
        x = jnp.asarray(x, self.compute_dtype)
        if x.ndim != 3:
            raise ValueError("rnn_activate_using_stored_state expects "
                             f"[batch, time, n_in]; got {x.shape}")
        carries = self._rnn_carries
        if carries is None:
            carries = self._initial_carries(x.shape[0])
        acts, _, new_carries = self._forward(
            self.params, self.state, x,
            train=training, rng=self._next_rng() if training else None,
            carries=carries, collect=True)
        if store_last_for_tbptt:
            self._rnn_carries = {**carries, **new_carries}
        return acts

    # -------------------------------------------------------- params plumbing
    def num_params(self) -> int:
        return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(self.params))

    def params_flat(self) -> np.ndarray:
        """Flat parameter vector (reference params():deterministic layer order)
        for averaging/serialization parity."""
        leaves = jax.tree.leaves(self.params)
        return np.concatenate([np.asarray(l).ravel() for l in leaves]) if leaves else np.zeros(0)

    def set_params_flat(self, flat: np.ndarray):
        leaves, treedef = jax.tree.flatten(self.params)
        out, off = [], 0
        for l in leaves:
            n = int(np.prod(l.shape))
            out.append(jnp.asarray(flat[off:off + n], l.dtype).reshape(l.shape))
            off += n
        self.params = jax.tree.unflatten(treedef, out)

    def clone(self) -> "MultiLayerNetwork":
        net = MultiLayerNetwork(copy.deepcopy(self.conf))
        net.init()
        if self.params is not None:
            net.params = jax.tree.map(jnp.copy, self.params)
            net.state = jax.tree.map(jnp.copy, self.state)
            net.opt_state = self.opt_state
        return net
