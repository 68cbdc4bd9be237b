"""Container-integrated pipeline parallelism (PP).

Builds pipeline stages from the REAL network conf — the builder-API
ComputationGraph (reference ComputationGraphConfiguration.GraphBuilder,
nn/conf/ComputationGraphConfiguration.java:446) — instead of requiring a
hand-stacked homogeneous stage_fn (the retired r2 demo
`pipeline_parallel.py` — its schedule ideas live in the scan body below;
ARCHITECTURE.md §The five parallel axes has the history):

- **Partitioning**: the DAG's topological order is scanned for single-value
  cuts (positions where exactly one activation is live); the longest run of
  structurally identical cut-to-cut segments (fingerprinted on vertex
  types, configs, wiring, and param shapes) becomes the pipelined body —
  e.g. the n_layers pre-norm transformer blocks. Everything before the run
  (embedding, positional encoding) is the heterogeneous PRE segment;
  everything after (final LN, LM head + loss) is the POST segment.

- **Schedule**: a GPipe microbatch schedule as one `lax.scan` of per-tick
  stage compute inside a `shard_map` that is MANUAL over the 'pipe' mesh
  axis ONLY (`axis_names={pipe}`): 'data' and 'model'/'expert' axes stay
  AUTO, so batch sharding and Megatron TP / MoE EP placements propagate
  through the per-stage compute via GSPMD — dp x tp x pp composes inside
  ONE jitted train step, with XLA inserting the collectives.

- **Heterogeneous ends without SPMD waste**: the PRE segment runs
  replicated-over-pipe at each injection tick (an embedding gather —
  negligible FLOPs); the POST segment + loss runs ONCE per microbatch,
  balanced round-robin across pipe devices via a second "done lane" ring:
  the last stage injects finished activations into the lane, each device
  captures the microbatches assigned to it (j % S == device), and computes
  the head loss for its share after the scan. Head FLOPs are never
  duplicated per stage, and no device stores more than M/S microbatches of
  final activations (the r2 review's full-batch-memory critique).

- **Memory layout**: stage parameters live STACKED on a leading [S] axis
  sharded over 'pipe' (each device holds one stage's blocks), composed
  with the TP/EP dim rules on the remaining axes. The token/label
  microbatch stream is replicated over pipe — int32 tokens are ~d_model x
  smaller than activations, so only activations ride the rings.

Differentiability is free: `ppermute`/`scan`/`dynamic_update_slice` all
have transpose rules, so `jax.grad` of the scheduled loss yields the
reverse (backward) pipeline schedule automatically.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.util.compat import pcast_varying, shard_map
from deeplearning4j_tpu.nn.conf.graph_conf import LayerVertexConf
from deeplearning4j_tpu.nn.conf.layers import BaseOutputLayer
from deeplearning4j_tpu.nn.layers import l1_l2_penalty


def _chain_cuts(conf):
    """Positions in topo order after which exactly ONE activation is live
    (single-edge cuts of the DAG — valid pipeline stage boundaries)."""
    topo = [n for n in conf.topological_order()
            if n not in conf.network_inputs]
    pos = {n: i for i, n in enumerate(topo)}
    INF = len(topo) + 1
    # last position consuming each value; network outputs live to the end
    last_use = {}
    for n in topo:
        for src in conf.vertex_inputs[n]:
            last_use[src] = max(last_use.get(src, -1), pos[n])
    for out in conf.network_outputs:
        last_use[out] = INF
    cuts = []
    for i, n in enumerate(topo):
        live = [v for v in topo[:i + 1] if last_use.get(v, -1) > i]
        live += [v for v in conf.network_inputs if last_use.get(v, -1) > i]
        if live == [n]:
            cuts.append(i)
    return topo, cuts


def _conf_repr(obj):
    """Structural repr of a (possibly nested) vertex/layer config with
    identity fields ('name') stripped — two blocks differing only in layer
    names must fingerprint equal."""
    import dataclasses

    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        fields = ", ".join(
            f"{f.name}={_conf_repr(getattr(obj, f.name))}"
            for f in dataclasses.fields(obj) if f.name != "name")
        return f"{type(obj).__name__}({fields})"
    return repr(obj)


def _fingerprint(conf, params, seg, ext):
    """Structural identity of one cut-to-cut segment: vertex kinds +
    configs + segment-local wiring + param leaf shapes/dtypes. Segments
    with equal fingerprints can be stacked into pipeline stages."""
    pos = {n: j for j, n in enumerate(seg)}
    entries = []
    for n in seg:
        v = conf.vertices[n]
        wires = tuple(("ext",) if i == ext else ("local", pos[i])
                      for i in conf.vertex_inputs[n])
        p = params.get(n, {})
        shapes = tuple(
            (tuple(leaf.shape), str(leaf.dtype))
            for leaf in jax.tree.leaves(p))
        entries.append((type(v).__name__, _conf_repr(v), wires, shapes))
    return tuple(entries)


def _longest_periodic_run(fps):
    """Find (lo, n_units, period): the maximal-coverage run of consecutive
    REPEAT UNITS of `period` segments each with identical per-unit
    fingerprints (a transformer block may span several single-value cuts —
    e.g. an attention half and an FF half)."""
    n = len(fps)
    best = (0, 1, 1)  # lo, units, period
    for p in range(1, n // 2 + 1):
        for lo in range(0, n - p + 1):
            unit = tuple(fps[lo:lo + p])
            c = 1
            while (lo + (c + 1) * p <= n
                   and tuple(fps[lo + c * p:lo + (c + 1) * p]) == unit):
                c += 1
            if c > 1 and c * p > best[1] * best[2]:
                best = (lo, c, p)
    return best


class PipelinePlan:
    """Partition of a ComputationGraph into pre / S stages / post, with the
    param-tree restructuring between the canonical per-layer layout and the
    pipelined {pre, stages(stacked leaves), post} layout."""

    def __init__(self, net, n_stages: int):
        conf = net.conf
        if len(conf.network_inputs) != 1 or len(conf.network_outputs) != 1:
            raise ValueError(
                "pipeline parallelism supports single-input single-output "
                f"graphs; got {len(conf.network_inputs)} inputs / "
                f"{len(conf.network_outputs)} outputs")
        if conf.loop is not None:
            raise ValueError("pipeline parallelism cuts a graph into stages "
                             "once; a looped graph runs its span again")
        self.net = net
        self.S = n_stages
        self.input_name = conf.network_inputs[0]
        out_name = conf.network_outputs[0]
        out_v = conf.vertices[out_name]
        if not (isinstance(out_v, LayerVertexConf)
                and isinstance(out_v.layer, BaseOutputLayer)):
            raise ValueError("pipeline parallelism needs an output layer "
                             "as the single network output")
        if net.params is None:
            net.init()

        topo, cuts = _chain_cuts(conf)
        if not cuts:
            raise ValueError("graph has no single-activation cut points — "
                             "cannot partition into pipeline stages")
        # segments between consecutive cuts; segment i spans
        # (cuts[i-1], cuts[i]]; a leading segment before the first cut
        bounds = [-1] + cuts
        segs = [topo[bounds[i] + 1:bounds[i + 1] + 1]
                for i in range(len(bounds) - 1)]
        if bounds[-1] != len(topo) - 1:
            segs.append(topo[bounds[-1] + 1:])
        ext_of = [self.input_name] + [s[-1] for s in segs[:-1]]
        fps = [_fingerprint(conf, net.params, s, e)
               for s, e in zip(segs, ext_of)]
        # longest periodic run of identical repeat units = pipelined body
        lo, units, period = _longest_periodic_run(fps)
        if units % n_stages:
            raise ValueError(
                f"the {units} repeated blocks do not divide into "
                f"{n_stages} pipeline stages. The GPipe schedule runs one "
                "stage program over params stacked on a [S] axis, so the "
                "pipelined body must be a run of structurally IDENTICAL "
                "blocks (uniform transformer blocks qualify; VGG/ResNet-"
                "style conv stacks whose channel widths grow between "
                "stages do not — their per-stage compute differs, which "
                "would need a heterogeneous-stage schedule; shard those "
                "over the data axis instead)")
        per_stage = units // n_stages
        hi = lo + units * period
        body_segs = segs[lo:hi]
        seg_per_stage = per_stage * period
        self.stage_groups = [
            sum(body_segs[g * seg_per_stage:(g + 1) * seg_per_stage], [])
            for g in range(n_stages)]
        self.pre_names = sum(segs[:lo], [])
        post = sum(segs[hi:], [])
        if post and post[-1] == out_name:
            post = post[:-1]  # the loss layer runs via post_loss, not here
        self.post_names = post
        self.out_name = out_name
        self.out_vconf = out_v

        # external input value feeding each region
        self.pre_ext = self.input_name
        self.body_ext = (segs[lo - 1][-1] if lo > 0
                         else self.input_name)
        self.post_ext = body_segs[-1][-1] if body_segs else self.body_ext
        # consistency: the value feeding the loss layer
        loss_in = conf.vertex_inputs[out_name][0]
        self.loss_ext = loss_in

        self._steps_pre = self._build_steps(self.pre_names, self.pre_ext)
        self._steps_stage = self._build_steps(self.stage_groups[0],
                                              self.body_ext)
        self._steps_post = self._build_steps(self.post_names, self.post_ext)

        # per-layer (name, treedef, n_leaves) template for stage stacking,
        # in TOPO order within the group (stable across groups, unlike
        # lexicographic sort — 'blk10' < 'blk9' would misalign leaves)
        self.group_layers = [
            [n for n in g if isinstance(conf.vertices[n], LayerVertexConf)]
            for g in self.stage_groups]
        self.stage_template = self._make_template(net.params)
        self.pre_layers = [n for n in self.pre_names
                           if isinstance(conf.vertices[n], LayerVertexConf)]
        self.post_layers = [n for n in self.post_names
                            if isinstance(conf.vertices[n], LayerVertexConf)
                            ] + [out_name]
        # mutable layer state (BatchNorm running stats) threads the same
        # pipelined layout as params: per-stage state rides the tick scan
        # carry, updated only on real-microbatch ticks
        self.state_template = self._make_template(net.state, default={})
        self.has_state = bool(jax.tree.leaves(net.state))

        # leaf paths for TP/EP rule matching on stacked leaves, named by
        # the template (group-0) layer names
        self.stage_leaf_names = []
        for name, _, _ in self.stage_template:
            flat = jax.tree_util.tree_flatten_with_path(
                net.params[name])[0]
            for path, _leaf in flat:
                suffix = "/".join(str(getattr(k, "key", k)) for k in path)
                self.stage_leaf_names.append(f"{name}/{suffix}")

    # ------------------------------------------------------------ executors
    def _build_steps(self, names, ext_value):
        conf = self.net.conf
        pos = {n: j for j, n in enumerate(names)}
        steps = []
        for n in names:
            v = conf.vertices[n]
            refs = tuple(("ext", None) if i == ext_value else ("local", pos[i])
                         for i in conf.vertex_inputs[n])
            steps.append((n, v, refs))
        return steps

    def _make_template(self, tree, default=None):
        """Per-layer (name, treedef, n_leaves) stacking template for any
        per-layer-keyed pytree sharing the params' layer names."""
        tmpl = []
        for name in self.group_layers[0]:
            sub = tree[name] if default is None else tree.get(name, default)
            leaves, treedef = jax.tree.flatten(sub)
            tmpl.append((name, treedef, len(leaves)))
        return tmpl

    def _apply_steps(self, steps, params, state, x, *, train, rng,
                     mask=None):
        """Run a region's vertices on one activation. Returns (final
        activation, new_state). params/state: {template_layer_name:
        subtree}; `mask` is the [B, T] features mask threaded to every
        layer apply (the non-PP _forward contract)."""
        net = self.net
        cdtype = net.compute_dtype
        if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
            x = jnp.asarray(x, cdtype)
        acts = {}
        new_state = {}
        keys = (jax.random.split(rng, max(len(steps), 1))
                if rng is not None else [None] * len(steps))
        out = x
        for (n, v, refs), k in zip(steps, keys):
            ins = [x if r[0] == "ext" else acts[steps[r[1]][0]] for r in refs]
            if isinstance(v, LayerVertexConf):
                xi = ins[0]
                if v.preprocessor is not None:
                    xi = v.preprocessor.pre_process(xi)
                p = params.get(n, {})
                if cdtype != net.param_dtype:
                    p = jax.tree.map(
                        lambda a: a.astype(cdtype)
                        if jnp.issubdtype(a.dtype, jnp.floating) else a, p)
                y, s = net.impls[n].apply(
                    v.layer, p, state.get(n, {}), xi, train=train, rng=k,
                    mask=mask)
                new_state[n] = s
            else:
                y = net._vertex_forward(n, v, ins, params, {}, train, k,
                                        {}, acts)
            acts[n] = y
            out = y
        return out, new_state

    def pre_apply(self, pre_params, pre_state, x, *, train, rng, mask=None):
        if not self._steps_pre:
            x = jnp.asarray(x, self.net.compute_dtype) \
                if jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating) else x
            return x, dict(pre_state)
        return self._apply_steps(self._steps_pre, pre_params, pre_state, x,
                                 train=train, rng=rng, mask=mask)

    def stage_apply(self, stage_params, stage_state, x, *, train, rng,
                    mask=None):
        return self._apply_steps(self._steps_stage, stage_params,
                                 stage_state, x, train=train, rng=rng,
                                 mask=mask)

    def post_loss(self, post_params, post_state, h, labels, *, train, rng,
                  mask=None, feat_mask=None):
        """POST region + output-layer loss for a batch of finished
        activations. Returns (loss, new_post_state)."""
        net = self.net
        new_state = dict(post_state)
        if self._steps_post:
            h, new_state = self._apply_steps(
                self._steps_post, post_params, post_state, h, train=train,
                rng=rng, mask=feat_mask)
        v = self.out_vconf
        if v.preprocessor is not None:
            h = v.preprocessor.pre_process(h)
        # same compute-dtype policy as the non-PP loss path: the head
        # weight must not stream through the loss kernels in f32 for a
        # bf16 model
        p_out = post_params[self.out_name]
        if net.compute_dtype != net.param_dtype:
            from deeplearning4j_tpu.nn.training import tree_cast

            p_out = tree_cast(p_out, net.compute_dtype)
        loss = net.impls[self.out_name].loss(
            v.layer, p_out, h, labels, train=train, rng=rng, mask=mask)
        new_state.setdefault(self.out_name, post_state.get(self.out_name, {}))
        return loss, new_state

    # ----------------------------------------------------- tree restructure
    def _stage_local(self, tmpl, stacked, g=None):
        tree = {}
        i = 0
        for name, treedef, n in tmpl:
            leaves = [stacked[i + j] if g is None else stacked[i + j][g]
                      for j in range(n)]
            tree[name] = jax.tree.unflatten(treedef, leaves)
            i += n
        return tree

    def stage_local(self, stacked, g=None):
        """Rebuild {template_name: subtree} from a tuple of stacked leaves.
        g=None: leaves already have the stage axis stripped (inside
        shard_map); integer g: take stage g's slice (tracing-safe)."""
        return self._stage_local(self.stage_template, stacked, g)

    def stage_local_state(self, stacked, g=None):
        return self._stage_local(self.state_template, stacked, g)

    def _to_pipelined(self, tree, default=None):
        def get(n):
            return tree[n] if default is None else tree.get(n, default)

        pre = {n: get(n) for n in self.pre_layers}
        post = {n: get(n) for n in self.post_layers}
        per_group = []
        for g in self.group_layers:
            per_group.append([leaf for name in g
                              for leaf in jax.tree.leaves(get(name))])
        stages = tuple(jnp.stack([per_group[g][i]
                                  for g in range(self.S)])
                       for i in range(len(per_group[0])))
        return {"pre": pre, "stages": stages, "post": post}

    def _to_canonical(self, pp, tmpl):
        tree = {}
        tree.update(pp["pre"])
        tree.update(pp["post"])
        for g, names in enumerate(self.group_layers):
            local = self._stage_local(tmpl, pp["stages"], g=g)
            for tmpl_name, name in zip(self.group_layers[0], names):
                tree[name] = local[tmpl_name]
        return tree

    def to_pipelined(self, params):
        return self._to_pipelined(params)

    def to_canonical(self, pp):
        return self._to_canonical(pp, self.stage_template)

    def to_pipelined_state(self, state):
        return self._to_pipelined(state, default={})

    def to_canonical_state(self, pp_state, full_state=None):
        """Canonical per-layer state from the pipelined layout; layers
        outside the plan's regions (none today) fall back to full_state."""
        out = dict(full_state or {})
        out.update(self._to_canonical(pp_state, self.state_template))
        return out

    # --------------------------------------------------------- param place
    def placements(self, mesh: Mesh, axes: dict, rules):
        """Pipelined-tree pytree of NamedShardings: stacked stage leaves
        shard their leading [S] dim over the pipe axis composed with the
        TP/EP dim rules; pre/post follow the rules, replicated over pipe."""
        from deeplearning4j_tpu.parallel.tensor_parallel import sharding_for

        pipe = axes["pipe"]

        def leaf_spec(name):
            base = sharding_for(name, mesh, rules).spec
            return NamedSharding(mesh, P(pipe, *base))

        stage_sh = tuple(leaf_spec(n) for n in self.stage_leaf_names)

        def place_named(subtree, prefix):
            flat, treedef = jax.tree_util.tree_flatten_with_path(subtree)
            shs = []
            for path, _leaf in flat:
                suffix = "/".join(str(getattr(k, "key", k)) for k in path)
                shs.append(sharding_for(f"{prefix}{suffix}", mesh, rules))
            return jax.tree.unflatten(treedef, shs)

        src = self.net.params
        if isinstance(src, dict) and "stages" in src:
            src = self.to_canonical(src)
        pre_sh = {n: place_named(src[n], f"{n}/") for n in self.pre_layers}
        post_sh = {n: place_named(src[n], f"{n}/") for n in self.post_layers}
        return {"pre": pre_sh, "stages": stage_sh, "post": post_sh}


def check_pp_supported(net):
    """Configuration modes the PP step cannot honor raise up front."""
    from deeplearning4j_tpu.nn.conf.enums import (
        BackpropType,
        GradientNormalization,
        OptimizationAlgorithm,
    )

    g = net.conf.conf
    if str(g.optimization_algo) != str(
            OptimizationAlgorithm.STOCHASTIC_GRADIENT_DESCENT):
        raise ValueError("pipeline parallelism supports SGD-family "
                         "training only (no second-order solvers)")
    if str(net.conf.backprop_type) in (str(BackpropType.TRUNCATED_BPTT),
                                       "truncated_bptt"):
        raise ValueError("pipeline parallelism does not support TBPTT")
    for name, v in net.layer_vertices.items():
        lc = v.layer
        gn = getattr(lc, "gradient_normalization", None)
        if gn not in (None, GradientNormalization.NONE, "none"):
            raise ValueError(
                f"per-layer gradient normalization on '{name}' is not "
                "supported under pipeline parallelism")
        if (getattr(lc, "updater", None) not in (None, g.updater)
                or getattr(lc, "learning_rate", None) is not None):
            raise ValueError(
                f"per-layer updater/learning-rate override on '{name}' is "
                "not supported under pipeline parallelism (the optimizer "
                "runs on the stacked stage tree)")


def make_pp_train_step(net, plan: PipelinePlan, mesh: Mesh, axes: dict,
                       n_microbatches: int, rules):
    """Jitted train step over the pipelined param tree, standard container
    contract: step(pp_params, opt_state, state, rng, batch) ->
    (pp_params, opt_state, new_state, loss, {}).

    batch: {"features": (tokens [B, ...],), "labels": (labels [B, ...],)}
    with B divisible into n_microbatches x (data-axis multiple). [B, T]
    feature/label masks ride the (replicated) microbatch stream: the
    features mask reaches every stage's layer apply for its current
    microbatch, the labels mask reaches the head loss. Mutable layer state
    (BatchNorm running stats) threads the tick scan per stage, updated
    only on real-microbatch ticks; MoE router aux losses are accumulated
    across stages/microbatches and added to the training loss.
    """
    import optax

    from deeplearning4j_tpu.nn.layers.base import pop_aux_losses

    pipe = axes["pipe"]
    data = axes.get("data")
    seq = axes.get("seq")
    S, M = plan.S, n_microbatches
    if M % S:
        raise ValueError(f"{M} microbatches do not divide over {S} stages")
    k_slots = M // S
    T_total = M + 2 * S - 2
    ring = [(i, (i + 1) % S) for i in range(S)]
    # the data axis runs MANUAL alongside pipe (model/expert stay auto):
    # GSPMD's subgroup partitioner CHECK-fails composing an auto data
    # axis with expert-sharded stage leaves inside a manual-pipe region
    # (spmd_partitioner_util.cc:495 on a data x pipe x expert mesh), and
    # manual data costs nothing — the batch is embarrassingly parallel
    # and the loss/state combines below psum/pmean over both axes.
    # 'seq' rides the same mechanism as 'data': an embarrassingly-
    # parallel content axis run manual alongside pipe. Its shards hold
    # time blocks instead of batch rows — the SP-configured layers'
    # ring collectives (ring attention, offset posenc) bind against it
    # inside the stage bodies, and the loss/state combines below treat
    # it exactly like a second data axis (equal shards; the masked-mean
    # weights already make the combine exact for unequal valid counts).
    manual = ({pipe} | ({data} if data is not None else set())
              | ({seq} if seq is not None else set()))
    extra = tuple(a for a in (data, seq) if a is not None)
    dax = (pipe,) + extra
    d_only = extra

    def _pmean_floats(tree, ax):
        if not ax:
            return tree
        return jax.tree.map(
            lambda a: (lax.pmean(a, ax)
                       if jnp.issubdtype(a.dtype, jnp.floating) else a),
            tree)

    def _local_shard(arr_m, idx):
        """Device idx's share of a [M, mb, ...] stream: microbatches
        j = s*S + idx, flattened to [k_slots*mb, ...]."""
        r = arr_m.reshape((k_slots, S) + arr_m.shape[1:])
        local = lax.dynamic_index_in_dim(jnp.moveaxis(r, 1, 0), idx, 0,
                                         False)
        return local.reshape((k_slots * arr_m.shape[1],) + arr_m.shape[2:])

    def make_program(has_f, has_l):
        def program(pre_p, stages_p, post_p, stages_s, pre_s, post_s,
                    toks, labs, fm, lm, key):
            if seq is not None:
                # decorrelate dropout streams across time shards (the SP
                # step does the same): one key would mask identical
                # positions in every shard's local block
                key = jax.random.fold_in(key, lax.axis_index(seq))
            # local stage slice: shard_map strips the leading [S] axis to 1
            stage_p = plan.stage_local(tuple(a[0] for a in stages_p))
            stage_s0 = plan.stage_local_state(
                tuple(a[0] for a in stages_s))
            idx = lax.axis_index(pipe)
            u = (idx + 1) % S  # done-lane hops from the last stage to here

            probe, _ = plan.pre_apply(
                pre_p, pre_s, toks[0], train=True,
                rng=jax.random.fold_in(key, 0),
                mask=(fm[0] if has_f else None))
            zero = jnp.zeros_like(probe)

            def tick(carry, t):
                (inflight, done_lane, store, st_stage, st_pre,
                 aux_stage, aux_pre) = carry
                kt = jax.random.fold_in(key, t)
                # stage 0 injects microbatch t while t < M (the PRE
                # segment is an embedding-scale gather — computing it
                # replicated over pipe is far cheaper than ringing the
                # token stream)
                inject = jnp.where(t < M, t, 0)
                fm_in = (lax.dynamic_index_in_dim(fm, inject, 0, False)
                         if has_f else None)
                x0, pre_new = plan.pre_apply(
                    pre_p, st_pre,
                    lax.dynamic_index_in_dim(toks, inject, 0, False),
                    train=True, rng=jax.random.fold_in(kt, S), mask=fm_in)
                aux0, pre_new = pop_aux_losses(pre_new)
                real_pre = t < M
                st_pre = jax.tree.map(
                    lambda a, b: jnp.where(real_pre, a, b), pre_new, st_pre)
                aux_pre = aux_pre + jnp.where(real_pre, aux0, 0.0)
                x_in = jnp.where(idx == 0,
                                 jnp.where(t < M, x0, zero), inflight)
                # this device's stage processes microbatch t - idx
                jb = t - idx
                real = (jb >= 0) & (jb < M)
                fm_b = (lax.dynamic_index_in_dim(
                    fm, jnp.clip(jb, 0, M - 1), 0, False)
                    if has_f else None)
                y, st_new = plan.stage_apply(
                    stage_p, st_stage, x_in, train=True,
                    rng=jax.random.fold_in(kt, idx), mask=fm_b)
                auxb, st_new = pop_aux_losses(st_new)
                st_stage = jax.tree.map(
                    lambda a, b: jnp.where(real, a, b), st_new, st_stage)
                aux_stage = aux_stage + jnp.where(real, auxb, 0.0)
                # done lane: last stage injects its finished microbatch;
                # each device captures the ones assigned to it (j%S == idx)
                done_in = jnp.where(idx == S - 1, y, done_lane)
                j = t - (S - 1) - u
                cap = (j % S == idx) & (j >= 0) & (j < M)
                slot = jnp.clip(j // S, 0, k_slots - 1)
                store = jnp.where(cap, store.at[slot].set(done_in), store)
                done_lane = lax.ppermute(done_in, pipe, ring)
                inflight = lax.ppermute(y, pipe, ring)
                return (inflight, done_lane, store, st_stage, st_pre,
                        aux_stage, aux_pre), None

            store0 = jnp.zeros((k_slots,) + probe.shape, probe.dtype)
            carry0 = jax.tree.map(
                lambda a: pcast_varying(a, (pipe,)),
                (zero, zero, store0, stage_s0, pre_s,
                 jnp.zeros(()), jnp.zeros(())))
            (_, _, store, st_stage, st_pre, aux_stage, aux_pre), _ = (
                lax.scan(tick, carry0, jnp.arange(T_total)))

            # POST + loss once per microbatch, balanced over pipe devices:
            # device d holds microbatches j = s*S + d in slots s
            h = store.reshape((k_slots * toks.shape[1],) + store.shape[2:])
            labs_local = _local_shard(labs, idx)
            lm_local = _local_shard(lm, idx) if has_l else None
            fm_local = _local_shard(fm, idx) if has_f else None
            local, post_new = plan.post_loss(
                post_p, post_s, h, labs_local, train=True,
                rng=jax.random.fold_in(key, T_total), mask=lm_local,
                feat_mask=fm_local)
            auxp, post_new = pop_aux_losses(post_new)
            # post/pre/stage state shards differ per device (disjoint
            # microbatch/data shards) — pmean is the EMA combine;
            # non-float leaves keep the local copy (update counters,
            # identical across devices)
            post_new = _pmean_floats(post_new, dax)
            st_pre = _pmean_floats(st_pre, d_only)
            st_stage = _pmean_floats(st_stage, d_only)
            # equal shard sizes: global mean = pmean of local means. With a
            # labels mask the local losses are masked means (sum/valid), so
            # the exact global combine weights each shard by its valid
            # count: psum(local*w)/psum(w) == sum(per*m)/sum(m) over all.
            if has_l:
                w = jnp.maximum(jnp.sum(lm_local.astype(jnp.float32)), 1.0)
                data_loss = lax.psum(local * w, dax) / lax.psum(w, dax)
            else:
                data_loss = lax.pmean(local, dax)
            # aux accounting: each microbatch visits every stage device
            # once -> psum over pipe / M is the per-batch mean aux summed
            # over all blocks (then averaged over data shards); the
            # replicated-over-pipe PRE contributes via pmean. POST runs
            # ONCE per device over its k_slots-microbatch shard, so its
            # per-shard aux values combine as a pmean over pipe — /M
            # would underweight them by k_slots.
            aux_total = (lax.psum(aux_stage, pipe) / M
                         + lax.pmean(aux_pre, pipe) / M
                         + lax.pmean(auxp, pipe))
            if d_only:
                aux_total = lax.pmean(aux_total, d_only)
            loss = data_loss + aux_total
            # re-stack the local stage state with its [1] pipe axis for
            # the P(pipe) out_spec
            flat_stage_state = []
            for name, treedef, n in plan.state_template:
                flat_stage_state.extend(
                    jax.tree.leaves(st_stage[name]))
            st_stage_out = tuple(a[None] for a in flat_stage_state)
            return loss, st_stage_out, st_pre, post_new

        return program

    def run_sm(pp, pp_state, rng, toks_m, labs_m, fm_m, lm_m):
        has_f, has_l = fm_m is not None, lm_m is not None
        program = make_program(has_f, has_l)
        operands = (pp["pre"], pp["stages"], pp["post"],
                    pp_state["stages"], pp_state["pre"], pp_state["post"],
                    toks_m, labs_m,
                    fm_m if has_f else (), lm_m if has_l else (), rng)
        # stream leaves are [M, mb, T, ...]: microbatch x batch x time
        stream = P(None, data, seq) if seq is not None else (
            P(None, data) if data is not None else P())
        sm = shard_map(
            program, mesh=mesh,
            in_specs=(P(), P(pipe), P(), P(pipe), P(), P(),
                      stream, stream, stream if has_f else P(),
                      stream if has_l else P(), P()),
            out_specs=(P(), P(pipe), P(), P()),
            axis_names=manual, check_vma=False)
        loss, st_stage, st_pre, st_post = sm(*operands)
        new_pp_state = {"pre": st_pre, "stages": st_stage, "post": st_post}
        return loss, new_pp_state

    def loss_fn(pp, pp_state, rng, toks_m, labs_m, fm_m, lm_m):
        loss, new_pp_state = run_sm(pp, pp_state, rng, toks_m, labs_m,
                                    fm_m, lm_m)
        # L1/L2 penalties (stacked leaves sum over stages exactly like the
        # canonical per-block sum — all blocks share one conf)
        for name in plan.pre_layers + plan.post_layers:
            src = pp["pre"] if name in pp["pre"] else pp["post"]
            loss = loss + l1_l2_penalty(
                net.layer_vertices[name].layer, src[name])
        i = 0
        stage_tree = {}
        for tname, treedef, n in plan.stage_template:
            stage_tree[tname] = jax.tree.unflatten(
                treedef, list(pp["stages"][i:i + n]))
            i += n
        for tname in stage_tree:
            loss = loss + l1_l2_penalty(
                net.layer_vertices[tname].layer, stage_tree[tname])
        return loss, new_pp_state

    def _first_mask(ms):
        return next((m for m in (ms or []) if m is not None), None)

    def step(pp_params, opt_state, state, rng, batch):
        toks = batch["features"][0]
        labs = batch["labels"][0]
        fmask = _first_mask(batch.get("features_masks"))
        lmask = _first_mask(batch.get("labels_masks"))
        B = toks.shape[0]
        if B % M:
            raise ValueError(f"batch {B} not divisible into {M} microbatches")
        mb = B // M
        if data is not None and mb % mesh.shape[data]:
            raise ValueError(
                f"microbatch size {mb} not divisible over the "
                f"{mesh.shape[data]}-way data axis")
        if seq is not None and toks.shape[1] % mesh.shape[seq]:
            raise ValueError(
                f"sequence length {toks.shape[1]} not divisible over the "
                f"{mesh.shape[seq]}-way seq axis")

        def to_stream(a):
            if a is None:
                return None
            return a.reshape((M, mb) + a.shape[1:])

        toks_m, labs_m, fm_m, lm_m = map(to_stream,
                                         (toks, labs, fmask, lmask))
        pp_state = plan.to_pipelined_state(state)
        (loss, new_pp_state), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(pp_params, pp_state, rng, toks_m,
                                   labs_m, fm_m, lm_m)
        updates, opt_state = net.tx.update(grads, opt_state, pp_params)
        pp_params = optax.apply_updates(pp_params, updates)
        new_state = (plan.to_canonical_state(new_pp_state, state)
                     if plan.has_state else state)
        return pp_params, opt_state, new_state, loss, {}

    return jax.jit(step, donate_argnums=(0, 1))
