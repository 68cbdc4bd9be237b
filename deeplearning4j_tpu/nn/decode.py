"""Incremental autoregressive decode with an explicit KV cache: the
serving steps of a transformer stack, on both containers.

The module is a plan, a walk, a `CacheStep` and three entry functions.

* **The plan** (`_plan`): the net's layers and vertices in order, each
  checked to be servable one slice of a sequence at a time. A layer is
  servable where its impl says it maps every position by itself
  (`per_position`: dense, embedding, norms, output heads, activation,
  dropout), where it counts its own work (`apply_counted`: the dropless
  expert layer), or where it carries its own cached forward
  (`apply_cached`, causal where its conf has the word: attention and
  the positional encodings, nn/layers/attention.py,
  nn/layers/latent_attention.py, nn/layers/grouped_attention.py,
  nn/layers/power_retention.py and nn/layers/gated_deltanet.py).
  Elementwise, merge, scale and subset vertices ride along. Anything
  else (LSTMs, convolutions over time, bidirectional attention) raises
  when the plan is built, with the layer named. This module names no
  layer class: it asks the impls.
* **The walk** (`_walk`): the forward with inference semantics and the
  containers' dtype policy. It *calls* a layer's ``apply_cached(conf,
  params, x, entry, step)`` with the layer's entry of the cache and
  puts back what the layer returns; the layer's mathematics lives once,
  in nn/layers/.
* **`CacheStep`**: what such a layer is told of the step it is called
  in (`rows`, `positions`, `keep`, `chunk`, `live`) and the two
  operations on a key-value entry in the cache's stored format,
  ``step.write`` and ``step.attend``: bfloat16/float32 rows, or int8
  codes with per-(row, page, head) float32 scales. That decision lives
  here and in ops/decode_attention.py and nowhere else. A layer whose
  entry is a STATE (a running sum a slot, not a row a token: power
  retention, the gated delta rule and its convolution's window) uses
  neither; it reads the step itself, because a sum
  forgives nothing a row does: a row whose first position is 0 starts a
  sequence and its state is zeroed first, a token with `keep` 0 adds and
  decays nothing, a row not `live` keeps its state bit for bit. A layer
  whose entry is a RING of rows (grouped attention with a window: its
  own head-major arrays, position p at row p % rows) reads the step
  likewise: a row not `live` writes nothing (the scratch position would
  land on a row a tenant needs), a chunk attends the ring as it found
  it and writes after, `keep` 0 writes nothing, and the position a ring
  row holds is arithmetic on `positions`, so a new tenant needs no
  reset; it walks its entry through ops/decode_attention.py itself and
  writes a chunk's rows as one run a row (``step.write_run``).
* **The loop** (a graph's `LoopConf`: a span of vertices run `times`
  times a token with one set of weights, `nn/graph.py`): the walk runs
  the span as ONE `lax.scan` over the pass, its body traced once, the
  span's weights loop invariants and its layers' cache entries carried
  and written in place. Every cached layer inside the span keeps one
  block of rows a pass: its entry has a PASS AXIS after the batch axis
  (`cache_specs`), and the `CacheStep` it is handed says which pass it
  is in (`pass_index`); only a layer whose impl says `passes`
  (grouped attention) is served there. What the body's layers count is
  counted a pass at a time and merged as if each pass were more layers;
  the loop counts `loop_passes` itself (the passes run, summed over the
  step's live rows). The loop's own operations lie in the region
  `loop`, the body's layers in their own. A loop of 1 is no loop.
* **The entry functions** build the `CacheStep`, walk, and pick the
  output rows. ``make_decode_fn``: ``(params, state, cache, token,
  pos[, live]) -> (probs, cache)``, one token a cache row, positions
  per row (continuous batching mixes rows at different depths).
  ``make_verify_fn``: the same for a window of K tokens a row, the
  speculative verification step (serving/speculative.py accepts on the
  host); the decode step is this at K = 1. It raises, with the layer
  named, for a net with a layer whose step cannot be unwound (the impl
  says `rewindable(conf)` False: a rejected draft's share of a state
  stays, its rows in a ring have overwritten rows the accepted position
  still sees).
  ``make_prefill_fn``: ``(params, state, cache, tokens, kmask, rows,
  start, last_idx) -> (probs_last, cache)``, a bucket-shaped chunk of a
  prompt into the cache rows `rows` from position `start` on, so that a
  long prompt prefills in several calls with decode steps between them.

``init_cache(net, batch, capacity)`` allocates and ``cache_specs`` lists
the cache, {layer: {array: [batch, ...]}}, from each layer's own
`cache_arrays`; the serving allocator bills the same spec. All of them
take ``kv_dtype`` ("f32": rows as the net computes them; "int8") and
``page_size``.

A net with counting layers (an impl with `counters`: the expert layer
through `apply_counted`, a state layer or a grouped-attention layer as
the third value its `apply_cached` returns) makes each step return a
third value, an int32 vector in the order of the fn's ``counters``
attribute: each kind of counting layer's names, the kinds in the order
the plan meets them (empty, and two values returned, for any other
net). ``live`` [B] bool, the
optional last argument of the decode and verify fns, is the caller's
word on which rows hold a request (the serving engine pads its batch
with idle rows): the others attend no key (key_limit 0, so an idle
row's scratch position never lengthens the walk over the cache) and a
counting layer computes and counts nothing for them.

The fns are pure (no net mutation, no rng), so an external jit owner,
the serving engine, controls the compile cache, as with `inference_fn`.
``serving_params(net)`` is the walk's dtype rule applied ahead of time:
the parameter tree a server hands to these fns step after step.

Equivalence contract (tier-1, tests/test_generation.py): greedy decode
through prefill + K incremental steps matches argmax over K
full-sequence forwards at atol 1e-5.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.layers.base import input_region, region_scope
from deeplearning4j_tpu.nn.training import tree_cast
from deeplearning4j_tpu.ops.decode_attention import (
    cache_attention,
    cache_attention_q8,
    quantized_cache_update,
)


# ------------------------------------------------------------- model plan

class _Op:
    """One traversal step: a layer or a non-layer vertex."""

    __slots__ = ("kind", "name", "conf", "impl", "preproc", "inputs")

    def __init__(self, kind, name, conf, impl, preproc, inputs):
        self.kind = kind
        self.name = name
        self.conf = conf
        self.impl = impl
        self.preproc = preproc
        self.inputs = inputs


def _plan(net):
    """-> (input_name, output_name, [ _Op ], loop) for either container,
    validating every layer/vertex is incrementally decodable; `loop` is
    (entry, first op, last op, times) of a graph's loop of more than one
    pass, else None."""
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    problems, ops, loop = [], [], None
    if isinstance(net, ComputationGraph):
        from deeplearning4j_tpu.nn.conf.graph_conf import (
            ElementWiseVertexConf,
            LayerVertexConf,
            MergeVertexConf,
            ScaleVertexConf,
            SubsetVertexConf,
        )

        ins, outs = net.conf.network_inputs, net.conf.network_outputs
        if len(ins) != 1 or len(outs) != 1:
            raise ValueError(
                "incremental decode needs a single-input/single-output "
                f"graph; this one has inputs {list(ins)} and outputs "
                f"{list(outs)}")
        for name in net.topo:
            if name in ins:
                continue
            vconf = net.conf.vertices[name]
            inputs = list(net.conf.vertex_inputs[name])
            if isinstance(vconf, LayerVertexConf):
                lc = vconf.layer
                if not _decodable_layer(lc, net.impls[name]):
                    problems.append(f"{name} ({type(lc).__name__})")
                ops.append(_Op("layer", name, lc, net.impls[name],
                               vconf.preprocessor, inputs))
            elif isinstance(vconf, (ElementWiseVertexConf, MergeVertexConf,
                                    ScaleVertexConf, SubsetVertexConf)):
                ops.append(_Op("vertex", name, vconf, None, None, inputs))
            else:
                problems.append(f"{name} ({type(vconf).__name__})")
        in_name, out_name = ins[0], outs[0]
        if net.loop is not None and net.loop[2] > 1:
            entry, span, times = net.loop
            at = [op.name for op in ops].index(span[0])
            loop = (entry, at, at + len(span) - 1, times)
            problems += [f"{op.name} ({type(op.conf).__name__}: its cache "
                         f"entry has no pass axis, so it cannot be looped)"
                         for op in ops[at:at + len(span)]
                         if op.kind == "layer"
                         and hasattr(op.impl, "cache_arrays")
                         and not getattr(op.impl, "passes", False)]
    else:
        prev = "__input__"
        for i, (name, lc, impl) in enumerate(zip(
                net.layer_names, net.layer_confs, net.impls)):
            if not _decodable_layer(lc, impl):
                problems.append(f"{name} ({type(lc).__name__})")
            ops.append(_Op("layer", name, lc, impl,
                           net.conf.get_preprocessor(i), [prev]))
            prev = name
        in_name, out_name = "__input__", prev
    if problems:
        raise ValueError(
            "incremental decode supports transformer stacks (layers that "
            "map each position by itself + causal attention + positional "
            "encodings); these cannot stream one token at a time: "
            + ", ".join(problems))
    return in_name, out_name, ops, loop


def _decodable_layer(lc, impl) -> bool:
    if hasattr(impl, "apply_cached"):
        # non-causal attention reads the future
        return bool(getattr(lc, "causal", True))
    return impl.per_position or hasattr(impl, "apply_counted")


def _mark_counters(fn, plan):
    """`fn.counting`: the impls of the plan's counting layers (one with
    `counters` names and `merge_counts`: the expert layer's, the
    retention layer's, the grouped-attention layer's), each kind once,
    in the order the plan meets them; `fn.counters`: the names of the
    int32 vector the step returns as its third value, kind after kind,
    () where there is no such layer."""
    fn.counting = list(dict.fromkeys(
        op.impl for op in plan[2]
        if op.kind == "layer" and hasattr(op.impl, "counters")))
    if plan[3] is not None:
        fn.counting.append(_LoopCount)
    fn.counters = tuple(n for impl in fn.counting for n in impl.counters)
    return fn


class _LoopCount:
    """The loop's own counter on the counters road: `loop_passes`, the
    passes a step ran, summed over its live rows."""

    counters = ("loop_passes",)

    @staticmethod
    def merge_counts(counts: list) -> dict:
        return {"loop_passes": sum(c["loop_passes"] for c in counts)}


def cache_specs(net, capacity: int, kv_dtype: str = "f32",
                page_size: int = 16) -> dict:
    """{layer: {array: (shape of one slot, dtype name)}} for every
    layer that keeps a cache entry, each as the layer's own
    `cache_arrays` gives it: what `init_cache` allocates a batch of and
    what the serving allocator bills (serving/kvcache.bytes_per_slot).
    An array that is no row a position (a state) carries a third entry,
    "slot"; an array of rows that holds fewer positions than the
    capacity (a ring) carries their number. A layer inside a loop keeps
    one block of its arrays a pass: a pass axis of `times` first."""
    if kv_dtype == "int8" and capacity % page_size != 0:
        raise ValueError(
            f"int8 cache needs page-quantized capacity; {capacity} "
            f"is not a multiple of page_size {page_size}")
    _, _, ops, loop = _plan(net)
    looped = (set() if loop is None else
              {op.name for op in ops[loop[1]:loop[2] + 1]})

    def arrays_of(op):
        try:
            return op.impl.cache_arrays(op.conf, capacity, kv_dtype,
                                        page_size, net.compute_dtype)
        except ValueError as e:     # a stored format the layer lacks
            raise ValueError(
                f"{op.name} ({type(op.conf).__name__}): {e}") from None

    def passes(op):
        return (loop[3],) if op.name in looped else ()

    return {op.name: {arr: (passes(op) + tuple(shape), jnp.dtype(dt).name,
                            *per)
                      for arr, (shape, dt, *per) in arrays_of(op).items()}
            for op in ops
            if op.kind == "layer" and hasattr(op.impl, "cache_arrays")}


def init_cache(net, batch: int, capacity: int, kv_dtype: str = "f32",
               page_size: int = 16):
    """Zeroed cache, {layer: {array: [batch, ...]}} by `cache_specs`
    (each layer's docstring has its arrays; an int8 capacity must sit on
    the page grid). `capacity` is the per-row key budget (prompt +
    generated, page-quantized by the serving layer)."""
    return {name: {arr: jnp.zeros((batch,) + shape, dt)
                   for arr, (shape, dt, *_per) in arrays.items()}
            for name, arrays in cache_specs(net, capacity, kv_dtype,
                                            page_size).items()}


class CacheStep:
    """What a layer that carries `apply_cached` is told about the
    serving step it is called in: `rows` [b] the cache rows the call's
    batch rows are (None: all of them, in order), `positions` [b, T] the
    position each token occupies, `keep` [b, T] 1 for real tokens (None:
    all; the pad of a prefill bucket writes zero rows), `chunk` True for
    a prefill chunk (many queries a row), False for a decode or verify
    step, `live` [b] bool the rows the caller says hold a request (None:
    all). `write` and `attend` are the two operations on a key-value
    entry in the cache's stored format (`kv_dtype`, `page_size`);
    `write_run` writes a chunk's rows into an entry as the one run a row
    they are. A layer whose entry is a state reads the fields
    alone: `positions[:, 0] == 0` zeroes a row's state before anything
    is added, `keep` 0 adds and decays nothing, a row not `live` keeps
    its state. Inside a loop (`in_pass`) `pass_index`, a traced scalar,
    names the pass, and the layer's entry has a pass axis after the batch
    axis; outside one it is None, and the entry has none."""

    __slots__ = ("rows", "positions", "keep", "chunk", "live", "kv_dtype",
                 "page_size", "pass_index")

    def __init__(self, rows, positions, keep=None, chunk=False, live=None,
                 kv_dtype="f32", page_size=16):
        self.rows, self.positions = rows, positions
        self.keep, self.chunk, self.live = keep, chunk, live
        self.kv_dtype, self.page_size = kv_dtype, page_size
        self.pass_index = None

    def in_pass(self, t):
        """This step as a layer inside a loop sees it in pass `t`."""
        step = CacheStep(self.rows, self.positions, self.keep, self.chunk,
                         self.live, self.kv_dtype, self.page_size)
        step.pass_index = t
        return step

    def write(self, entry, k_new, v_new):
        """`entry` with k_new/v_new [b, T, H, D] written at the step's
        rows and positions."""
        rows = (jnp.arange(k_new.shape[0]) if self.rows is None
                else self.rows)
        return _cache_write(entry, k_new, v_new, rows, self.positions,
                            self.kv_dtype, self.page_size)

    def write_run(self, entry, new, keep, axis):
        """A prefill chunk's entry {name: [B, ..., R, ...]} (R rows a
        cache row on `axis`) with the chunk's new {name: [b, ..., T, ...]}
        (T <= R on the same axis) written where keep [b, T] is True:
        token t of batch row i at cache row rows[i], row (positions[i, 0]
        + t) % R. The same rows and values as a scatter of the step's
        positions with `keep` 0 dropped, bit for bit, but written as the
        one run a chunk's rows are (a ring's with at most one wrap), so
        that the cost is the run's bytes and not a serial pass over its
        rows (`_cache_write`'s scatter).

        Per batch row, with s = positions[i, 0] % R and o = max(s + T -
        R, 0) the run's tokens past the ring's end, two blended blocks of
        T rows an array, always both: B at 0 takes the o tokens past the
        end, A at s - o (= min(s, R - T)), read after B is written, those
        up to it; a run that does not wrap leaves B as it found it. The
        rows the two write do not meet, so their order is free; B first
        is the order in which XLA keeps a chunk program's temporaries
        where the scatter's were. Each block is a dynamic_slice of the
        old rows, a select with the new ones and a dynamic_update_slice
        back, so a donated entry is written in place, and no start is
        ever clamped (A ends at R at the most). `rows` must name rows of
        the entry: none is dropped. The batch rows are a Python loop: a
        vmap of per-row offsets lowers to a scatter again. Plain `lax`
        throughout: the write is traced for every layer of every prefill
        program a server warms. Inside a loop the entry's axis 1 is the
        pass axis, which `new` lacks: the run lands in the step's pass."""
        R = next(iter(entry.values())).shape[axis]
        lead = 1 if self.pass_index is None else 2  # axes `new` lacks
        b, T = keep.shape
        if T > R:
            raise ValueError(f"a run of {T} rows does not fit {R}")
        rows = jnp.arange(b) if self.rows is None else self.rows
        s = jax.lax.rem(self.positions[:, 0], R)
        o = jax.lax.max(s + (T - R), 0)
        m = jax.lax.iota(o.dtype, T)

        def rolled(x, i, dim):
            """x along `dim` turned by o[i]: row m holds x[(m - o) % T]."""
            return jax.lax.dynamic_slice_in_dim(
                jax.lax.concatenate([x, x], dim), T - o[i], T, dim,
                allow_negative_indices=False)

        for i in range(b):
            kept = rolled(keep[i], i, 0)
            blocks = ((0, kept & (m < o[i])), (s[i] - o[i], kept & (m >= o[i])))
            for n, a in entry.items():
                run = jax.lax.expand_dims(
                    rolled(new[n][i].astype(a.dtype), i, axis - lead),
                    tuple(range(lead)))
                for at, mine in blocks:
                    corner = [0] * a.ndim
                    corner[0], corner[axis] = rows[i], at
                    if self.pass_index is not None:
                        corner[1] = self.pass_index
                    old = jax.lax.dynamic_slice(a, corner, run.shape,
                                                allow_negative_indices=False)
                    a = jax.lax.dynamic_update_slice(
                        a, jax.lax.select(jax.lax.broadcast_in_dim(
                            mine, run.shape, (axis,)), run, old),
                        corner, allow_negative_indices=False)
                entry = {**entry, n: a}
        return entry

    def attend(self, entry, qh, key_limit, rows=None):
        """qh [b, H, Tq, D] against `entry`, query t of row i seeing the
        keys before key_limit[i, t]: -> (out, lse). A row not `live`
        sees no key (an idle row is fed the scratch position, whose
        limit would be the whole capacity: it never lengthens the walk
        over the cache's blocks). `rows` [b] names the cache rows the
        queries attend (a prefill chunk's); they are taken from each key
        block as the walk loads it, never gathered from the whole
        cache."""
        if self.live is not None:
            key_limit = jnp.where(jnp.asarray(self.live, bool)[:, None],
                                  key_limit, 0)
        if self.kv_dtype == "int8":
            return cache_attention_q8(qh, entry["k"], entry["v"],
                                      entry["k_scale"], entry["v_scale"],
                                      key_limit, self.page_size, rows)
        return cache_attention(qh, entry["k"], entry["v"], key_limit, rows)


def _cache_write(entry, k_new, v_new, rows, positions, kv_dtype,
                 page_size):
    """Write k_new/v_new [b, T, H, D] at (rows x positions [b, T]): the
    one function every key-value write goes through. Out-of-range
    positions (the engine's inactive-row scratch / a speculative tail
    past capacity) are dropped on both paths: the plain scatter by jax's
    out-of-bounds default, the int8 path inside
    quantized_cache_update. Its ops lie in the region
    `attention/cache_write`."""
    with jax.named_scope("cache_write"):
        if kv_dtype == "int8":
            ck, ks = quantized_cache_update(entry["k"], entry["k_scale"],
                                            k_new, rows, positions, page_size)
            cv, vs = quantized_cache_update(entry["v"], entry["v_scale"],
                                            v_new, rows, positions, page_size)
            return {"k": ck, "k_scale": ks, "v": cv, "v_scale": vs}
        ck = entry["k"].at[rows[:, None], positions].set(
            k_new.astype(entry["k"].dtype))
        cv = entry["v"].at[rows[:, None], positions].set(
            v_new.astype(entry["v"].dtype))
        return {"k": ck, "v": cv}


# -------------------------------------------------------------- the walk

def _walk(net, plan, params, state, cache, x0, step, valid):
    """Topo traversal with inference semantics (train=False, no rng) of
    the tokens x0 [b, T] -> (out [b, T, V], the cache with what the
    layers wrote, the counting layers' counters). A layer that carries
    `apply_cached` is called with its entry of the cache (None for a
    layer that keeps none) and `step`, and may return its counters
    behind (y, entry); a counting layer is told which tokens are real
    (`valid`); the counters come back as (impl, counts) pairs. Each
    layer runs under its impl's region (`region_scope`: a named scope of
    the compiled program), a vertex under the region of its latest
    input (`input_region`). A loop's span runs in `_loop`. Mirrors the
    containers' _forward dtype policy: float inputs and per-layer params
    cast to the compute dtype where the two differ."""
    in_name, out_name, ops, loop = plan
    cache, counts = dict(cache), []
    x0 = jnp.asarray(x0)
    if jnp.issubdtype(x0.dtype, jnp.floating):
        x0 = x0.astype(net.compute_dtype)
    acts, regions = {in_name: x0}, {}

    def run(first, last, acts, cache, counts, step):
        for at in range(first, last):
            op = ops[at]
            inputs = [acts[i] for i in op.inputs]
            region = (op.impl.region if op.kind == "layer"
                      else input_region(op.inputs, regions))
            regions[op.name] = (at, region)
            with region_scope(region):
                acts[op.name] = (_layer(net, op, params, state, cache,
                                        counts, inputs[0], step, valid)
                                 if op.kind == "layer"
                                 else _vertex(op.conf, inputs))

    if loop is None:
        run(0, len(ops), acts, cache, counts, step)
    else:
        _entry, first, last, _times = loop
        run(0, first, acts, cache, counts, step)
        with region_scope("loop"):
            _loop(ops, run, loop, acts, cache, counts, step, valid)
        run(last + 1, len(ops), acts, cache, counts, step)
    return _as_seq(acts[out_name]), cache, counts


def _loop(ops, run, loop, acts, cache, counts, step, valid):
    """The loop's span (`_plan`'s `loop`: entry, first and last op,
    times) as ONE `lax.scan` over the pass, its body traced once: pass t
    reads the last op's output of pass t - 1 (pass 0 the entry), the
    span's cache entries are carried (each with its pass axis; a layer
    writes its own pass's rows in place), and what the body counts comes
    out a pass at a time, into `counts` as if each pass were more layers
    of the walk, beside the loop's own `loop_passes`."""
    entry, first, last, times = loop
    held = [op.name for op in ops[first:last + 1] if op.name in cache]
    kinds = []

    def body(carry, t):
        x, mine = carry
        inner, c, n = dict(acts, **{entry: x}), dict(cache, **mine), []
        run(first, last + 1, inner, c, n, step.in_pass(t))
        kinds[:] = [impl for impl, _c in n]
        return ((inner[ops[last].name], {k: c[k] for k in held}),
                [c_ for _impl, c_ in n])

    (x, mine), per_pass = jax.lax.scan(
        body, (acts[entry], {k: cache[k] for k in held}),
        jnp.arange(times, dtype=jnp.int32))
    acts[ops[last].name] = x
    cache.update(mine)
    counts.extend((impl, jax.tree.map(lambda a, t=t: a[t], c))
                  for t in range(times) for impl, c in zip(kinds, per_pass))
    rows = (x.shape[0] if valid is None
            else jnp.sum(jnp.any(valid, axis=1), dtype=jnp.int32))
    counts.append((_LoopCount,
                   {"loop_passes": jnp.asarray(times * rows, jnp.int32)}))


def _layer(net, op, params, state, cache, counts, x, step, valid):
    """One layer of the walk (`_walk`): its output; what it writes goes
    into `cache`, what it counts onto `counts`."""
    if op.preproc is not None:
        x = op.preproc.pre_process(x)
    p = params.get(op.name, {})
    if net.compute_dtype != net.param_dtype:
        p = tree_cast(p, net.compute_dtype)
    if hasattr(op.impl, "apply_cached"):
        y, entry, *counted = op.impl.apply_cached(
            op.conf, p, _as_seq(x), cache.get(op.name), step)
        counts.extend((op.impl, c) for c in counted)
        if entry is not None:
            cache[op.name] = entry
        if x.ndim == 2:     # a one-token walk that arrived 2-D
            y = y[:, 0, :]  # stays so (see `_as_seq`)
        return y
    if hasattr(op.impl, "apply_counted"):
        y, c = op.impl.apply_counted(op.conf, p, x, valid)
        counts.append((op.impl, c))
        return y
    return op.impl.apply(op.conf, p, state.get(op.name, {}), x,
                         train=False, rng=None)[0]


_cast_tree = jax.jit(tree_cast, static_argnums=1)


def serving_params(net):
    """`net.params` as `_walk` multiplies by them: where the net's
    compute dtype differs from its parameter dtype, every floating leaf
    cast to the compute dtype by the walk's own `tree_cast` (one jitted
    program over the tree); where the two are equal, the very tree it
    was given (no copy). The cast does not depend on the step, so a
    server makes this tree once and hands it to every step: the walk's
    `tree_cast` then traces to nothing, and every product has the
    operands it had with the stored tree. `net.params` stay the
    caller's, in `param_dtype`."""
    if net.compute_dtype == net.param_dtype:
        return net.params
    return _cast_tree(net.params, net.compute_dtype)


def _vertex(vconf, inputs):
    from deeplearning4j_tpu.nn.conf.graph_conf import (
        ElementWiseVertexConf,
        MergeVertexConf,
        ScaleVertexConf,
        SubsetVertexConf,
    )

    if isinstance(vconf, MergeVertexConf):
        return jnp.concatenate(inputs, axis=-1)
    if isinstance(vconf, ScaleVertexConf):
        return inputs[0] * vconf.scale
    if isinstance(vconf, SubsetVertexConf):
        return inputs[0][..., vconf.from_idx:vconf.to_idx + 1]
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
    raise ValueError(f"unhandled vertex {type(vconf).__name__}")


def _live_tokens(live, positions):
    """[B, T] True for the tokens of the rows `live` [B] marks (the
    caller's word on which rows of the batch hold a request); None, and
    every token real, where the caller gave none."""
    if live is None:
        return None
    return jnp.broadcast_to(jnp.asarray(live, bool)[:, None],
                            positions.shape)


def _as_seq(x):
    """Re-expand [B, d] to [B, 1, d]. EmbeddingImpl squeezes a [B, 1]
    index column to [B] (reference EmbeddingLayer is feed-forward), so a
    single-token walk's activations can arrive 2-D; adding a [B, 1, d]
    positional term to a 2-D [B, d] would BROADCAST to [B, B, d] and
    silently hand every row past 0 row 0's features. Every layer that
    mixes x with per-row position data is handed x through this."""
    return x[:, None, :] if x.ndim == 2 else x


def _finish(fn, counts, out, cache):
    """A step's return: (out, cache), and the layers' counters merged,
    each kind of counting layer by its own `merge_counts`, into one
    int32 vector in the order of `fn.counters` where the plan has
    counting layers."""
    if not fn.counters:
        return out, cache
    total = {}
    for impl in fn.counting:
        total.update(impl.merge_counts([c for i, c in counts if i is impl]))
    return out, cache, jnp.stack(
        [total[n] for n in fn.counters]).astype(jnp.int32)


# ------------------------------------------------------------ entry fns

def make_decode_fn(net, kv_dtype: str = "f32", page_size: int = 16):
    """-> pure ``step(params, state, cache, token, pos) -> (probs,
    cache)``: the verify step at K = 1. token [B] int32; pos [B] int32
    is the position the token OCCUPIES (0-based — a row whose prompt
    filled [0, L) decodes its first generated token at pos=L). probs
    [B, V] is the output layer's activation row for that token; cache
    comes back with the token's K/V written at (row, pos)."""
    plan = _plan(net)

    def step(params, state, cache, token, pos, live=None):
        positions = pos[:, None]                           # [B, 1]
        probs, cache, counts = _walk(
            net, plan, params, state, cache, token[:, None],
            CacheStep(None, positions, live=live, kv_dtype=kv_dtype,
                      page_size=page_size),
            _live_tokens(live, positions))
        return _finish(step, counts, probs[:, 0, :], cache)

    return _mark_counters(step, plan)


def make_prefill_fn(net, kv_dtype: str = "f32", page_size: int = 16):
    """-> pure ``prefill(params, state, cache, tokens, kmask, rows,
    start, last_idx) -> (probs_last, cache)``. tokens [b, Tc] int32 (a
    bucket-shaped prompt chunk, zero-padded); kmask [b, Tc] (1 = real
    token); rows [b] — which cache rows this chunk fills; start [b] —
    the global position of the chunk's first token (0 for the first
    chunk; later chunks of a long prompt attend the cache prefix they
    already wrote); last_idx [b] — the LOCAL index of the last real
    token in this chunk (its output row is gathered device-side so only
    [b, V] comes home; pass Tc-1 for non-final chunks and ignore the
    result). Padded positions write ZERO K/V (masked) and are
    overwritten as decode advances."""
    plan = _plan(net)

    def prefill(params, state, cache, tokens, kmask, rows, start,
                last_idx):
        b, Tc = tokens.shape
        local = jnp.arange(Tc)
        positions = start[:, None] + local[None, :]        # [b, Tc]
        probs, cache, counts = _walk(
            net, plan, params, state, cache, tokens,
            CacheStep(rows, positions, keep=kmask, chunk=True,
                      kv_dtype=kv_dtype, page_size=page_size),
            kmask > 0 if prefill.counters else None)
        return _finish(prefill, counts,
                       probs[jnp.arange(b), last_idx, :], cache)

    return _mark_counters(prefill, plan)


def make_verify_fn(net, kv_dtype: str = "f32", page_size: int = 16):
    """-> pure ``verify(params, state, cache, tokens, pos) -> (probs,
    cache)`` — the speculative-decode verification step. tokens [B, K]
    int32 is each row's candidate window (its true last token followed
    by K-1 draft tokens); pos [B] is the position the FIRST token
    occupies. probs [B, K, V]: row i is the model's next-token output
    after consuming tokens[:, :i+1] — bit-identical to what i+1
    sequential `make_decode_fn` steps would produce, because all K K/Vs
    are written first and query row i attends with key_limit pos+i+1
    (causal including self). The host-side acceptance mask
    (serving/speculative.py) compares argmax rows against the drafts;
    rejected positions' stale K/V stays invisible until the next verify
    window overwrites it. A layer whose step cannot be unwound (its impl
    says `rewindable(conf)` False: a state, or a ring of rows) has no such
    forgiveness, so a net with one is refused here, when the fn is built."""
    plan = _plan(net)
    fixed = [f"{op.name} ({type(op.conf).__name__})" for op in plan[2]
             if op.kind == "layer" and not op.impl.rewindable(op.conf)]
    if fixed:
        raise ValueError(
            "speculative verification writes a window of drafts and "
            "unwinds the rejected ones; these layers keep a state or a "
            "ring a step cannot be taken out of again: " + ", ".join(fixed))

    def verify(params, state, cache, tokens, pos, live=None):
        positions = pos[:, None] + jnp.arange(tokens.shape[1])[None, :]
        probs, cache, counts = _walk(
            net, plan, params, state, cache, tokens,
            CacheStep(None, positions, live=live, kv_dtype=kv_dtype,
                      page_size=page_size),
            _live_tokens(live, positions))
        return _finish(verify, counts, probs, cache)

    return _mark_counters(verify, plan)
