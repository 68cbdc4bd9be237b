"""The ep-sharded embedding engine: row-partitioned tables under
shard_map, sparse-gather forward, (indices, values) scatter-add backward.

Reference (SURVEY §2.3 / §L7): InMemoryLookupTable + SkipGram HS/NS are
the training core of the reference's ~31k-LoC embeddings library; the
legacy port (nlp/lookup.py) runs them as dense single-device steps.
This engine is the mesh-native redesign, the cross-replica-sharding
shape of arXiv:2004.13336 applied to the embedding table itself:

* `syn0`/`syn1`/`syn1neg` rows are partitioned across the `expert` (ep)
  mesh axis — tables deliberately sized past one process's memory are
  the point. Per-device bytes are attributed through the memstat ledger
  (`ledger`, subsystem "params"), which is how the bench verifies that
  ep=2 really halves the per-device footprint.
* Forward is a SPARSE GATHER: each rank gathers the rows it owns
  (masked take), then one psum over `expert` assembles the full [B, D]
  strips. Scoring runs through the fused negative-sampling
  sampled-softmax kernel (ops/fused_neg_softmax.py — pure-jnp reference
  outside its envelope, bit-identical to the legacy math).
* Backward travels as (indices, values) COO pairs — the overlap layer's
  sparse bucket kind (parallel/overlap.sparse_bucket_reduce) when a
  `data` axis is present — and each rank scatter-adds ONLY its owned
  rows. The gradient is never materialized at the table's shape
  (graftlint G030 polices exactly that outside this package).
* At ep=1 every masking/psum op is value-preserving, so the engine is
  BIT-IDENTICAL to nlp/lookup.sgns_step / sg_hs_step — the parity
  contract tests/test_embedding.py pins after N seeded steps.

Host-side `self._trace_count += 1` inside the traced bodies runs at
TRACE time only — the zero-retrace warmup gate counts these, exactly
like serving/engine.py's counter.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.nlp.lookup import MAX_ROW_STEP
from deeplearning4j_tpu.ops.fused_neg_softmax import neg_softmax_scores
from deeplearning4j_tpu.parallel.mesh import make_mesh
from deeplearning4j_tpu.parallel.overlap import (
    plan_sparse_bucket,
    sparse_bucket_reduce,
)
from deeplearning4j_tpu.telemetry import get_default
from deeplearning4j_tpu.telemetry.memstat import MemoryLedger
from deeplearning4j_tpu.util.compat import shard_map

# mesh axis names; the step bodies below run under the shard_map in
# `_wrap`, which binds both (G012's axis-name contract)
EP_AXIS = "expert"
DP_AXIS = "data"


def _ep_gather(local, idx, lo, v_local, axis_name=EP_AXIS):
    """Sparse gather across the `expert` axis: each rank takes the rows
    of its [V/ep, D] shard that `idx` names (masked take — out-of-shard
    indices contribute zero rows) and one psum assembles the full
    strips. idx [...], returns [..., D]. At ep=1 every op is
    value-preserving, so the result is bit-identical to `table[idx]`."""
    rel = idx - lo
    owned = (rel >= 0) & (rel < v_local)
    rows = local[jnp.where(owned, rel, 0)]
    rows = jnp.where(owned[..., None], rows, jnp.zeros((), rows.dtype))
    return lax.psum(rows, axis_name)


def _ep_scatter_update(local, idx, grads, lr, lo, v_local):
    """Owned-rows scatter-add + SGD with the legacy per-row trust-region
    cap (nlp/lookup._scatter_update, applied to the local shard — each
    global row lives on exactly one rank, so the row sums and norms
    match the dense formulation's). idx [N], grads [N, D]."""
    rel = idx - lo
    owned = (rel >= 0) & (rel < v_local)
    safe = jnp.where(owned, rel, 0)
    grads = jnp.where(owned[:, None], grads, jnp.zeros((), grads.dtype))
    sums = jnp.zeros_like(local).at[safe].add(grads.astype(local.dtype))
    step = lr * sums
    n = jnp.linalg.norm(step, axis=1, keepdims=True)
    step = step * jnp.minimum(1.0, MAX_ROW_STEP / jnp.maximum(n, 1e-12))
    return local - step


class ShardedEmbeddingEngine:
    """Row-sharded embedding tables + jitted SGNS / hierarchical-softmax
    train steps. Construction mirrors InMemoryLookupTable (same seed ->
    same init bits at ep=1); `EngineLookupView` adapts the query API."""

    def __init__(self, vocab_size: int, vector_length: int, *,
                 ep: int = 1, dp: int = 1, negative: int = 5,
                 use_hs: bool = False, seed: int = 123,
                 dtype=jnp.float32, recorder=None):
        if vocab_size <= 0 or vector_length <= 0:
            raise ValueError("vocab_size and vector_length must be positive")
        self.vocab_size = int(vocab_size)
        self.vector_length = int(vector_length)
        self.ep = int(ep)
        self.dp = int(dp)
        self.negative = int(negative)
        self.use_hs = bool(use_hs)
        self.seed = int(seed)
        self.dtype = dtype
        # rows pad to an ep multiple so every rank owns an equal shard;
        # padding rows are init'd but never indexed by real ids
        self.padded_vocab = -(-self.vocab_size // self.ep) * self.ep
        axes = {"expert": self.ep} if self.dp == 1 else \
            {"data": self.dp, "expert": self.ep}
        self.mesh = make_mesh(axes)
        self._table_spec = P("expert", None)
        self._batch_spec = P("data") if self.dp > 1 else P()
        self._recorder = recorder if recorder is not None else get_default()
        self._trace_count = 0
        self._steps = {}            # (kind, *shape) -> jitted step
        self._lookups = {}          # n -> jitted gather
        self._mu = threading.Lock()
        self.loss_history = []
        self.reset_weights()
        self.ledger = MemoryLedger()
        self.ledger.register("params", self._device0_shards)

    # ------------------------------------------------------------- state
    def reset_weights(self):
        key = jax.random.PRNGKey(self.seed)
        # reference init: (rand - 0.5) / dim (InMemoryLookupTable.java:133)
        # — identical bits to nlp/lookup.InMemoryLookupTable at ep=1
        syn0 = ((jax.random.uniform(
            key, (self.padded_vocab, self.vector_length)) - 0.5)
            / self.vector_length).astype(self.dtype)
        sharding = NamedSharding(self.mesh, self._table_spec)
        shape = (self.padded_vocab, self.vector_length)
        self.syn0 = jax.device_put(syn0, sharding)
        # separate buffers: a shared zeros array would make a later
        # donation of one table delete the other
        self.syn1 = jax.device_put(np.zeros(shape, np.float32)
                                   .astype(self.dtype), sharding)
        self.syn1neg = jax.device_put(np.zeros(shape, np.float32)
                                      .astype(self.dtype), sharding)

    def _device0_shards(self):
        """Memstat ledger source: the table shards resident on mesh
        device 0 — per-device table bytes, the number the ep-scaling
        acceptance row halves."""
        dev = self.mesh.devices.flat[0]
        out = []
        for table in (self.syn0, self.syn1, self.syn1neg):
            for shard in table.addressable_shards:
                if shard.device == dev:
                    out.append(shard.data)
        return out

    def table_bytes_per_device(self) -> int:
        """Per-device table bytes, read through the memstat ledger (the
        blessed G029 producer)."""
        return int(self.ledger.attributed().get("params", 0))

    @property
    def trace_count(self) -> int:
        """Times any engine computation was (re)traced — the
        zero-retrace warmup gate's counter."""
        return self._trace_count

    # ------------------------------------------------------- train steps
    def _v_local(self) -> int:
        return self.padded_vocab // self.ep

    def _wrap(self, body, n_tables, n_batch):
        """shard_map + jit a step body: tables row-sharded over
        `expert`, batch over `data` (replicated when dp == 1), lr
        replicated; tables donated."""
        in_specs = ((self._table_spec,) * n_tables
                    + (self._batch_spec,) * n_batch + (P(),))
        out_specs = (self._table_spec,) * n_tables + (P(),)
        fn = shard_map(body, mesh=self.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
        return jax.jit(fn, donate_argnums=tuple(range(n_tables)))

    def _build_sgns(self, batch: int, k: int):
        v_local = self._v_local()
        dp = self.dp
        b_local = batch // dp if dp > 1 else batch
        sb_center = plan_sparse_bucket(
            "sgns_syn0", b_local, self.vector_length, n_participants=dp)
        sb_out = plan_sparse_bucket(
            "sgns_syn1neg", b_local * (1 + k), self.vector_length,
            n_participants=dp)
        self._emit_bucket_plan("sgns", (sb_center, sb_out))

        def body(syn0, syn1neg, center, context, negatives, lr):
            self._trace_count += 1      # trace time only
            lo = lax.axis_index(EP_AXIS) * v_local
            c = _ep_gather(syn0, center, lo, v_local)
            pos = _ep_gather(syn1neg, context, lo, v_local)
            neg = _ep_gather(syn1neg, negatives, lo, v_local)

            pos_score, neg_score = neg_softmax_scores(c, pos, neg)

            g_pos = (pos_score - 1.0)[:, None]
            g_neg = neg_score[:, :, None]
            grad_c = g_pos * pos + jnp.einsum(
                "bko,bkd->bd", g_neg, neg,
                preferred_element_type=jnp.float32)
            grad_pos = g_pos * c
            grad_neg = g_neg * c[:, None, :]

            b, kk = negatives.shape
            cen_idx, cen_vals = center, grad_c
            out_idx = jnp.concatenate([context, negatives.reshape(b * kk)])
            out_vals = jnp.concatenate(
                [grad_pos, grad_neg.reshape(b * kk, -1)])
            if dp > 1:
                cen_idx, cen_vals = sparse_bucket_reduce(
                    cen_idx, cen_vals, DP_AXIS, bucket=sb_center)
                out_idx, out_vals = sparse_bucket_reduce(
                    out_idx, out_vals, DP_AXIS, bucket=sb_out)
            syn0 = _ep_scatter_update(syn0, cen_idx, cen_vals, lr,
                                      lo, v_local)
            syn1neg = _ep_scatter_update(syn1neg, out_idx, out_vals, lr,
                                         lo, v_local)

            loss = -(jnp.sum(jnp.log(pos_score + 1e-10))
                     + jnp.sum(jnp.log(1.0 - neg_score + 1e-10)))
            if dp > 1:
                loss = lax.psum(loss, DP_AXIS)
            return syn0, syn1neg, loss / batch

        return self._wrap(body, n_tables=2, n_batch=3)

    def _build_hs(self, batch: int, depth: int):
        v_local = self._v_local()
        dp = self.dp
        b_local = batch // dp if dp > 1 else batch
        sb_center = plan_sparse_bucket(
            "hs_syn0", b_local, self.vector_length, n_participants=dp)
        sb_nodes = plan_sparse_bucket(
            "hs_syn1", b_local * depth, self.vector_length,
            n_participants=dp)
        self._emit_bucket_plan("hs", (sb_center, sb_nodes))

        def body(syn0, syn1, center, codes, points, mask, lr):
            self._trace_count += 1      # trace time only
            lo = lax.axis_index(EP_AXIS) * v_local
            c = _ep_gather(syn0, center, lo, v_local)
            nodes = _ep_gather(syn1, points, lo, v_local)
            sign = 1.0 - 2.0 * codes.astype(c.dtype)
            logit = jnp.einsum("bd,bld->bl", c, nodes,
                               preferred_element_type=jnp.float32)
            p = jax.nn.sigmoid(sign * logit)
            m = mask.astype(c.dtype)

            g = -sign * (1.0 - p) * m
            grad_c = jnp.einsum("bl,bld->bd", g, nodes,
                                preferred_element_type=jnp.float32)
            grad_nodes = g[:, :, None] * c[:, None, :]

            b, length = codes.shape
            cen_idx, cen_vals = center, grad_c
            flat_pts = jnp.where(mask, points, 0).reshape(b * length)
            flat_vals = (grad_nodes * m[:, :, None]).reshape(b * length, -1)
            if dp > 1:
                cen_idx, cen_vals = sparse_bucket_reduce(
                    cen_idx, cen_vals, DP_AXIS, bucket=sb_center)
                flat_pts, flat_vals = sparse_bucket_reduce(
                    flat_pts, flat_vals, DP_AXIS, bucket=sb_nodes)
            syn0 = _ep_scatter_update(syn0, cen_idx, cen_vals, lr,
                                      lo, v_local)
            syn1 = _ep_scatter_update(syn1, flat_pts, flat_vals, lr,
                                      lo, v_local)

            loss = -jnp.sum(jnp.log(p + 1e-10) * m)
            if dp > 1:
                loss = lax.psum(loss, DP_AXIS)
            return syn0, syn1, loss / batch

        return self._wrap(body, n_tables=2, n_batch=4)

    def _emit_bucket_plan(self, step_kind, buckets):
        self._recorder.event(
            "bucket_plan", sparse=True, step=step_kind, ep=self.ep,
            dp=self.dp, buckets=[b.summary() for b in buckets])

    def _get_step(self, kind, *shape):
        key = (kind, *shape)
        with self._mu:
            fn = self._steps.get(key)
        if fn is None:
            fn = (self._build_sgns(*shape) if kind == "sgns"
                  else self._build_hs(*shape))
            with self._mu:
                fn = self._steps.setdefault(key, fn)
        return fn

    def _pair_bytes(self, n_rows: int) -> int:
        """Wire bytes of an (indices, values) gradient pair."""
        return n_rows * (4 + self.vector_length
                         * jnp.dtype(self.dtype).itemsize)

    def sgns_step(self, center, context, negatives, lr):
        """One SGNS step over a fixed-shape pair batch: center [B],
        context [B], negatives [B, K], scalar lr. Returns the device
        loss scalar (no host sync)."""
        center = jnp.asarray(center, jnp.int32)
        context = jnp.asarray(context, jnp.int32)
        negatives = jnp.asarray(negatives, jnp.int32)
        batch, k = negatives.shape
        fn = self._get_step("sgns", batch, k)
        sparse_rows = batch * (2 + k)
        with self._recorder.span(
                "scatter_add", step="sgns", rows=sparse_rows,
                bytes=self._pair_bytes(sparse_rows), ep=self.ep,
                ep_gather_bytes=self._gather_bytes(sparse_rows)):
            self.syn0, self.syn1neg, loss = fn(
                self.syn0, self.syn1neg, center, context, negatives, lr)
        self.loss_history.append(loss)
        return loss

    def hs_step(self, center, codes, points, mask, lr):
        """One hierarchical-softmax step: center [B], codes/points/mask
        [B, L] (Huffman rows gathered host-side, like the legacy path)."""
        center = jnp.asarray(center, jnp.int32)
        codes = jnp.asarray(codes, jnp.int32)
        points = jnp.asarray(points, jnp.int32)
        mask = jnp.asarray(mask, bool)
        batch, depth = codes.shape
        fn = self._get_step("hs", batch, depth)
        sparse_rows = batch * (1 + depth)
        with self._recorder.span(
                "scatter_add", step="hs", rows=sparse_rows,
                bytes=self._pair_bytes(sparse_rows), ep=self.ep,
                ep_gather_bytes=self._gather_bytes(sparse_rows)):
            self.syn0, self.syn1, loss = fn(
                self.syn0, self.syn1, center, codes, points, mask, lr)
        self.loss_history.append(loss)
        return loss

    def _gather_bytes(self, n_rows: int) -> int:
        """Bytes the forward sparse gather moves across the ep axis:
        each psum'd [rows, D] strip carries (ep-1)/ep remote rows."""
        row_bytes = self.vector_length * jnp.dtype(self.dtype).itemsize
        return n_rows * row_bytes * (self.ep - 1) // self.ep

    # ----------------------------------------------------------- lookup
    def _get_lookup(self, n: int):
        with self._mu:
            fn = self._lookups.get(n)
        if fn is None:
            v_local = self._v_local()

            def body(syn0, idx):
                self._trace_count += 1  # trace time only
                lo = lax.axis_index(EP_AXIS) * v_local
                return _ep_gather(syn0, idx, lo, v_local)

            wrapped = shard_map(
                body, mesh=self.mesh, in_specs=(self._table_spec, P()),
                out_specs=P(), check_vma=False)
            fn = jax.jit(wrapped)
            with self._mu:
                fn = self._lookups.setdefault(n, fn)
        return fn

    def embed(self, ids) -> jax.Array:
        """Sparse-gather `syn0` rows for `ids` [n] (fixed shape per n —
        serving pads to a bucket grid). Returns the device [n, D]."""
        ids = jnp.asarray(ids, jnp.int32)
        n = int(ids.shape[0])
        fn = self._get_lookup(n)
        row_bytes = self.vector_length * jnp.dtype(self.dtype).itemsize
        with self._recorder.span("gather", rows=n, ep=self.ep,
                                 bytes=n * (row_bytes + 4)):
            return fn(self.syn0, ids)


class EngineLookupView:
    """InMemoryLookupTable's query API over the engine — what
    SequenceVectors/serializers see when the engine is installed.
    Reads slice padding rows off; `nearest` keeps the legacy exact
    brute-force contract (the ANN index is the serving-path variant)."""

    def __init__(self, engine: ShardedEmbeddingEngine):
        self._engine = engine
        self.use_hs = engine.use_hs
        self.negative = engine.negative
        self.dtype = engine.dtype

    @property
    def engine(self) -> ShardedEmbeddingEngine:
        return self._engine

    @property
    def vocab_size(self) -> int:
        return self._engine.vocab_size

    @property
    def vector_length(self) -> int:
        return self._engine.vector_length

    @property
    def syn0(self):
        return self._engine.syn0[:self._engine.vocab_size]

    @property
    def syn1(self):
        return self._engine.syn1[:self._engine.vocab_size]

    @property
    def syn1neg(self):
        return self._engine.syn1neg[:self._engine.vocab_size]

    def reset_weights(self):
        self._engine.reset_weights()

    # vectors -------------------------------------------------------------
    def vector(self, index: int) -> np.ndarray:
        return np.asarray(self._engine.embed(jnp.asarray([index]))[0])

    def vectors(self) -> np.ndarray:
        return np.asarray(self.syn0)

    def set_vectors(self, arr: np.ndarray):
        e = self._engine
        arr = jnp.asarray(arr, e.dtype)
        v, d = arr.shape
        if (v, d) != (e.vocab_size, e.vector_length):
            raise ValueError(
                f"set_vectors shape {(v, d)} != engine table "
                f"{(e.vocab_size, e.vector_length)}")
        if e.padded_vocab != v:
            arr = jnp.concatenate(
                [arr, jnp.zeros((e.padded_vocab - v, d), e.dtype)])
        e.syn0 = jax.device_put(
            arr, NamedSharding(e.mesh, e._table_spec))

    # similarity ----------------------------------------------------------
    def _normed(self):
        syn0 = self.syn0
        n = jnp.linalg.norm(syn0, axis=1, keepdims=True)
        return syn0 / jnp.maximum(n, 1e-12)

    def nearest(self, query_vec: np.ndarray, top_n: int = 10,
                exclude=()) -> list:
        normed = self._normed()
        q = jnp.asarray(query_vec, self.dtype)
        q = q / jnp.maximum(jnp.linalg.norm(q), 1e-12)
        sims = jnp.einsum("vd,d->v", normed, q,
                          preferred_element_type=jnp.float32)
        if exclude:
            sims = sims.at[jnp.asarray(list(exclude))].set(-jnp.inf)
        vals, idx = jax.lax.top_k(sims, min(top_n, self.vocab_size))
        return list(zip(np.asarray(idx).tolist(), np.asarray(vals).tolist()))

    def similarity(self, i: int, j: int) -> float:
        rows = self._engine.embed(jnp.asarray([i, j]))
        a, b = rows[0], rows[1]
        denom = jnp.linalg.norm(a) * jnp.linalg.norm(b)
        return float(jnp.vdot(a, b) / jnp.maximum(denom, 1e-12))
