"""The weight set a `GenerationEngine` serves from (ISSUE 34): the net's
parameters in its COMPUTE dtype, made once when the store is built
(nn/decode.serving_params), not cast again in every step. Where the net
stores float32 and computes in bfloat16 the store holds a bfloat16 copy
and the greedy streams are token for token what the same steps give when
handed the float32 tree; where the two dtypes are equal the store holds
the net's own arrays and the programs are the ones lowered from
`net.params`. One store serves every replica, and nothing of it outlives
`publish(None, None, 0)`."""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.latent_moe import latent_moe_lm
from deeplearning4j_tpu.models.transformer import transformer_lm
from deeplearning4j_tpu.nn.decode import serving_params
from deeplearning4j_tpu.serving.buckets import BucketLattice
from deeplearning4j_tpu.serving.engine import GenerationEngine
from deeplearning4j_tpu.telemetry import Recorder
from deeplearning4j_tpu.telemetry.memstat import tree_bytes

pytestmark = pytest.mark.serving

NEW = 32
_PROMPT_LENS = (3, 8, 11, 16, 5, 13)


def _lm(dtype, d_model=32, d_ff=64):
    """The tiny LM, float32 parameters scaled up so that its greedy
    stream depends on the prompt; `dtype` is what it computes in."""
    net = transformer_lm(vocab_size=64, d_model=d_model, n_heads=2,
                         n_layers=2, d_ff=d_ff, max_length=64, dtype=dtype)
    net.init()
    net.params = jax.tree.map(lambda x: x * 8.0, net.params)
    return net


def _latent_bf16():
    net = latent_moe_lm(
        vocab_size=64, d_model=64, n_heads=4, n_layers=2, q_rank=24,
        kv_rank=16, nope_dim=8, rope_dim=8, v_dim=8, d_ff=96,
        n_dense_layers=1, n_experts=16, top_k=4, d_expert=32, n_shared=1,
        first_expert=0, n_held=4, routed_scaling=2.5, dtype="bfloat16",
        param_dtype="bfloat16")
    net.init()
    return net


def _engine(net, rec=None, **kw):
    kw = {"slots": 2, "max_new_tokens": NEW, "page_size": 8, **kw}
    return GenerationEngine(
        net, BucketLattice(batch_sizes=(1,), seq_lens=(8, 16)),
        recorder=rec, **kw)


def _floating(tree):
    return [x for x in jax.tree.leaves(tree)
            if jnp.issubdtype(x.dtype, jnp.floating)]


def _streams(net, stored_tree: bool, **kw):
    """The greedy streams of the mixed prompts through an engine over
    `net`, all queued before the loop starts. `stored_tree`: the store
    is handed `net.params` as they are, the float32 tree every step then
    casts for itself (the path before ISSUE 34, through the very same
    jitted steps)."""
    engine = _engine(net, **kw)
    if stored_tree:
        engine.weights.publish(net.params, net.state, 0)
    engine.warmup()
    traced = engine.trace_count
    rng = np.random.default_rng(11)
    reqs = [engine.submit_generate(rng.integers(0, 64, n).astype(np.int32),
                                   NEW) for n in _PROMPT_LENS]
    engine.start()
    for req in reqs:
        assert req.wait(120) and req.error is None
    assert engine.trace_count == traced, "a step retraced after warmup"
    stats = engine.stats()
    engine.drain()
    return [list(r.emitted) for r in reqs], stats


def test_mixed_dtypes_serve_a_compute_dtype_copy_token_for_token():
    net = _lm("bfloat16")
    assert net.param_dtype == jnp.float32 != net.compute_dtype
    rec = Recorder(path=None)
    engine = _engine(net, rec)
    served = engine.weights.current.params
    n_float = len(_floating(net.params))
    assert n_float == len(jax.tree.leaves(net.params)) > 0
    assert {x.dtype.name for x in _floating(served)} == {"bfloat16"}
    # the caller's tree is left as it was, in the dtype it was stored in
    assert {x.dtype.name for x in _floating(net.params)} == {"float32"}
    facts = {"weights_dtype": "bfloat16",
             "weights_bytes": tree_bytes(net.params) // 2,
             "weights_cast_leaves": n_float}
    meta = [e for e in rec.events if e.get("event") == "meta"
            and e.get("role") == "generation-engine"]
    assert len(meta) == 1 and facts.items() <= meta[0].items()
    assert facts.items() <= engine.stats().items()
    assert facts.items() <= engine.fleet_workers()[0].describe().items()
    # the ledger's `params` entry reads the served set
    assert engine.memsampler.ledger.attributed()["params"] == \
        facts["weights_bytes"]

    served_streams, _ = _streams(net, stored_tree=False)
    stored_streams, _ = _streams(net, stored_tree=True)
    assert served_streams == stored_streams
    assert [len(s) for s in served_streams] == [NEW] * len(_PROMPT_LENS)
    assert len({tuple(s) for s in served_streams}) > 3, \
        "the streams do not depend on the prompt"


@pytest.mark.parametrize("build", [lambda: _lm("float32"), _latent_bf16],
                         ids=["gpt2_float32", "latent_bfloat16"])
def test_equal_dtypes_serve_the_nets_own_arrays(build):
    net = build()
    assert net.param_dtype == net.compute_dtype
    assert serving_params(net) is net.params
    engine = _engine(net)
    worker = engine.fleet_workers()[0]
    served = engine.weights.current.params
    for ours, theirs in zip(jax.tree.leaves(served),
                            jax.tree.leaves(net.params)):
        assert ours is theirs
    assert engine.stats()["weights_cast_leaves"] == 0
    assert engine.stats()["weights_bytes"] == tree_bytes(net.params)

    n = worker.plan.n_slots
    # the slots' last tokens as the device holds them, positions, `live`
    step = (worker._tokens, jnp.zeros(n, jnp.int32), jnp.ones(n, bool))
    text = [worker._decode_jit.lower(p, net.state, worker.cache, *step)
            .as_text() for p in (served, net.params)]
    assert text[0] == text[1]


def test_replicas_share_one_served_copy():
    net = _lm("bfloat16")
    engine = _engine(net, replicas=2)
    first, second = engine.fleet_workers()
    assert first.weights is second.weights is engine.weights
    assert first.weights_facts == second.weights_facts
    assert first.weights_facts["weights_cast_leaves"] > 0
    # one copy on the device, not one a worker
    assert engine.memsampler.ledger.attributed()["params"] == \
        tree_bytes(net.params) // 2


def test_nothing_of_the_served_copy_outlives_the_store():
    """What the benchmark's `Served.stop()` does to make room for its
    float32 reference: the store is emptied, the caller drops its own
    tree, and no array of a weight's shape is left on the device."""
    before = {id(a) for a in jax.live_arrays()}
    # widths no other test of the process uses
    net = _lm("bfloat16", d_model=40, d_ff=72)
    net.opt_state = None        # a served net holds no optimizer moments
    shapes = {x.shape for x in jax.tree.leaves(net.params) if x.ndim == 2}
    engine = _engine(net)
    engine.warmup()
    engine.start()
    assert len(engine.generate(np.arange(1, 6, dtype=np.int32), 4)) == 4

    def weights_left():
        gc.collect()
        return [a for a in jax.live_arrays() if id(a) not in before
                and a.shape in shapes]

    # the float32 tree and its bfloat16 copy
    assert len(weights_left()) == 2 * len(
        [x for x in jax.tree.leaves(net.params) if x.ndim == 2])
    engine.drain()
    for w in engine.fleet_workers():
        w.cache = None
    engine.weights.publish(None, None, 0)
    net.params = None
    assert weights_left() == []


def test_speculative_path_accepts_the_same_tokens():
    net = _lm("bfloat16")
    plain, _ = _streams(net, stored_tree=False)
    served, s_served = _streams(net, stored_tree=False, speculative_k=2)
    stored, s_stored = _streams(net, stored_tree=True, speculative_k=2)
    assert served == stored == plain
    assert s_served["speculative"]["verify_steps"] > 0
    for key in ("verify_steps", "accepted_tokens_per_step",
                "draft_acceptance_rate"):
        assert s_served["speculative"][key] == s_stored["speculative"][key]
