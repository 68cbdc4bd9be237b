"""The seam between the harness and a model family (`spec.family_of`): the
GPT-2 family's seeded weights are bit for bit what `harness/weights.py`
made before the move; every family module offers the whole interface; a
second family that is only new files (`tests/families/twin_lm.py`) runs
every traffic kind through `run.execute` with `correct` true, and the
whole-step shares are computed from ITS counts; an unknown `model_type`
fails naming the file that is missing."""
import glob
import hashlib
import os

import jax
import numpy as np
import presets
import pytest
import run
from harness import device, serve_facts, spec, weights

HERE = os.path.dirname(os.path.abspath(__file__))
INTERFACE = ("dims_of", "serving_net", "training_net", "give_weights",
             "served_gaps", "first_moment_tree", "program_sq_norms",
             "program_projections", "seeded_program_tree",
             "reference_readings", "train_flops_per_token", "prefill_flops",
             "decode_flops", "decode_step_min_bytes", "kv_bytes_per_token",
             "count_params")
COUNTS = ("train_flops_per_token", "prefill_flops", "decode_flops",
          "decode_step_min_bytes")
# sha256 over every leaf (its path, dtype, shape and bytes) of the trees
# that the parent's harness/weights.py made for DIMS on the CPU, taken at
# commit e142555 before the code moved
DIMS = {"d": 64, "H": 2, "L": 3, "F": 128, "V": 97, "eps": 1e-5,
        "embed_gain": 12.0, "qk_gain": 2.5, "resid_gain": 0.3, "head_gain": 4.0}
PARENT = {
    (7, "program_params"):
        "5c4153f2d540aea5c5102b5928c22e799e6d57d8e11a96b1d5e3708bff51ea43",
    (7, "reference_params"):
        "59d664e317322e21ac057087935d6446c1a22dc387905a4fd9abd09b4810155a",
    (2**31 + 7, "program_params"):
        "c41be2db70c439c65f72d81d7e28d4a4523e4f40708c1d3b7243267fb7b886ee",
    (2**31 + 7, "reference_params"):
        "c62642c31df207eb4e75c736d2807562c66bb1df6a429f2f3e8139ff18cef027",
}
TWIN_MODEL = {"model_type": "twin_lm", "hidden_size": 64,
              "num_attention_heads": 2, "num_hidden_layers": 2,
              "intermediate_size": 128, "vocab_size": 256,
              "max_position_embeddings": 128, "norm_eps": 1e-5,
              "compute_dtype": "bfloat16", "param_dtype": "float32"}
TWIN_TRAIN = dict(TWIN_MODEL, training=presets.TINY_TRAIN["training"])
TWIN_SERVE = dict(TWIN_MODEL,
                  seeded_weights=presets.TINY_SERVE["seeded_weights"],
                  deployment=presets.TINY_SERVE["deployment"])


def digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("seed,layout", sorted(PARENT))
def test_gpt2_seeded_weights_are_the_parents(seed, layout):
    gpt2 = spec.family_of(presets.GPT2)
    made = jax.jit(lambda k: getattr(gpt2, layout)(k, DIMS))(
        weights.seed_key(seed))
    assert digest(made) == PARENT[seed, layout]


@pytest.fixture
def twin(monkeypatch):
    monkeypatch.setattr(spec, "FAMILY_DIRS", spec.FAMILY_DIRS
                        + [os.path.join(HERE, "families")])
    return spec.family_of(TWIN_MODEL)


def test_every_family_offers_the_interface(twin):
    paths = glob.glob(os.path.join(spec.BENCH_DIR, "families", "*.py"))
    names = [os.path.basename(p)[:-3] for p in paths
             if not p.endswith("__init__.py")]
    assert "gpt2" in names
    for name in names + ["twin_lm"]:
        family = spec.family_of({"model_type": name})
        missing = [f for f in INTERFACE if not callable(getattr(family, f, None))]
        assert not missing, (name, missing)
    assert spec.family_of({"model_type": "gpt2"}) \
        is spec.family_of(presets.GPT2)         # one module object a process


def test_unknown_model_type_names_the_missing_file():
    with pytest.raises(SystemExit, match=r"benchmarks/families/no_such\.py"):
        spec.family_of({"model_type": "no_such"})
    with pytest.raises(SystemExit, match="model_type"):
        spec.family_of({"n_embd": 64})


def test_twin_counts_differ_on_purpose(twin):
    gpt2 = spec.family_of(presets.GPT2)
    dims = twin.dims_of(TWIN_MODEL)
    assert dims == gpt2.dims_of(presets.TINY_MODEL)
    assert twin.decode_flops(dims, 40) == 2 * gpt2.decode_flops(dims, 40)
    # the shared serving arithmetic asks the configuration's family
    rec = {"prompt_len": 12, "t_tokens": [1.0, 2.0, 3.0]}
    facts = {"dims": dims, "load": {"records": [rec]}}
    work = {m: serve_facts.work_flops(dict(facts, config={"model_type": m}),
                                      0.0, 9.0) for m in ("gpt2", "twin_lm")}
    assert work["twin_lm"] == 2 * work["gpt2"] > 0


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("like,config,mix,limits", [
    ("train_590m_seq2048", TWIN_TRAIN, presets.TRAIN_MIX,
     presets.LOOSE_TRAIN_LIMITS),
    ("serve_1p3b_chat", TWIN_SERVE, presets.OPEN_MIX, presets.SERVE_LIMITS),
    ("serve_1p3b_batchgen", TWIN_SERVE, presets.CLOSED_MIX,
     presets.SERVE_LIMITS),
], ids=["train", "serve_open", "serve_closed"])
def test_twin_family_runs_every_traffic_kind(twin, monkeypatch, like, config,
                                             mix, limits, trace):
    """No file of run.py, harness/ or layer_metrics/ knows the twin. The
    GPT-2 family's counts are broken for the run; the twin's are watched.
    The v5e's peaks stand in for the CPU's so that the readers of the
    whole-step shares run (their values mean nothing here and are not
    looked at)."""
    gpt2 = spec.family_of(presets.GPT2)
    asked = []

    def broken(*_a, **_k):
        raise AssertionError("the GPT-2 family's counts were used")

    for name in COUNTS:
        monkeypatch.setattr(gpt2, name, broken)
        monkeypatch.setattr(twin, name, lambda *a, _real=getattr(twin, name),
                            _name=name: (asked.append(_name), _real(*a))[1])
    monkeypatch.setattr(run, "_peaks", lambda *_a: device.PEAKS["TPU v5e"])
    line = run.execute("c", 2**31 + 21, 3, trace,
                       bench=presets.bench_with("c", like), config=config,
                       traffic=mix, limits=limits, rehearsal=True)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 3
    if mix["kind"] == "train":
        # `train_mfu` reads facts["flops_per_token"], which the driver asks
        # of the family in every run (a CPU step is too slow for the reader
        # to find two of them in the traced second)
        assert "train_flops_per_token" in asked
        want = {"train_step_device_ms"} if trace else {"train_tokens_per_s"}
    else:
        want = {"serve_mfu"} if trace else {"tpot_ms_p95"}
        if trace:
            assert {"prefill_flops", "decode_flops"} <= set(asked)
    assert want <= set(line["metrics"]), line["metrics"]
