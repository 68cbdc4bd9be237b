"""Off-TPU compile smoke for the bench's Transformer-LM modes (VERDICT
r5 #1): the r5 `transformer_large` mode crashed ONLY under driver capture
because no CI path ever built the d1024 model — its CPU branch printed a
skip line and returned. Here every mode in bench.LM_MODE_DIMS is built at
its REAL (TPU) dims and its training step is traced end-to-end with
jax.eval_shape (fwd + bwd + optimizer, no FLOPs executed), so a mode that
cannot even trace fails tier-1, not the round artifact.

This is also where the r6 tentpole's end-to-end acceptance lives off-TPU:
`longcontext_chunked_dropout` (masked + attention dropout at seq 32768)
must trace through the chunked flash dispatch — in r5 that config raised
chunked_unsupported_reason.
"""

import os

import numpy as np

import jax
import pytest

import bench
from bench import LM_MODE_DIMS, lm_mode_net_ds


def _trace_step(mode):
    net, ds, cfg = lm_mode_net_ds(mode, force_tpu_dims=True)
    batch = net._batch_dict(net._to_mds(ds))
    step = net._get_train_step()
    out = jax.eval_shape(step, net.params, net.opt_state, net.state,
                         jax.random.PRNGKey(0), batch)
    return out, cfg


@pytest.mark.parametrize("mode", sorted(LM_MODE_DIMS))
def test_lm_mode_builds_and_traces_at_real_dims(mode):
    (params, opt_state, state, loss, _), cfg = _trace_step(mode)
    assert loss.shape == ()
    # the traced model really is the TPU config, not a CPU shrink
    emb = params["embed"]["W"] if "embed" in params else None
    if emb is not None:
        assert emb.shape[-1] == cfg["d_model"]


@pytest.mark.parametrize("mode", ["transformer", "transformer_large"])
def test_lm_mode_scanned_fit_path_traces_at_real_dims(mode):
    """The bench times `_time_net_steps` -> fit_scanned (the whole-epoch
    lax.scan over the jitted step), a path the bare-step smoke above
    does not reach — the r5 transformer_large crash class lived exactly
    in "works when the author tried a step, dies in the sweep's stock
    fit path". Trace the scan end-to-end at REAL dims."""
    from deeplearning4j_tpu.nn.training import make_scanned_fit, stack_batches

    net, ds, cfg = lm_mode_net_ds(mode, force_tpu_dims=True)
    batch = net._batch_dict(net._to_mds(ds))
    stacked = stack_batches([batch])
    run = make_scanned_fit(net._get_train_step())
    params, _, _, losses = jax.eval_shape(
        lambda *a: run(*a, n_epochs=2),
        net.params, net.opt_state, net.state, jax.random.PRNGKey(0),
        stacked)
    assert losses.shape == (2, 1)
    assert params["embed"]["W"].shape[-1] == cfg["d_model"]


@pytest.mark.slow
def test_transformer_large_real_dims_executes_one_step():
    """Execute (not just trace) the d1024/8-head/d_ff-4096 config at the
    REAL model dims through the same fit_scanned path the bench times —
    interpret-mode kernels off-TPU, batch shrunk to 2 to keep the run in
    the slow-tier budget. A d1024 path that only breaks at execution
    time fails here, not in the round artifact."""
    from deeplearning4j_tpu.datasets.api import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.models.transformer import transformer_lm

    cfg = LM_MODE_DIMS["transformer_large"]
    batch = 2
    net = transformer_lm(
        vocab_size=bench.VOCAB_LM, d_model=cfg["d_model"],
        n_heads=cfg["n_heads"], n_layers=cfg.get("n_layers", 6),
        d_ff=cfg["d_ff"], max_length=cfg["seq"], dtype="bfloat16")
    net.init()
    rng = np.random.default_rng(0)
    toks = np.asarray(rng.integers(0, bench.VOCAB_LM, (batch, cfg["seq"])),
                      np.int32)
    ds = DataSet(toks, np.roll(toks, -1, axis=1))
    net.fit_scanned(ListDataSetIterator([ds]), epochs=1)
    assert np.isfinite(net.score_value)


# ------------------------------------------------- causal FLOP accounting

def test_causal_flop_formula_pinned_at_two_sequence_lengths():
    """VERDICT r5 #4 / ISSUE 7 satellite: the executed-FLOPs accounting
    must count exactly T(T+1)/2 causal (query, key) pairs — not the
    dense T^2 and not the 0.5 approximation. Pinned against the closed
    form at both the flagship and the chunked-path sequence lengths."""
    from deeplearning4j_tpu.models.transformer import (
        causal_attention_factor,
        transformer_flops_per_token,
        transformer_flops_per_token_executed,
    )

    V, d, L, dff = 10000, 256, 6, 1024
    for T in (512, 32768):
        factor = causal_attention_factor(T)
        assert factor == (T + 1) / (2.0 * T)
        # exact closed form of the executed count
        per_layer = (4 * 2 * d * d + 2 * 2 * d * dff
                     + factor * 2 * 2 * T * d)
        want = int(3 * (L * per_layer + 2 * d * V))
        got = transformer_flops_per_token_executed(V, d, L, dff, T)
        assert got == want
        dense = transformer_flops_per_token(V, d, L, dff, T)
        # causal executes T(T+1)/2 of the dense T^2 attention pairs
        attn_dense = 3 * L * 2 * 2 * T * d
        assert dense - got == int(round(attn_dense * (1 - factor)))
        assert got < dense
        # non-causal executes the full dense matrix
        assert transformer_flops_per_token_executed(
            V, d, L, dff, T, causal=False) == dense
    # the inflation the dense convention buys grows with T: ~12% of the
    # attention-dominated total at 32k vs ~4% at 512
    r512 = (transformer_flops_per_token(V, d, L, dff, 512)
            / transformer_flops_per_token_executed(V, d, L, dff, 512))
    r32k = (transformer_flops_per_token(V, d, L, dff, 32768)
            / transformer_flops_per_token_executed(V, d, L, dff, 32768))
    assert r32k > 1.8 > 1.2 > r512 > 1.0


def test_every_lm_mode_is_runnable_from_the_cli():
    """Each registry entry is wired to a MODES command (and vice versa
    for the LM family), so the smoke can't drift from what the driver
    actually runs."""
    for mode in LM_MODE_DIMS:
        assert mode in bench.MODES, mode


def test_dropout_seq32768_cfg_is_the_tentpole_config():
    cfg = LM_MODE_DIMS["longcontext_chunked_dropout"]
    assert cfg["seq"] == 32768 and cfg["attention_dropout"] > 0
    assert cfg["masked"]


_REAL_RUN = bench.subprocess.run


def _fake_mode_run(argv, env=None, capture_output=True, text=True,
                   timeout=None):
    """Fake subprocess.run for the sweep loop: one clean mode, one
    deterministic crasher, one wall-clock timeout. The sweep's OWN
    tracetool self-audit passes through to the real CLI — the check
    over the fake sweep's telemetry is part of the contract."""
    import subprocess as sp
    import json as _json
    if any("tracetool" in str(a) for a in argv):
        return _REAL_RUN(argv, env=env, capture_output=capture_output,
                         text=text, timeout=timeout)
    mode = argv[-1]

    class Out:
        def __init__(self, rc, stdout="", stderr=""):
            self.returncode, self.stdout, self.stderr = rc, stdout, stderr

    if mode == "ok":
        return Out(0, stdout=_json.dumps(
            {"metric": "ok", "value": 1.0, "unit": "x"}) + "\n")
    if mode == "crashy":
        return Out(1, stderr="Traceback (most recent call last):\n"
                             "ValueError: boom at real dims\n")
    raise sp.TimeoutExpired(argv, timeout, stderr=b"partial child stderr")


def test_sweep_classifies_env_failures_off_tpu(monkeypatch, tmp_path):
    """ROADMAP "get the sweep to rc=0": OFF-TPU, a mode lost to the
    environment (the vgg16 CPU-contention timeout class, or any per-mode
    crash) becomes a skipped-env metric line with the FULL stderr in
    telemetry — the sweep exits 0 and the summary names what was
    skipped."""
    import json as _json
    from deeplearning4j_tpu.telemetry import set_default

    monkeypatch.setattr(bench.subprocess, "run", _fake_mode_run)
    monkeypatch.setattr(bench, "_probe_backend", lambda: "cpu")
    monkeypatch.setattr(bench, "MODES", {"ok": None, "crashy": None,
                                         "slow": None})
    tpath = tmp_path / "tel.jsonl"
    monkeypatch.setenv("DL4J_TPU_TELEMETRY", str(tpath))
    monkeypatch.setenv("DL4J_TPU_TRACE_ARTIFACT",
                       str(tmp_path / "TRACE_test.json"))
    try:
        rc = bench._run_all()
    finally:
        set_default(None)
    assert rc == 0
    # the self-audit rows rode the sweep record (clean run: 0 findings)
    assert (tmp_path / "TRACE_test.json").exists()
    events = [_json.loads(line) for line in open(tpath)]
    errors = [e for e in events if e["event"] == "error"]
    # full stderr survives in telemetry even though the sweep passed
    assert any("skipped-env" in e["error"]
               and "boom at real dims" in e["traceback"] for e in errors)
    assert any("skipped-env" in e["error"]
               and "partial child stderr" in e["traceback"]
               for e in errors)
    metrics = [e for e in events if e["event"] == "metric"]
    skip_lines = {e["metric"]: e["skipped"] for e in metrics
                  if "skipped" in e}
    assert set(skip_lines) == {"crashy", "slow"}
    assert all(s.startswith("env: off-TPU") for s in skip_lines.values())
    summary = [e for e in metrics if e.get("metric") == "summary"][-1]
    assert sorted(summary["skipped_env"]) == ["crashy", "slow"]
    assert summary.get("ok") == 1.0


def test_sweep_still_fails_on_tpu(monkeypatch, tmp_path):
    """ON the real chip the same failures keep rc=1 — skipped-env is an
    off-TPU smoke classification, not a blanket amnesty."""
    from deeplearning4j_tpu.telemetry import set_default

    monkeypatch.setattr(bench.subprocess, "run", _fake_mode_run)
    monkeypatch.setattr(bench, "_probe_backend", lambda: "tpu")
    monkeypatch.setattr(bench, "MODES", {"ok": None, "crashy": None})
    monkeypatch.setenv("DL4J_TPU_TELEMETRY", str(tmp_path / "tel.jsonl"))
    monkeypatch.setenv("DL4J_TPU_TRACE_ARTIFACT",
                       str(tmp_path / "TRACE_test.json"))
    try:
        rc = bench._run_all()
    finally:
        set_default(None)
    assert rc == 1


def test_sweep_trace_check_gates_on_fleet_rank_skew(monkeypatch, tmp_path):
    """ISSUE 15 CI satellite: the sweep audits its own telemetry — a
    rank-skew (or hang) left in the fleet modes' .pN shards fails the
    sweep with rc=1 even when every mode exited 0."""
    import json as _json
    from deeplearning4j_tpu.telemetry import set_default

    monkeypatch.setattr(bench.subprocess, "run", _fake_mode_run)
    monkeypatch.setattr(bench, "_probe_backend", lambda: "cpu")
    monkeypatch.setattr(bench, "MODES", {"ok": None})
    tpath = tmp_path / "tel.jsonl"
    monkeypatch.setenv("DL4J_TPU_TELEMETRY", str(tpath))
    monkeypatch.setenv("DL4J_TPU_TRACE_ARTIFACT",
                       str(tmp_path / "TRACE_test.json"))
    # a fleet mode's shard pair with the pN:hang@stepK signature: p1
    # stops at step 2 while p0 runs on (minutes of silence)
    for proc, last in (("p0", 6), ("p1", 2)):
        with open(f"{tpath}.{proc}", "w") as fh:
            for s in range(1, last + 1):
                fh.write(_json.dumps(
                    {"event": "step", "run": proc, "seq": s,
                     "iteration": s, "ts": 1000.0 + s * 60.0,
                     "trace_id": f"step-{s}"}) + "\n")
    try:
        rc = bench._run_all()
    finally:
        set_default(None)
    assert rc == 1
    events = [_json.loads(line) for line in open(tpath)]
    anomalies = [e for e in events if e["event"] == "anomaly"]
    assert anomalies and anomalies[0]["kind"] == "straggler"
    skew_rows = [e for e in events if e.get("metric")
                 == "straggler_skew_ms"]
    assert skew_rows and skew_rows[-1]["value"] > 0


def test_embed_mode_registered_and_smoke_runs():
    """ISSUE 19: the embed bench mode is in the sweep and a toy-sized
    `_embed_run` passes its structural gates — zero post-warmup
    retraces on both the train and /search paths, the ep=2 memstat
    table-bytes ratio at exactly 0.5, exact /embed rows, and every row
    family the benchdiff baseline tracks present in the output. The
    5x ANN speedup floor is a full-size (`python bench.py embed`)
    gate; at toy sizes brute force wins and that is expected."""
    assert "embed" in bench.MODES
    cfg = dict(bench.EMBED_DIMS, vocab=2048, dim=32, n_partitions=64,
               n_clusters=64, batch=256, train_steps=3, query_batch=16,
               qps_reps=3)
    out = bench._embed_run(cfg)
    g = out["gates"]
    assert g["train_retraces"] == 0 and g["search_retraces"] == 0
    assert g["sharding_ratio"] == 0.5
    assert g["embed_exact"]
    assert g["recall"] >= cfg["recall_floor"]
    names = {row["metric"] for row in out["lines"]}
    for family in ("embed_queries_per_sec", "embed_recall_at_k",
                   "embed_scatter_add_us", "embed_ep2_ep_gather_bytes",
                   "embed_mem_table_bytes_ep1", "embed_mem_table_bytes_ep2",
                   "embed_brute_force_queries_per_sec",
                   "embed_ann_speedup_vs_brute"):
        assert family in names, family


def test_failed_backend_probe_is_an_error_not_a_skippable_backend(
        monkeypatch):
    """`_run_all` turns crashed modes into rc-0 'skipped-env' lines when
    the probed backend is not tpu; a probe that FAILS must therefore
    raise, never read as some other backend."""
    import subprocess

    class _Out:
        returncode, stdout, stderr = 1, "", "RuntimeError: no backend"

    monkeypatch.setattr(subprocess, "run", lambda *a, **k: _Out())
    with pytest.raises(RuntimeError, match="backend probe failed"):
        bench._probe_backend()

    class _Ok:
        returncode, stdout, stderr = 0, "cpu\n", ""

    monkeypatch.setattr(subprocess, "run", lambda *a, **k: _Ok())
    assert bench._probe_backend() == "cpu"


def test_bench_parent_imports_do_not_initialize_a_backend():
    """A chip belongs to one process: the sweep parent imports bench and
    the telemetry package, and must leave the backend to its children."""
    import subprocess
    import sys

    code = ("import bench\n"
            "from deeplearning4j_tpu.telemetry import Recorder, set_default\n"
            "from deeplearning4j_tpu.telemetry.artifact import "
            "build_summary\n"
            "from jax._src import xla_bridge as xb\n"
            "print(xb.backends_are_initialized())\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         cwd=os.path.dirname(os.path.abspath(bench.__file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "False"
