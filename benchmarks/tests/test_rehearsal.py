"""Each traffic driver at a tiny preset on the CPU, through run.py's own
code path. The command never passes `rehearsal`; without it a run with no
chip exits non-zero and prints no result."""
import json
import os
import subprocess
import sys

import pytest
import presets
import run

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def check_line(line, metrics):
    assert KEYS <= set(line)
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"
    assert "tpu" not in json.dumps(line["device"]).lower()
    assert line["correct"] is True, line["checks"]
    assert set(metrics) <= set(line["metrics"]), line["metrics"]
    for m in line["metrics"].values():
        assert isinstance(m["value"], float) and m["unit"]


def test_train_rehearsal():
    line = run.execute("t", 2**31 + 11, 2, 0,
                       bench=presets.bench_with("t", "train_590m_seq2048"),
                       config=presets.TINY_TRAIN, traffic=presets.TRAIN_MIX,
                       limits=presets.LOOSE_TRAIN_LIMITS, rehearsal=True)
    check_line(line, ["train_tokens_per_s", "setup_s"])
    assert line["attempted"] > 3


def test_train_rehearsal_traced():
    line = run.execute("t", 12, 3, 1,
                       bench=presets.bench_with("t", "train_590m_seq2048"),
                       config=presets.TINY_TRAIN, traffic=presets.TRAIN_MIX,
                       limits=presets.LOOSE_TRAIN_LIMITS, rehearsal=True)
    check_line(line, ["input_wait_share", "train_step_device_ms",
                      "device_idle_share.train"])
    # shares of a peak are never printed from a CPU run: no peaks, no number
    assert "train_mfu" not in line["metrics"]
    assert "flash_roofline" not in line["metrics"]
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert len(line["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("like,mix,want", [
    ("serve_1p3b_chat", presets.OPEN_MIX, ["request_ms_mean", "tpot_ms_p95"]),
    ("serve_1p3b_batchgen", presets.CLOSED_MIX,
     ["serve_tokens_per_s", "tpot_ms_p95"]),
])
def test_serve_rehearsal(like, mix, want):
    line = run.execute("s", 5, 3, 0, bench=presets.bench_with("s", like),
                       config=presets.TINY_SERVE, traffic=mix,
                       limits=presets.SERVE_LIMITS, rehearsal=True)
    check_line(line, want + ["setup_s"])
    assert line["failed"] == 0 and line["attempted"] > 5


def test_serve_rehearsal_traced():
    line = run.execute("s", 6, 3, 1,
                       bench=presets.bench_with("s", "serve_1p3b_chat"),
                       config=presets.TINY_SERVE, traffic=presets.OPEN_MIX,
                       limits=presets.SERVE_LIMITS, rehearsal=True)
    check_line(line, ["ttft_ms_p90", "http_overhead_ms",
                      "queue_ms_p95", "decode_step_ms", "slot_occupancy",
                      "prefill_ms_per_ktok"])
    assert "serve_mfu" not in line["metrics"]
    assert "decode_step_roofline" not in line["metrics"]


def test_no_chip_no_result():
    """The command as the driver runs it, on a machine without a chip."""
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "train_590m_seq2048", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=presets.ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr
