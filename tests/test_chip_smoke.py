"""chip_smoke.py's contract, as far as a machine without a chip can show
it: no accelerator -> non-zero exit and no result line; a failing phase
-> non-zero exit; the CPU rehearsal runs every phase and never reports a
TPU. The run on the chip itself is the driver's."""

import json
import os
import subprocess
import sys

import chip_smoke

ROOT = os.path.dirname(os.path.abspath(chip_smoke.__file__))


def _run(args, tmp_path, code=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cc"))
    env.pop("XLA_FLAGS", None)  # conftest's 8 virtual devices
    cmd = ([sys.executable, "-c", code] if code
           else [sys.executable, os.path.join(ROOT, "chip_smoke.py")])
    return subprocess.run(cmd + args, env=env, cwd=ROOT, timeout=600,
                          capture_output=True, text=True)


def test_no_accelerator_fails_and_prints_no_result(tmp_path):
    out = _run([], tmp_path)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "no accelerator" in out.stderr


def test_a_phase_that_raises_fails_the_run(tmp_path):
    code = ("import sys, chip_smoke\n"
            "def boom(*a, **k): raise RuntimeError('phase failed')\n"
            "chip_smoke.phase_trainer = boom\n"
            "sys.argv = ['chip_smoke.py', '--rehearse']\n"
            "sys.exit(chip_smoke.main())\n")
    out = _run([], tmp_path, code=code)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "phase failed" in out.stderr


def test_rehearsal_runs_every_phase_and_never_reports_a_tpu(tmp_path):
    out = _run(["--rehearse"], tmp_path)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    body = "\n".join(lines[:-1])
    for phase in ("trainer:", "kernels:", "server:", "compile cache:"):
        assert phase in body
    assert "compiles after warm-up 0" in body
    assert (f"compile cache: {tmp_path / 'cc'} "
            f"(from JAX_COMPILATION_CACHE_DIR)") in body


def test_kernel_counts_reads_names_through_jvp_and_transpose():
    text = "\n".join([
        'x = custom-call(a), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(step)/jvp(flash_fwd_qkv)/pallas_call" '
        'stack_frame_id=1}',
        'y = custom-call(b), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(step)/transpose(jvp(flash_bwd_qkv))/'
        'pallas_call"}',
        'z = custom-call(c), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(f)/shard_map/softmax_xent_fwd/pallas_call"}',
        'w = custom-call(d), custom_call_target="tpu_custom_call", '
        'metadata={}',
        'v = custom-call(e), custom_call_target="Sharding"',
    ])
    assert chip_smoke.kernel_counts(text) == {
        "flash_fwd_qkv": 1, "flash_bwd_qkv": 1, "softmax_xent_fwd": 1,
        "unnamed": 1}
