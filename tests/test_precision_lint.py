"""Tier-1 gate for graftlint stage 5 (ISSUE 20): the precision-flow
audit (analysis/precision_audit.py). Proves that every stage-5 entry
point's dtype profile matches the shipped analysis/precision_budget.json
with zero P-findings, that the manifest is NON-EMPTY for the int8 decode
/ fused-sampling / fused-neg-softmax entries (the acceptance bar), that
a doctored manifest trips a named PB01 finding with a non-zero CLI exit,
that the checked-in bf16-accumulation fixture trips P001 through the
CLI, that the extras' profiles are rank-independent (and a
rank-branching dtype decision is a P005 DEADLOCK-class finding), and
that each P-rule fires on a minimal positive jaxpr and stays silent on
its disciplined twin."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from deeplearning4j_tpu.analysis import precision_audit

pytestmark = pytest.mark.lint

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(ROOT, "tools", "graftlint.py")
FIXTURE = os.path.join(ROOT, "tests", "fixtures",
                       "precision_bf16_entry.py")


def _cli_main():
    spec = importlib.util.spec_from_file_location("_graftlint_cli", CLI)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main


def _profile(fn, *args):
    import jax

    closed = jax.make_jaxpr(fn)(*args)
    return precision_audit.profile_closed(closed, "unit")


# ------------------------------------------------ the shipped entry set

@pytest.mark.parametrize("entry", precision_audit.entry_names())
def test_entry_matches_frozen_profile_with_zero_findings(entry):
    findings, profiles = precision_audit.audit([entry])
    assert not findings, "\n".join(f.format() for f in findings)
    assert profiles[entry] == precision_audit.load_budget()[entry]


def test_manifest_covers_acceptance_entries_nonempty():
    """The ISSUE 20 acceptance bar: the frozen manifest must cover the
    int8 decode, fused-sampling, and fused-neg-softmax entries with
    NON-EMPTY profiles — the stage actually sees the serving kernels,
    not just the training steps."""
    frozen = precision_audit.load_budget()
    assert set(frozen) == set(precision_audit.entry_names())

    q8 = frozen["decode_attention/q8"]
    assert q8["q8"]["dequantize"] >= 2       # k-codes AND v-codes reads
    assert any(k.startswith("int8->") for k in q8["converts"])
    assert q8["dots"], "q8 decode entry froze no dot_generals"

    upd = frozen["decode_attention/q8_update"]
    assert upd["q8"]["quantize"] >= 1        # the requantize write path
    assert upd["q8"]["dequantize"] >= 1      # the read-modify-write read

    sampling = frozen["fused_sampling/sample"]
    assert sampling["reductions"] and sampling["converts"]

    # the kernel's contractions are multiply + lane reduction (the chip's
    # compiler refuses the free-dimension-less batched dot): what must
    # hold is that they accumulate in f32
    neg = frozen["fused_neg_softmax/scores"]
    assert neg["reductions"], "neg-softmax entry froze no accumulations"
    assert all(k.endswith(":float32") for k in neg["reductions"])


def test_lm_steps_freeze_their_dot_population():
    """Every bench LM mode's train step is in the manifest with a
    non-trivial dot population — the audit walks the real training
    traces, not toy stand-ins."""
    frozen = precision_audit.load_budget()
    lm = {k: v for k, v in frozen.items() if k.startswith("lm_step/")}
    assert len(lm) >= 8
    assert all(sum(p["dots"].values()) > 0 for p in lm.values())


# ------------------------------------------------------ drift tripping

def test_profile_drift_trips_named_finding_and_cli_exit(
        tmp_path, monkeypatch, capsys):
    frozen = precision_audit.load_budget()
    doctored = {k: dict(v) for k, v in frozen.items()}
    doctored["fused_neg_softmax/scores"] = dict(
        doctored["fused_neg_softmax/scores"],
        dots={"bfloat16,bfloat16->bfloat16": 2})
    bad = tmp_path / "precision_budget.json"
    bad.write_text(json.dumps({"entries": doctored}))

    findings, _ = precision_audit.audit(
        ["fused_neg_softmax/scores"], budget_path=str(bad),
        divergence=False)
    assert [f.rule for f in findings] == ["PB01"]
    assert findings[0].path == "fused_neg_softmax/scores"
    assert findings[0].stage == "precision"
    assert "drift" in findings[0].message
    assert "dots" in findings[0].message     # names the divergent key

    # the full CLI gate must refuse the doctored manifest
    monkeypatch.setattr(precision_audit, "BUDGET_PATH", str(bad))
    assert _cli_main()(["--check", "--stage", "precision"]) == 1
    out = capsys.readouterr().out
    assert "PB01" in out and "fused_neg_softmax/scores" in out


def test_missing_profile_is_a_finding(tmp_path):
    empty = tmp_path / "precision_budget.json"
    empty.write_text(json.dumps({"entries": {}}))
    findings, _ = precision_audit.audit(
        ["fused_neg_softmax/scores"], budget_path=str(empty),
        divergence=False)
    assert [f.rule for f in findings] == ["PB01"]
    assert "--update-precision" in findings[0].fixit


# ------------------------------------------------- rank independence

def test_rank_branching_dtype_is_a_deadlock_finding():
    """A dtype decision branching on process_index compiles different
    mixed-precision programs per replica — P005, stage 3's C003 class."""

    def build():
        import jax
        import jax.numpy as jnp

        def fn(x):
            if jax.process_index() == 0:
                return jnp.sum(x.astype(jnp.float32))
            return jnp.sum(x)

        return fn, (jax.ShapeDtypeStruct((4,), "bfloat16"),)

    findings = precision_audit.check_rank_independence("toy/dtype", build)
    assert [f.rule for f in findings] == ["P005"]
    assert "DEADLOCK" in findings[0].message
    assert findings[0].stage == "precision"


def test_rank_invariant_entry_is_clean():
    assert precision_audit.check_rank_independence(
        "decode_attention/q8") == []


# --------------------------------------------- per-rule jaxpr fixtures

def test_p001_fires_on_bf16_chain_not_on_f32_accumulation():
    import jax
    import jax.numpy as jnp

    def chained(x, w):
        # jnp.sum upcasts sub-f32 inputs before reducing, so the raw
        # primitive is the only spelling of a bf16 reduce-over-dot —
        # exactly what a hand-written kernel accumulator lowers to
        return jax.lax.reduce_sum_p.bind(jnp.dot(x, w), axes=(0, 1))

    def disciplined(x, w):
        acc = jnp.dot(x, w, preferred_element_type=jnp.float32)
        return jnp.sum(acc).astype(x.dtype)

    bf = jax.ShapeDtypeStruct((16, 16), jnp.bfloat16)
    _, findings = _profile(chained, bf, bf)
    assert {f.rule for f in findings} == {"P001"}
    assert "chained" in findings[0].message
    _, findings = _profile(disciplined, bf, bf)
    assert not findings, "\n".join(f.format() for f in findings)
    # jnp.sum's own promotion already accumulates sub-f32 inputs in f32;
    # the naive spelling is silent BECAUSE it is safe, not missed
    _, findings = _profile(lambda x, w: jnp.sum(jnp.dot(x, w)), bf, bf)
    assert not findings


def test_p001_fires_on_bf16_scan_carry_not_on_f32_carry():
    import jax
    import jax.numpy as jnp

    def running(dtype):
        def fn(xs):
            def body(c, x):
                c = c + x
                return c, c
            return jax.lax.scan(body, jnp.zeros((4,), dtype), xs)
        return fn

    xs = jax.ShapeDtypeStruct((8, 4), jnp.bfloat16)
    _, findings = _profile(running(jnp.bfloat16), xs)
    assert {f.rule for f in findings} == {"P001"}
    assert "carry" in findings[0].message
    # the kernels' pattern: f32 carry, downcast after — silent (the
    # per-step convert feeds the stacked ys, so it is not P003 churn)
    def f32_carry(xs):
        def body(c, x):
            c = c + x.astype(jnp.float32)
            return c, c.astype(jnp.bfloat16)
        return jax.lax.scan(body, jnp.zeros((4,), jnp.float32), xs)
    _, findings = _profile(f32_carry, xs)
    assert not findings, "\n".join(f.format() for f in findings)


def test_p001_fires_on_bf16_cumsum():
    import jax
    import jax.numpy as jnp

    def fn(x):
        return jnp.cumsum(x)

    _, findings = _profile(fn, jax.ShapeDtypeStruct((64,), jnp.bfloat16))
    assert {f.rule for f in findings} == {"P001"}
    assert "cumulative" in findings[0].message
    _, findings = _profile(fn, jax.ShapeDtypeStruct((64,), jnp.float32))
    assert not findings


def test_p001_backward_scopes_are_exempt():
    """bf16 TRAINING traces are full of autodiff bias-grad reduce_sums
    over dot outputs; add_any (the transpose-rule fan-in) marks those
    scopes and the chain check stands down — the f32 answer there is
    master weights, not rewriting transpose rules. The bias grad below
    IS a bf16 reduce_sum directly over a dot_general; only the add_any
    gate keeps it from flagging."""
    import jax
    import jax.numpy as jnp

    def loss(x, w, b):
        y = jnp.dot(x, w) + b[None, :]   # bias grad -> backward reduce
        z = jnp.dot(y, w)
        return jnp.sum((z * z).astype(jnp.float32))  # z reused -> add_any

    bf = jax.ShapeDtypeStruct((16, 16), jnp.bfloat16)
    bv = jax.ShapeDtypeStruct((16,), jnp.bfloat16)
    closed = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(bf, bf, bv)
    prims = {e.primitive.name
             for s in precision_audit._iter_scopes(closed.jaxpr)
             for e in s.eqns}
    assert "add_any" in prims, "fixture lost its autodiff fan-in"
    assert "reduce_sum" in prims         # the bias grad is really there
    _, findings = precision_audit.profile_closed(closed, "unit")
    assert not findings, "\n".join(f.format() for f in findings)


def test_p002_raw_code_read_fires_scaled_read_does_not():
    import jax
    import jax.numpy as jnp

    def raw_read(codes):
        return jnp.sum(codes.astype(jnp.float32))

    def scaled_read(codes, scale):
        return jnp.sum(codes.astype(jnp.float32) * scale)

    i8 = jax.ShapeDtypeStruct((8, 64), jnp.int8)
    sc = jax.ShapeDtypeStruct((8, 1), jnp.float32)
    _, findings = _profile(raw_read, i8)
    assert {f.rule for f in findings} == {"P002"}
    assert "raw-code read" in findings[0].message
    _, findings = _profile(scaled_read, i8, sc)
    assert not findings, "\n".join(f.format() for f in findings)


def test_p002_unmasked_requantize_fires_masked_does_not():
    import jax
    import jax.numpy as jnp

    def rmw(masked):
        def fn(codes, scale, new, pos):
            vals = codes.astype(jnp.float32) * scale
            if masked:
                vals = jnp.where(pos < 4, new, vals)
            else:
                vals = vals + new
            maxabs = jnp.max(jnp.abs(vals), axis=-1, keepdims=True)
            # deliberately hand-rolled: the P002 requantize-write shape
            return jnp.round(
                vals / (maxabs / 127.0)  # graftlint: disable=G033
            ).astype(jnp.int8)
        return fn

    i8 = jax.ShapeDtypeStruct((8, 64), jnp.int8)
    f32 = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    sc = jax.ShapeDtypeStruct((8, 1), jnp.float32)
    pos = jax.ShapeDtypeStruct((8, 64), jnp.int32)
    _, findings = _profile(rmw(False), i8, sc, f32, pos)
    assert {f.rule for f in findings} == {"P002"}
    assert "write head" in findings[0].message
    _, findings = _profile(rmw(True), i8, sc, f32, pos)
    assert not findings, "\n".join(f.format() for f in findings)


def test_p003_round_trip_churn_fires_consumed_intermediate_does_not():
    import jax
    import jax.numpy as jnp

    def churn(x):
        return x.astype(jnp.float32).astype(jnp.bfloat16) * 2.0

    def real_value(x):
        up = x.astype(jnp.float32)
        return up.astype(jnp.bfloat16) * 2.0, jnp.sum(up)

    bf = jax.ShapeDtypeStruct((16,), jnp.bfloat16)
    profile, findings = _profile(churn, bf)
    assert {f.rule for f in findings} == {"P003"}
    assert profile["convert_round_trips"] == 1
    _, findings = _profile(real_value, bf)
    assert not findings, "\n".join(f.format() for f in findings)


def test_p004_widening_collective_fires_width_preserving_does_not():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from deeplearning4j_tpu.parallel.mesh import make_mesh
    from deeplearning4j_tpu.util.compat import shard_map

    mesh = make_mesh({"data": 2})

    def sharded(local):
        return lambda x: shard_map(local, mesh=mesh, in_specs=(P("data"),),
                                   out_specs=P("data"),
                                   check_vma=False)(x)

    bf = jax.ShapeDtypeStruct((4, 8), jnp.bfloat16)
    f32 = jax.ShapeDtypeStruct((4, 8), jnp.float32)

    # bf16 entry upcast before the psum: widened bytes on the wire
    widened = sharded(lambda v: jax.lax.psum(v.astype(jnp.float32),
                                             "data"))
    _, findings = _profile(widened, bf)
    assert {f.rule for f in findings} == {"P004"}
    assert "wire" in findings[0].message

    # width-preserving f32 psum over an f32 entry: clean
    plain = sharded(lambda v: jax.lax.psum(v, "data"))
    _, findings = _profile(plain, f32)
    assert not findings, "\n".join(f.format() for f in findings)

    # a bf16 psum is the OTHER failure: a sub-f32 cross-replica sum
    _, findings = _profile(plain, bf)
    assert {f.rule for f in findings} == {"P001"}
    assert "cross-replica" in findings[0].message


# --------------------------------------------------------------- CLI

def test_cli_precision_demo_exits_nonzero_with_p001():
    """The acceptance demo: `--stage precision` on the bf16-accumulation
    fixture must exit non-zero with the P001 chain finding."""
    proc = subprocess.run(
        [sys.executable, CLI, "--check", "--stage", "precision", FIXTURE],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "P001" in proc.stdout
    assert "demo/bf16_carry_over_dot" in proc.stdout


def test_fixture_audit_in_process():
    findings, profiles = precision_audit.audit_paths([FIXTURE])
    assert [f.rule for f in findings] == ["P001"]
    assert "carry" in findings[0].message
    prof = profiles["demo/bf16_carry_over_dot"]
    assert prof["dots"] == {"bfloat16,bfloat16->bfloat16": 1}
    assert prof["scan_carries"] == {"bfloat16": 1}


def test_cli_precision_clean_tree_emits_labeled_json():
    proc = subprocess.run(
        [sys.executable, CLI, "--check", "--stage", "precision", "--json"],
        cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["findings"] == []
    profiles = payload["precision_profiles"]
    assert set(profiles) == set(precision_audit.entry_names())
    assert profiles["decode_attention/q8"]["q8"]["dequantize"] >= 2


def test_cli_changed_bad_ref_is_a_usage_error():
    proc = subprocess.run(
        [sys.executable, CLI, "--check", "--changed",
         "0000000000000000000000000000000000000000"],
        cwd=ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2, proc.stdout + proc.stderr
