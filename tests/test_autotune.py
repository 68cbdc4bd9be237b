"""Tier-1 gate for the kernel autotuning layer (ISSUE 8 tentpole):
table schema round-trip, unknown-key fallback to the deterministic
heuristics, interpret-mode parity (tuned vs default block sizes produce
bit-identical kernel outputs for fwd AND grad), the kerneltune sweep's
match-or-beat contract + kernel_tune telemetry, and the off-TPU
bit-identity contract (the checked-in table must NOT activate here)."""

import json
import os
import subprocess
import sys

import numpy as np

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.ops import autotune

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KERNELTUNE = os.path.join(ROOT, "tools", "kerneltune.py")
BENCHDIFF = os.path.join(ROOT, "tools", "benchdiff.py")


def _qkv(B=2, H=2, T=256, D=32, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((B, H, T, D)) * 0.3,
                             jnp.float32) for _ in range(3))


# ------------------------------------------------------- schema round-trip

class TestTableSchema:
    def test_key_roundtrip(self):
        key = autotune.config_key("flash_fwd", 512, 64, causal=True,
                                  dropout=False, masked=True)
        assert key == "flash_fwd|T512|D64|c1|d0|m1"
        cfg = autotune.parse_key(key)
        assert cfg == {"kernel": "flash_fwd", "T": 512, "D": 64,
                       "causal": True, "dropout": False, "masked": True}

    def test_valid_table_roundtrips_through_disk(self, tmp_path):
        table = {"version": autotune.SCHEMA_VERSION,
                 "provenance": {"tool": "test", "backend": "cpu"},
                 "entries": {
                     "flash_fwd|T512|D64|c1|d0|m0":
                         {"block_q": 256, "block_k": 512, "g": 2,
                          "best_us": 10, "default_us": 12},
                     "fused_layer_norm|T1024|D512|c0|d0|m0":
                         {"rows": 256},
                 }}
        assert autotune.validate_table(table) == []
        path = tmp_path / "table.json"
        path.write_text(json.dumps(table))
        loaded = autotune.load_table(str(path))
        assert loaded["entries"] == table["entries"]
        # cache: same path returns the cached object, reload re-reads
        assert autotune.load_table(str(path)) is loaded
        autotune.reload_table(autotune.TABLE_PATH)  # restore default

    def test_invalid_tables_name_their_problems(self, tmp_path):
        bad_version = {"version": 99, "entries": {}}
        assert any("version" in p
                   for p in autotune.validate_table(bad_version))
        bad_key = {"version": 1, "entries": {"nonsense": {}}}
        assert any("malformed" in p
                   for p in autotune.validate_table(bad_key))
        bad_kernel = {"version": 1, "entries":
                      {"warp_drive|T1|D1|c0|d0|m0": {}}}
        assert any("unknown kernel" in p
                   for p in autotune.validate_table(bad_kernel))
        bad_param = {"version": 1, "entries":
                     {"flash_fwd|T512|D64|c1|d0|m0": {"rows": 8}}}
        assert any("not tunable" in p
                   for p in autotune.validate_table(bad_param))
        bad_value = {"version": 1, "entries":
                     {"flash_fwd|T512|D64|c1|d0|m0": {"block_q": -4}}}
        assert any("positive int" in p
                   for p in autotune.validate_table(bad_value))
        # a malformed checked-in file fails at LOAD, not mid-compile
        path = tmp_path / "broken.json"
        path.write_text(json.dumps(bad_param))
        with pytest.raises(ValueError, match="invalid tuning table"):
            autotune.load_table(str(path))
        autotune.reload_table(autotune.TABLE_PATH)

    def test_checked_in_table_is_valid(self):
        table = autotune.reload_table(autotune.TABLE_PATH)
        assert autotune.validate_table(table) == []
        assert table["provenance"].get("tool") == "tools/kerneltune.py"
        # every entry matches-or-beats its own default micro-bench
        for key, e in table["entries"].items():
            if "best_us" in e and "default_us" in e:
                assert e["best_us"] <= e["default_us"], key


# ------------------------------------------------- fallback + resolution

class TestResolution:
    def test_unknown_key_falls_back_to_heuristics(self):
        with autotune.override({}):  # no table, no override
            assert autotune.flash_blocks(
                512, 64, causal=True, dropout=False, masked=False) == \
                (512, 512)
            assert autotune.flash_blocks(
                4096, 64, causal=True, dropout=False, masked=False) == \
                (512, 512)
            assert autotune.flash_g("flash_fwd", 8, 512, 64, causal=True,
                                    dropout=False, masked=False) is None
            assert autotune.ln_rows(1024, 512) == 512
            assert autotune.xent_blocks(2048, 256, 10240) == (1024, 2048)

    def test_off_tpu_table_is_inactive(self):
        """The bit-identity contract: off-TPU, checked-in entries never
        apply (DL4J_TPU_TUNING unset) — interpret runs equal HEAD."""
        assert jax.default_backend() != "tpu"
        assert os.environ.get(autotune.ENV_TUNING) in (None, "")
        assert not autotune.table_active()
        assert autotune.lookup("flash_fwd", 512, 64, causal=True) is None

    def test_env_force_and_off(self, monkeypatch, tmp_path):
        table = {"version": 1, "provenance": {},
                 "entries": {"flash_fwd|T512|D64|c1|d0|m0":
                             {"block_q": 256, "block_k": 256, "g": 1}}}
        path = tmp_path / "t.json"
        path.write_text(json.dumps(table))
        monkeypatch.setattr(autotune, "TABLE_PATH", str(path))
        autotune.reload_table(str(path))
        try:
            monkeypatch.setenv(autotune.ENV_TUNING, "force")
            assert autotune.table_active()
            e = autotune.lookup("flash_fwd", 512, 64, causal=True)
            assert e == {"block_q": 256, "block_k": 256, "g": 1}
            monkeypatch.setenv(autotune.ENV_TUNING, "off")
            assert not autotune.table_active()
            assert autotune.lookup("flash_fwd", 512, 64,
                                   causal=True) is None
        finally:
            autotune.reload_table(autotune.TABLE_PATH)

    def test_invalid_entry_params_fall_back(self):
        """A tuned block that does not divide T (or a G that does not
        divide BH) must never reach a kernel grid."""
        with autotune.override({"flash_fwd": {"block_q": 384,
                                              "block_k": 512, "g": 3}}):
            assert autotune.flash_blocks(
                512, 64, causal=True, dropout=False, masked=False) == \
                (512, 512)
            assert autotune.flash_g("flash_fwd", 8, 512, 64, causal=True,
                                    dropout=False, masked=False) is None
        with autotune.override({"fused_layer_norm": {"rows": 320}}):
            assert autotune.ln_rows(1024, 512) == 512  # 320 not lane-tile
        with autotune.override({"flash_chunk": {"chunk": 640}}):
            from deeplearning4j_tpu.ops.flash_attention import (
                chunked_flash_attention_lse,
            )
            q = jnp.zeros((1, 1024, 32), jnp.float32)
            # invalid tuned chunk -> heuristic pick, no raise
            jax.eval_shape(lambda q: chunked_flash_attention_lse(
                q, q, q, 1.0, True), q)

    def test_max_tile_for_dim_envelope(self):
        assert autotune.max_tile_for_dim(None) == 8192
        assert autotune.max_tile_for_dim(128) == 8192
        assert autotune.max_tile_for_dim(256) == 4096
        for D in (64, 128, 160, 256, 384, 512, 1024):
            tile = autotune.max_tile_for_dim(D)
            assert tile * max(D, 128) <= autotune.TILE_ELEM_BUDGET

    def test_tuned_chunk_resolves_through_dispatch(self):
        """A valid flash_chunk entry changes the tile the loop picks."""
        from deeplearning4j_tpu.ops.flash_attention import (
            chunked_flash_attention_lse,
        )

        q = jnp.zeros((1, 1024, 32), jnp.float32)

        def n_outputs(fn):
            out = jax.eval_shape(fn, q)
            return out[0].shape

        with autotune.override({"flash_chunk": {"chunk": 256}}):
            shape = n_outputs(lambda q: chunked_flash_attention_lse(
                q, q, q, 1.0, True))
            assert shape == (1, 1024, 32)


# -------------------------------------------------- interpret-mode parity

class TestTunedParity:
    """Tuned vs default block sizes through the REAL dispatch.
    G-batching is pure batching (per-slice math unchanged), so fwd AND
    grad are BIT-identical; block re-tiling keeps per-row reductions but
    hands XLA different matmul shapes (different CPU micro-kernel/
    threading choices), so it gets a float32-epsilon allclose bound plus
    a correctness check against the dense reference."""

    def _run(self, dropout=0.0, mask=None):
        from deeplearning4j_tpu.ops.flash_attention import flash_attention
        q, k, v = _qkv()
        kw = {}
        if dropout:
            kw = dict(dropout=dropout, dropout_rng=jax.random.PRNGKey(3))
        if mask is not None:
            kw["mask"] = mask

        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True, **kw)
                           ** 2)

        o = flash_attention(q, k, v, causal=True, **kw)
        g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return o, g

    @pytest.mark.parametrize("dropout", [0.0, 0.2])
    def test_g_variants_bit_identical(self, dropout):
        o0, g0 = self._run(dropout=dropout)
        variants = [
            {"flash_fwd": {"block_q": 256, "block_k": 256, "g": 1}},
            {"flash_fwd": {"block_q": 256, "block_k": 256, "g": 2}},
            {"flash_bwd": {"block_q": 256, "block_k": 256, "g": 2}},
            {"flash_fwd": {"block_q": 256, "block_k": 256, "g": 4},
             "flash_bwd": {"block_q": 256, "block_k": 256, "g": 1}},
        ]
        for ov in variants:
            with autotune.override(ov):
                o1, g1 = self._run(dropout=dropout)
            assert bool(jnp.all(o0 == o1)), ov
            for a, b in zip(g0, g1):
                assert bool(jnp.all(a == b)), ov

    @pytest.mark.parametrize("dropout", [0.0, 0.2])
    def test_block_retiling_allclose(self, dropout):
        o0, g0 = self._run(dropout=dropout)
        variants = [
            {"flash_fwd": {"block_q": 128, "block_k": 256, "g": 1}},
            {"flash_fwd": {"block_q": 256, "block_k": 128, "g": 1},
             "flash_bwd": {"block_q": 256, "block_k": 128, "g": 1}},
            {"flash_bwd": {"block_q": 128, "block_k": 256, "g": 1}},
        ]
        for ov in variants:
            with autotune.override(ov):
                o1, g1 = self._run(dropout=dropout)
            np.testing.assert_allclose(np.asarray(o0), np.asarray(o1),
                                       atol=2e-6, err_msg=str(ov))
            for a, b in zip(g0, g1):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=2e-5, err_msg=str(ov))

    def test_block_q_over_block_k_is_correct(self):
        """The r8 causal key-block bound fix: a tuned block_q LARGER
        than block_k must still attend every needed key block (the old
        `qi*bq//bk + 1` bound silently dropped them)."""
        from deeplearning4j_tpu.nn.layers.attention import (
            dot_product_attention,
        )
        q, k, v = _qkv(T=256)
        ref = dot_product_attention(q, k, v, causal=True)
        from deeplearning4j_tpu.ops.flash_attention import flash_attention
        with autotune.override({"flash_fwd": {"block_q": 256,
                                              "block_k": 128, "g": 1}}):
            out = flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_ln_and_xent_variants_bit_identical(self):
        from deeplearning4j_tpu.ops.fused_layernorm import fused_layer_norm
        from deeplearning4j_tpu.ops.fused_softmax_xent import (
            softmax_xent_head,
        )
        rng = np.random.default_rng(5)
        x = jnp.asarray(rng.standard_normal((512, 256)), jnp.float32)
        g = jnp.asarray(rng.standard_normal((256,)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((256,)), jnp.float32)
        y0 = fused_layer_norm(x, g, b)
        d0 = jax.grad(lambda x: jnp.sum(fused_layer_norm(x, g, b) ** 2))(x)
        with autotune.override({"fused_layer_norm": {"rows": 128}}):
            y1 = fused_layer_norm(x, g, b)
            d1 = jax.grad(lambda x: jnp.sum(
                fused_layer_norm(x, g, b) ** 2))(x)
        assert bool(jnp.all(y0 == y1))
        assert bool(jnp.all(d0 == d1))

        xx = jnp.asarray(rng.standard_normal((256, 128)) * 0.2,
                         jnp.float32)
        w = jnp.asarray(rng.standard_normal((128, 2560)) * 0.05,
                        jnp.float32)
        bb = jnp.zeros((2560,), jnp.float32)
        lab = jnp.asarray(rng.integers(0, 2560, (256,)), jnp.int32)
        l0 = softmax_xent_head(xx, w, bb, lab)
        gw0 = jax.grad(lambda w: softmax_xent_head(xx, w, bb, lab).sum())(w)
        # block_n re-tiling re-partitions rows: per-token loss is
        # bit-identical; dW re-groups the cross-row accumulation, so it
        # gets the allclose bound
        with autotune.override({"softmax_xent": {"block_n": 128,
                                                 "block_v": 2048}}):
            l1 = softmax_xent_head(xx, w, bb, lab)
            gw1 = jax.grad(lambda w: softmax_xent_head(
                xx, w, bb, lab).sum())(w)
        assert bool(jnp.all(l0 == l1))
        np.testing.assert_allclose(np.asarray(gw0), np.asarray(gw1),
                                   atol=2e-5)
        # block_v re-chunks the online logsumexp: allclose bound
        with autotune.override({"softmax_xent": {"block_n": 256,
                                                 "block_v": 1024}}):
            l2 = softmax_xent_head(xx, w, bb, lab)
        np.testing.assert_allclose(np.asarray(l0), np.asarray(l2),
                                   atol=2e-5)


# ------------------------------------------------------ kerneltune sweep

class TestKernelTune:
    def _kt(self):
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        try:
            import kerneltune
        finally:
            sys.path.pop(0)
        return kerneltune

    def test_sweep_match_or_beat_and_telemetry(self, tmp_path):
        """A real (tiny) sweep through the real kernels: every entry
        matches-or-beats its default in the harness's own micro-bench,
        and every measurement leaves a typed kernel_tune event."""
        from deeplearning4j_tpu.telemetry.recorder import Recorder

        kerneltune = self._kt()
        cfgs = [dict(family="flash_fwd", B=1, H=2, T=256, D=16,
                     causal=True, dropout=False, masked=False),
                dict(family="fused_layer_norm", N=256, C=128)]
        rec = Recorder(str(tmp_path / "tel.jsonl"))
        entries = kerneltune.sweep(cfgs, repeats=1, margin=0.03,
                                   recorder=rec, trust_wins=True)
        rec.close()
        assert set(entries) == {
            "flash_fwd|T256|D16|c1|d0|m0",
            "fused_layer_norm|T256|D128|c0|d0|m0"}
        for key, e in entries.items():
            assert e["best_us"] <= e["default_us"], key
        events = [json.loads(line)
                  for line in open(tmp_path / "tel.jsonl")]
        kt = [e for e in events if e["event"] == "kernel_tune"]
        roles = {e["role"] for e in kt}
        assert roles == {"default", "candidate", "chosen"}
        assert all("params" in e and "seconds" in e for e in kt)
        # the table the sweep would write is schema-valid
        table = {"version": autotune.SCHEMA_VERSION, "provenance": {},
                 "entries": entries}
        assert autotune.validate_table(table) == []

    def test_off_tpu_wins_do_not_displace_defaults(self, tmp_path):
        """trust_wins=False (the off-TPU CLI default): candidates are
        timed but the written params are the deterministic defaults."""
        from deeplearning4j_tpu.telemetry.recorder import NullRecorder

        kerneltune = self._kt()
        cfgs = [dict(family="flash_fwd", B=1, H=2, T=256, D=16,
                     causal=True, dropout=False, masked=False)]
        entries = kerneltune.sweep(cfgs, repeats=1, margin=0.03,
                                   recorder=NullRecorder(),
                                   trust_wins=False)
        (entry,) = entries.values()
        dflt = kerneltune.default_params(cfgs[0])
        assert {k: entry[k] for k in dflt} == dflt

    def test_cli_dry_run_lists_configs(self):
        proc = subprocess.run(
            [sys.executable, KERNELTUNE, "--quick", "--dry-run"],
            cwd=ROOT, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "flash_fwd|T256" in proc.stdout
        assert "candidates" in proc.stdout


# -------------------------------------------------- benchdiff integration

class TestBenchdiffTables:
    def _tables(self, tmp_path):
        old = {"version": 1, "provenance": {"date": "a"}, "entries": {
            "flash_fwd|T512|D64|c1|d0|m0":
                {"block_q": 512, "block_k": 512, "g": 8,
                 "best_us": 129, "default_us": 263},
            "softmax_xent|T10240|D256|c0|d0|m0":
                {"block_n": 1024, "block_v": 2048,
                 "best_us": 100, "default_us": 100},
        }}
        import copy
        new = copy.deepcopy(old)
        new["entries"]["flash_fwd|T512|D64|c1|d0|m0"].update(
            block_q=256, best_us=110)
        new["entries"]["fused_layer_norm|T2048|D512|c0|d0|m0"] = {
            "rows": 512, "best_us": 10, "default_us": 10}
        op, np_ = tmp_path / "old.json", tmp_path / "new.json"
        op.write_text(json.dumps(old))
        np_.write_text(json.dumps(new))
        return old, new, str(op), str(np_)

    def test_diff_names_changed_entries(self, tmp_path):
        sys.path.insert(0, os.path.join(ROOT, "tools"))
        try:
            import benchdiff
        finally:
            sys.path.pop(0)
        old, new, _, _ = self._tables(tmp_path)
        result = benchdiff.diff_tables(old, new)
        assert not result["regressions"]
        fields = {(r["metric"], r["field"]) for r in result["changes"]}
        assert ("flash_fwd|T512|D64|c1|d0|m0", "params") in fields
        assert ("flash_fwd|T512|D64|c1|d0|m0", "best_us") in fields
        assert result["added"] == ["fused_layer_norm|T2048|D512|c0|d0|m0"]
        # timing regression: best_us GROWS past threshold
        new["entries"]["flash_fwd|T512|D64|c1|d0|m0"]["best_us"] = 260
        result = benchdiff.diff_tables(old, new)
        assert any(r["field"] == "best_us" and "lower-is-better"
                   in r["reason"] for r in result["regressions"])
        # match-or-beat violation always regresses
        new["entries"]["softmax_xent|T10240|D256|c0|d0|m0"][
            "best_us"] = 150
        result = benchdiff.diff_tables(old, new)
        assert any("match-or-beat" in r["reason"]
                   for r in result["regressions"])

    def test_cli_diffs_tables_and_gates(self, tmp_path):
        _, new, op, npath = self._tables(tmp_path)
        proc = subprocess.run(
            [sys.executable, BENCHDIFF, op, npath], cwd=ROOT,
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "flash_fwd|T512|D64|c1|d0|m0" in proc.stdout
        # regressing table exits 1
        new["entries"]["flash_fwd|T512|D64|c1|d0|m0"]["best_us"] = 400
        (tmp_path / "new.json").write_text(json.dumps(new))
        proc = subprocess.run(
            [sys.executable, BENCHDIFF, op, npath], cwd=ROOT,
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 1
        assert "REGRESSED" in proc.stdout
        # mixed table-vs-bench artifact is a usage error
        bench_art = tmp_path / "bench.txt"
        bench_art.write_text(json.dumps(
            {"metric": "lenet", "value": 1.0, "unit": "x"}) + "\n")
        proc = subprocess.run(
            [sys.executable, BENCHDIFF, op, str(bench_art)], cwd=ROOT,
            capture_output=True, text=True, timeout=120)
        assert proc.returncode == 2


# ------------------------------------------------ what a run resolved

def test_resolved_keys_name_where_each_lookup_came_from(monkeypatch):
    """chip_smoke.py prints autotune.resolved_keys(): every key a kernel
    looked up and whether its params came from the table, an override,
    or the default heuristics."""
    monkeypatch.setattr(autotune, "_resolved", {})
    key_hit = "flash_fwd|T512|D128|c1|d0|m0"      # in the checked-in table
    key_miss = "flash_fwd_qkv|T512|D128|c1|d0|m0"  # not in it

    monkeypatch.setenv(autotune.ENV_TUNING, "off")
    autotune.flash_blocks(512, 128, causal=True, dropout=False,
                          masked=False)
    assert autotune.resolved_keys() == {key_hit: "default"}

    monkeypatch.setenv(autotune.ENV_TUNING, "force")
    autotune.flash_blocks(512, 128, causal=True, dropout=False,
                          masked=False)
    autotune.flash_g("flash_fwd_qkv", 32, 512, 128, causal=True,
                     dropout=False, masked=False)
    assert autotune.resolved_keys() == {key_hit: "table",
                                        key_miss: "default"}

    with autotune.override({"flash_fwd_qkv": {"g": 2}}):
        autotune.flash_g("flash_fwd_qkv", 32, 512, 128, causal=True,
                         dropout=False, masked=False)
    assert autotune.resolved_keys()[key_miss] == "override"
