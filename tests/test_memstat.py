"""Memory-ledger + cost-book unit tests (telemetry/memstat.py,
telemetry/costbook.py): subsystem attribution and the activation
residual, sampler cadence/rate-limit/no-op contracts, the compiled-cost
harvest off a warmed jit (with the zero-retrace guarantee the serving
gates freeze), and the predicted-vs-measured reconcile loop."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.telemetry import NullRecorder, Recorder
from deeplearning4j_tpu.telemetry import costbook as costbook_mod
from deeplearning4j_tpu.telemetry import memstat as memstat_mod
from deeplearning4j_tpu.telemetry.costbook import CostBook
from deeplearning4j_tpu.telemetry.memstat import (
    MemoryLedger,
    MemorySampler,
    sampler_for_net,
)

pytestmark = pytest.mark.telemetry


# ------------------------------------------------------------------ ledger

def test_tree_bytes_sums_array_leaves():
    tree = {"w": np.zeros((4, 8), dtype=np.float32),
            "b": np.zeros(8, dtype=np.float32),
            "meta": "not-an-array"}
    assert memstat_mod.tree_bytes(tree) == 4 * 8 * 4 + 8 * 4


def test_ledger_rejects_unknown_subsystem():
    with pytest.raises(ValueError, match="unknown ledger subsystem"):
        MemoryLedger().register("heap", lambda: {})


def test_ledger_attribution_and_activation_residual():
    params = {"w": np.zeros(100, dtype=np.float32)}   # 400 B
    opt = {"m": np.zeros(25, dtype=np.float32)}       # 100 B
    ledger = (MemoryLedger()
              .register("params", lambda: params)
              .register("opt_state", opt))  # plain tree registers too
    assert ledger.attributed() == {"params": 400, "opt_state": 100}
    # whatever the sources miss is the activation envelope
    assert ledger.breakdown(1000) == {
        "params": 400, "opt_state": 100, "activations": 500}
    # an over-attributed snapshot clamps the residual at zero
    assert ledger.breakdown(300)["activations"] == 0


def test_ledger_residual_moves_to_other_when_activations_registered():
    acts = {"a": np.zeros(10, dtype=np.float32)}      # 40 B
    out = MemoryLedger().register("activations", lambda: acts) \
                        .breakdown(100)
    assert out == {"activations": 40, "other": 60}


def test_ledger_source_tracks_replacement_and_failure_is_zero():
    box = {"tree": np.zeros(10, dtype=np.float32)}
    ledger = MemoryLedger().register("params", lambda: box["tree"])
    assert ledger.attributed()["params"] == 40
    box["tree"] = np.zeros(20, dtype=np.float32)  # hot-swap: no re-register
    assert ledger.attributed()["params"] == 80

    def boom():
        raise RuntimeError("source died")

    ledger.register("kv_pages", boom)
    assert ledger.attributed() == {"params": 80, "kv_pages": 0}


# ----------------------------------------------------------------- sampler

def test_sampler_disabled_under_null_recorder():
    s = MemorySampler(NullRecorder(), mem_every=1)
    assert not s.enabled
    assert s.sample("x") == {}
    assert s.on_step(0) == {}
    assert s.maybe_sample("x") == {}


def test_sample_emits_ledger_annotated_memory_event():
    rec = Recorder(path=None)
    keep = jnp.zeros((16, 16), dtype=jnp.float32)  # pin a live array
    ledger = MemoryLedger().register("params", lambda: keep)
    s = MemorySampler(rec, ledger, mem_every=1)
    ev = s.sample("test", iteration=7)
    assert ev["event"] == "memory" and ev["source"] == "test"
    assert ev["iteration"] == 7
    assert ev["live_array_bytes"] >= keep.nbytes
    assert ev["ledger"]["params"] == keep.nbytes
    assert ev["ledger_total_bytes"] == sum(ev["ledger"].values())
    assert ev["live_array_count"] >= 1
    # CPU backends expose no memory_stats: devices dict stays empty
    for stats in ev["devices"].values():
        assert stats.get("bytes_limit", 0) >= 0
    # cached surfaces for the scrape path
    assert s.last["live_array_bytes"] == ev["live_array_bytes"]
    assert s.peak_live_bytes == ev["live_array_bytes"]


def test_on_step_cadence_is_modulo_mem_every():
    rec = Recorder(path=None)
    s = MemorySampler(rec, mem_every=3)
    hits = [i for i in range(7) if s.on_step(i)]
    assert hits == [0, 3, 6]
    assert all(e["event"] == "memory" and e["source"] == "fit"
               for e in rec.events if e["event"] == "memory")
    # cadence off: one modulo, zero sampling
    off = MemorySampler(rec, mem_every=0)
    assert off.on_step(0) == {} and off.on_step(3) == {}


def test_mem_every_reads_env_and_tolerates_garbage(monkeypatch):
    monkeypatch.setenv(memstat_mod.ENV_MEM_EVERY, "5")
    assert MemorySampler(Recorder(path=None)).mem_every == 5
    monkeypatch.setenv(memstat_mod.ENV_MEM_EVERY, "banana")
    assert MemorySampler(Recorder(path=None)).mem_every == 0
    monkeypatch.delenv(memstat_mod.ENV_MEM_EVERY)
    assert MemorySampler(Recorder(path=None)).mem_every == 0


def test_maybe_sample_rate_limits_scrape_storms():
    rec = Recorder(path=None)
    s = MemorySampler(rec, min_interval_s=3600.0, mem_every=1)
    assert s.maybe_sample("stats_tick")  # first tick samples
    assert s.maybe_sample("stats_tick") == {}  # storm absorbed
    assert sum(1 for e in rec.events if e["event"] == "memory") == 1
    eager = MemorySampler(rec, min_interval_s=0.0, mem_every=1)
    assert eager.maybe_sample("t1") and eager.maybe_sample("t2")


def test_sampler_thread_starts_and_stops_cleanly():
    s = MemorySampler(Recorder(path=None), mem_every=1)
    s.start(interval_s=3600.0)
    thread = s._thread
    assert thread is not None and thread.daemon
    s.stop()
    assert s._thread is None and not thread.is_alive()
    # NullRecorder never spawns the thread at all
    null = MemorySampler(NullRecorder()).start(interval_s=0.001)
    assert null._thread is None


def test_sampler_for_net_caches_per_recorder():
    class Net:
        params = {"w": np.zeros(8, dtype=np.float32)}
        opt_state = {"m": np.zeros(2, dtype=np.float32)}

    net = Net()
    rec = Recorder(path=None)
    s1 = sampler_for_net(net, rec)
    assert sampler_for_net(net, rec) is s1  # cached on the net
    assert s1.ledger.attributed() == {"params": 32, "opt_state": 8}
    rec2 = Recorder(path=None)
    s2 = sampler_for_net(net, rec2)  # new recorder: rebuilt
    assert s2 is not s1 and s2.recorder is rec2


# --------------------------------------------------------------- cost book

def _warm_jit():
    """A warmed jit wrapper with a host-side trace counter."""
    calls = {"n": 0}

    @jax.jit
    def f(x):
        calls["n"] += 1
        return (x @ x.T).sum()

    x = jnp.ones((8, 8), dtype=jnp.float32)
    f(x).block_until_ready()  # warm: populates the jaxpr + exec caches
    return f, x, calls


def test_harvest_pulls_xla_cost_and_memory_analyses():
    f, x, calls = _warm_jit()
    fields = costbook_mod.harvest(f, x)
    assert fields["flops"] > 0
    assert fields["bytes_accessed"] > 0
    assert "peak_temp_bytes" in fields
    # the zero-retrace guarantee: lower() after the warm call is a
    # jaxpr-cache hit — the traced fn body ran exactly once
    assert calls["n"] == 1


def test_costbook_records_once_per_entry_shape():
    rec = Recorder(path=None)
    book = CostBook(rec)
    f, x, _ = _warm_jit()
    ev = book.record("forward", [8, 8], f, (x,))
    assert ev["event"] == "cost" and ev["entry"] == "forward"
    assert ev["shape"] == [8, 8] and ev["flops"] > 0
    # dedup: a respawn re-warm emits nothing
    assert book.record("forward", [8, 8], f, (x,)) == {}
    assert sum(1 for e in rec.events if e["event"] == "cost") == 1
    # a new shape key is a new book entry
    assert book.record("forward", [8, 16], f, (x,))["shape"] == [8, 16]
    assert book.record("forward", [8, 8], f, (x,), ) == {}
    assert len(book.entries()) == 2


def test_costbook_disabled_and_flops_lookups():
    assert CostBook(NullRecorder()).record("e", [1], None, ()) == {}
    book = CostBook(Recorder(path=None))
    f, x, _ = _warm_jit()
    book.record("forward", [8, 8], f, (x,))
    book.record("fit_scanned", [2, 4], f, (x,))
    per_shape = book.flops("forward", [8, 8])
    assert per_shape > 0
    assert book.flops("forward") == per_shape
    assert book.flops() == pytest.approx(
        per_shape + book.flops("fit_scanned"))
    assert book.flops("forward", [9, 9]) == 0.0
    assert book.peak_temp_bytes() >= 0


def test_mfu_is_clamped_and_guarded():
    assert CostBook.mfu(1e12, 1.0, 1e12) == 1.0
    assert CostBook.mfu(5e11, 1.0, 1e12) == 0.5
    assert CostBook.mfu(1e15, 0.001, 1e12) == 1.0  # clamped at 1
    assert CostBook.mfu(0.0, 1.0, 1e12) == 0.0
    assert CostBook.mfu(1e12, 0.0, 1e12) == 0.0
    assert CostBook.mfu(1e12, 1.0, 0.0) == 0.0


class _Dev:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_peak_flops_reads_the_one_table_by_device_kind():
    assert costbook_mod.peak_flops(_Dev("tpu", "TPU v5 lite")) == 197e12
    assert "source" in costbook_mod.DEVICE_PEAKS["TPU v5 lite"]


def test_peak_flops_is_absent_off_tpu_and_raises_on_unknown_tpu():
    assert costbook_mod.peak_flops(_Dev("cpu", "cpu")) is None
    with pytest.raises(KeyError, match="TPU v9"):
        costbook_mod.peak_flops(_Dev("tpu", "TPU v9"))
    assert not hasattr(costbook_mod, "DEFAULT_PEAK_FLOPS")


# --------------------------------------------------------------- reconcile

def test_reconcile_emits_typed_cost_drift_event():
    rec = Recorder(path=None)
    ev = costbook_mod.reconcile(rec, 1000, measured_bytes=32000,
                                source="placement", grid="2x2")
    assert ev["event"] == "cost_drift"
    assert ev["predicted_bytes"] == 1000 and ev["measured_bytes"] == 32000
    assert ev["ratio"] == pytest.approx(32.0)
    assert ev["factor"] == costbook_mod.DEFAULT_DRIFT_FACTOR
    assert ev["source"] == "placement" and ev["grid"] == "2x2"


def test_reconcile_measures_live_arrays_off_tpu():
    keep = jnp.zeros((32, 32), dtype=jnp.float32)
    ev = costbook_mod.reconcile(Recorder(path=None), 10_000)
    assert ev["measured_bytes"] >= keep.nbytes  # live-array fallback
    assert ev["ratio"] > 0


def test_reconcile_skips_null_recorder_and_empty_prediction():
    assert costbook_mod.reconcile(NullRecorder(), 1000,
                                  measured_bytes=1) == {}
    assert costbook_mod.reconcile(Recorder(path=None), 0,
                                  measured_bytes=1) == {}


def test_costbook_record_is_thread_safe_single_emit():
    """Concurrent warmups of the same (entry, shape) — the D002-shaped
    race — emit exactly one cost event."""
    rec = Recorder(path=None)
    book = CostBook(rec)
    f, x, _ = _warm_jit()
    results = []

    def worker():
        results.append(book.record("forward", [8, 8], f, (x,)))

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert sum(1 for r in results if r) <= 1
    assert sum(1 for e in rec.events if e["event"] == "cost") == 1
