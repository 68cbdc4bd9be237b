"""The rest of a run with the timed path broken underneath: `correct` has
to come out false, once for each fault a cell can have. (The control, the
reference in float8 in the program's place, is read against the same
reference in test_control.py.)"""
import presets
import run
from harness import spec, train_driver

gpt2 = spec.family_of(presets.GPT2)


def train_line(seed=100):
    return run.execute("t", seed, 1, 0,
                       bench=presets.bench_with("t", "train_590m_seq2048"),
                       config=presets.TINY_TRAIN, traffic=presets.TRAIN_MIX,
                       limits=presets.TRAIN_LIMITS, rehearsal=True)


def test_sound_run_is_correct():
    assert train_line()["correct"] is True


def test_step_that_returns_its_state_unchanged(monkeypatch):
    real = gpt2.training_net

    def build(config, seed, dims):
        net = real(config, seed, dims)
        step = net._get_train_step()

        def frozen(params, opt_state, state, rng, batch):
            import jax

            copies = jax.tree.map(lambda x: x + 0, (params, opt_state, state))
            _p, _o, _s, loss, extras = step(params, opt_state, state, rng, batch)
            return (*copies, loss, extras)

        net._train_step = frozen
        return net

    monkeypatch.setattr(gpt2, "training_net", build)
    line = train_line()
    assert line["correct"] is False
    assert line["checks"]["change_norm"][0] > 0.9      # reads 1: nothing moved


def test_half_of_the_batch_left_out(monkeypatch):
    real = train_driver.StepFeed.next

    def half(self, num=None):
        from deeplearning4j_tpu.datasets.api import DataSet

        ds = real(self, num)        # the reference keeps the whole batch
        n = ds.features.shape[0] // 2
        return DataSet(ds.features[:n], ds.labels[:n])

    monkeypatch.setattr(train_driver.StepFeed, "next", half)
    line = train_line()
    assert line["correct"] is False
    assert line["checks"]["grad_norm"][0] > line["checks"]["grad_norm"][1]


def serve_line(seed, like="serve_1p3b_chat", mix=presets.OPEN_MIX):
    return run.execute("s", seed, 3, 0, bench=presets.bench_with("s", like),
                       config=presets.TINY_SERVE, traffic=mix,
                       limits=presets.SERVE_LIMITS, rehearsal=True)


def test_a_cache_row_left_stale(monkeypatch):
    """Every slot's fourth row is never written (it stays as it was
    allocated): the answers that read it differ from the reference's."""
    from deeplearning4j_tpu.nn import decode

    real = decode._cache_write

    def stale(entry, k_new, v_new, rows, positions, kv_dtype, page_size):
        import jax.numpy as jnp

        beyond = entry["k"].shape[1]        # out of range: the write is dropped
        return real(entry, k_new, v_new, rows,
                    jnp.where(positions == 3, beyond, positions), kv_dtype,
                    page_size)

    monkeypatch.setattr(decode, "_cache_write", stale)
    line = serve_line(9)
    assert line["correct"] is False
    assert line["checks"]["token_gap_mean"][0] > line["checks"]["token_gap_mean"][1]


def test_a_token_altered_where_it_is_produced(monkeypatch):
    from deeplearning4j_tpu.serving import batcher

    real = batcher.GenRequest.emit

    def altered(self, token, now):
        return real(self, (int(token) + 1) % presets.TINY_MODEL["vocab_size"],
                    now)

    monkeypatch.setattr(batcher.GenRequest, "emit", altered)
    line = serve_line(8)
    assert line["correct"] is False
    assert line["checks"]["token_gap"][0] > line["checks"]["token_gap"][1]
