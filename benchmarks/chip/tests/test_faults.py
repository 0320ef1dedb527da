"""A run with the timed path broken underneath must come out not correct.

Each test drives a whole tiny cell on the CPU (the chip check skipped),
with one fault planted in the program, and sees ``correct`` false: for
serving, a token altered where it is produced and decode steps that leave
the KV cache unchanged; for training, a step that returns its state
unchanged and a step that leaves half of the batch out. A sound run of the
same cell comes out correct."""
import numpy as np

import tiny


def _fresh_engine_programs():
    from repro.serve import engine

    engine._CHUNK_CACHE.clear()


def test_sound_serve_run_is_correct(tmp_path):
    _fresh_engine_programs()
    res, _ = tiny.run_tiny(tmp_path, "serve")
    assert res["correct"], res["checks"]


def test_altered_token_is_caught(tmp_path, monkeypatch):
    from repro.serve import engine

    _fresh_engine_programs()
    orig = engine.ServeEngine._collect

    def collect(self, emits):
        emits = np.array(emits)
        if len(emits):
            hit = emits[0] >= 0
            emits[0, hit] = (emits[0, hit] + 1) % self.cfg.vocab_size
        return orig(self, emits)

    monkeypatch.setattr(engine.ServeEngine, "_collect", collect)
    res, _ = tiny.run_tiny(tmp_path, "serve")
    assert not res["correct"], res["checks"]


def test_kv_cache_left_unchanged_is_caught(tmp_path, monkeypatch):
    from repro.models import attention, stack

    _fresh_engine_programs()
    keep = lambda pool, *a, **k: pool  # noqa: E731
    monkeypatch.setattr(attention, "pool_write", keep)
    monkeypatch.setattr(stack, "pool_write", keep, raising=False)
    res, _ = tiny.run_tiny(tmp_path, "serve")
    _fresh_engine_programs()
    assert not res["correct"], res["checks"]


def test_sound_train_run_is_correct(tmp_path):
    res, _ = tiny.run_tiny(tmp_path, "train")
    assert res["correct"], res["checks"]


def test_unchanged_state_is_caught(tmp_path, monkeypatch):
    from repro.train import loop

    orig = loop.finish_step

    def finish(state, grads, metrics, *a, **k):
        _, m = orig(state, grads, metrics, *a, **k)
        return state, m

    monkeypatch.setattr(loop, "finish_step", finish)
    res, _ = tiny.run_tiny(tmp_path, "train")
    assert not res["correct"], res["checks"]


def test_half_batch_is_caught(tmp_path, monkeypatch):
    from repro.train import loop

    orig = loop.loss_fn

    def half(cfg, params, batch, rt, *a, **k):
        B = batch["tokens"].shape[0]
        return orig(cfg, params, {k_: v[: B // 2] for k_, v in batch.items()},
                    rt, *a, **k)

    monkeypatch.setattr(loop, "loss_fn", half)
    res, _ = tiny.run_tiny(tmp_path, "train")
    assert not res["correct"], res["checks"]
