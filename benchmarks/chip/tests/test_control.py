"""The control comes out not correct: the f32 reference put in the program's
place and computed in fp8 (the precision below the bf16 the configurations
state) fails a limit that the program's own run passes. Tiny cells on the
CPU with limits set for that size (``tiny.TINY_LIMITS``); the readings at
the cells' own size, which set their limits, were taken on the chip
(PERF.md, "Limits")."""
import tiny


def _checks(res):
    return {name: (value, limit) for name, value, limit in res["checks"]}


def test_serving_control_fails_where_the_program_passes(tmp_path):
    from repro.serve import engine

    engine._CHUNK_CACHE.clear()
    res, _ = tiny.run_tiny(tmp_path, "serve", seed=11, control=True)
    c = _checks(res)
    value, limit = c["served_gap"]
    assert res["correct"] and value <= limit, c
    assert c["control_fp8_gap"][0] > limit, c
    assert c["fault_altered_token_gap"][0] > limit, c


def test_training_control_and_half_batch_fail(tmp_path):
    res, _ = tiny.run_tiny(tmp_path, "train", seed=11, control=True)
    c = _checks(res)
    names = ("loss_gap", "grad_gap", "update_gap")
    assert res["correct"] and all(c[n][0] <= c[n][1] for n in names), c
    assert any(c["control_fp8_" + n][0] > c[n][1] for n in names), c
    assert any(c["fault_half_batch_" + n][0] > c[n][1] for n in names), c
