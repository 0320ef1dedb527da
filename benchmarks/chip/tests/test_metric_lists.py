"""Each cell reports the end-to-end metrics that ``BENCHMARK.json`` lists
for it, and no others: tiny runs on the CPU, named as the real cells, with
the repository's own metric lists. A window request that never gets a
first token counts as failed and stays out of the TTFT sample."""
import pytest

import tiny


def _fresh_engine_programs():
    from repro.serve import engine

    engine._CHUNK_CACHE.clear()


@pytest.mark.parametrize("kind, names", [
    ("serve", {"ttft_p50_ms", "itl_p95_ms", "setup_s"}),
    ("train", {"train_tokens_per_s", "train_peak_hbm_gib", "setup_s"}),
])
def test_cell_reports_its_own_end_to_end_metrics(tmp_path, kind, names):
    if kind == "serve":
        _fresh_engine_programs()
    res, run = tiny.run_tiny(tmp_path, kind, real=True)
    metrics = run.end_to_end_metrics(res["metrics"])
    assert set(metrics) == names
    units = {m["name"]: m["unit"] for m in run.bench["end_to_end"]}
    # peak memory reads 0 on the CPU, which reports no memory stats
    assert all(m["unit"] == units[k] and m["value"] >= 0
               for k, m in metrics.items())


def test_missing_reading_is_an_error(tmp_path):
    _, bench, cell = tiny.make_root(tmp_path, "serve", real=True)
    from chipbench.common import CellRun

    run = CellRun(bench, cell, 1, 1.0, False, root=str(tmp_path))
    run.setup_s = 1.0
    with pytest.raises(ValueError, match="itl_p95_ms"):
        run.end_to_end_metrics({"ttft_p50_ms": 5.0})


def test_request_without_a_first_token_is_failed(tmp_path, monkeypatch):
    """The engine drops the first request submitted once the window is
    open: it never delivers a token of it."""
    from chipbench import common, serve
    from repro.serve import engine

    _fresh_engine_programs()
    state = {"open": False, "dropped": None}
    open_window = common.CellRun.start_window

    def start_window(self):
        state["open"] = True
        open_window(self)

    submit = engine.ServeEngine.submit

    def drop_first(self, prompt, max_new, *a, on_token=None, **k):
        if state["open"] and state["dropped"] is None:
            rid = submit(self, prompt, max_new, *a,
                         on_token=lambda *_: None, **k)
            state["dropped"] = rid
            return rid
        return submit(self, prompt, max_new, *a, on_token=on_token, **k)

    seen = {}
    end_to_end = serve.Driver.end_to_end

    def keep(self, w):
        seen["window"] = w["window"]
        return end_to_end(self, w)

    monkeypatch.setattr(common.CellRun, "start_window", start_window)
    monkeypatch.setattr(engine.ServeEngine, "submit", drop_first)
    monkeypatch.setattr(serve.Driver, "end_to_end", keep)
    res, run = tiny.run_tiny(tmp_path, "serve", real=True)
    window = seen["window"]
    assert state["dropped"] in {r.rid for r in window}
    assert res["failed"] == 1 and res["attempted"] == len(window)
    ttft = [r.times[0] - r.due for r in window if r.out]
    assert len(ttft) == len(window) - 1
    assert res["metrics"]["ttft_p50_ms"] == pytest.approx(
        serve.percentile(ttft, 50) * 1e3)
