"""The trace reduction, checked before any metric is trusted: by hand on
small made-up event lists, and on two recorded TPU v5 lite traces (a chat
serving window and a training window, trimmed) against a second, plain
implementation."""
import json
import os
import types

import pytest

import tiny  # noqa: F401  (puts the benchmark on the path)

from chipbench import trace  # noqa: E402
from chipbench.common import load_module  # noqa: E402

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")


def _fixture(name):
    with open(os.path.join(FIX, name)) as f:
        d = json.load(f)
    dev = {int(k): [tuple(e) for e in v] for k, v in d["device"].items()}
    return {"device": dev, "host": [tuple(h) for h in d["host"]]}


def _sweep_busy(events, w0, w1):
    """Busy time by a sweep over event boundaries (independent of union)."""
    pts = []
    for _, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            pts += [(a, 1), (b, -1)]
    pts.sort(key=lambda p: (p[0], -p[1]))
    busy, depth, last = 0.0, 0, None
    for t, step in pts:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_by_hand():
    ev = [("loop", 0.0, 100.0), ("a", 10.0, 20.0), ("b", 40.0, 10.0),
          ("c", 150.0, 30.0), ("d", 180.0, 10.0)]
    assert trace.union(ev) == [(0.0, 100.0), (150.0, 190.0)]
    assert trace.busy_ns(ev) == 140.0
    assert trace.busy_ns(trace.clip(ev, 50.0, 160.0)) == 60.0
    assert [e[0] for e in trace.leaves(ev)] == ["a", "b", "c", "d"]
    host = [("bench.window", 0.0, 200.0), ("bench.tick", 0.0, 120.0),
            ("bench.wait", 120.0, 80.0)]
    gaps = trace.idle_gaps(ev, host, 0.0, 200.0)
    assert gaps == [("bench.wait", 50e-9), ("bench.wait", 10e-9)]
    assert trace.top_ops(ev)[0] == ("c", 30e-9)


@pytest.mark.parametrize("name", ["trace_chat.json", "trace_train.json"])
def test_busy_matches_a_sweep(name):
    tr = _fixture(name)
    dev, host, w0, w1 = trace.summarize(tr)
    ev = dev[0]
    assert len(ev) > 1000
    busy = trace.busy_ns(ev)
    assert busy == pytest.approx(_sweep_busy(tr["device"][0], w0, w1), rel=1e-12)
    assert 0 < busy <= w1 - w0


def test_chat_fixture_numbers():
    tr = _fixture("trace_chat.json")
    dev, host, w0, w1 = trace.summarize(tr)
    ev = dev[0]
    top = trace.top_ops(ev, 3)
    # the loops that hold the decode steps are containers, never "ops"
    assert all(not n.startswith("while") for n, _ in trace.top_ops(ev))
    assert top[0][0].startswith("paged_prefill_attention_kernel")
    kern = trace.matching(ev, ["paged_prefill_attention_kernel"])
    assert len(kern) > 100
    k_s = sum(d for _, _, d in kern) / 1e9
    busy_s = trace.busy_ns(ev) / 1e9
    assert 0.5 * busy_s < k_s < busy_s          # the kernel dominates decode
    gaps = trace.idle_gaps(ev, host, w0, w1)
    assert gaps and all(lbl.startswith("bench.") for lbl, _ in gaps)
    # the reader: bound over kernel time
    mod = load_module(os.path.join(tiny.CHIP, "metrics",
                                   "paged_attn_roofline.decode.py"), "m_pa")
    ctx = types.SimpleNamespace(device=dev, inputs={"work": {"attn_bound_s": 0.01}})
    assert mod.read(ctx) == pytest.approx(100 * 0.01 / k_s)
    ctx.inputs = {"work": {}}
    assert mod.read(ctx) is None                 # nothing to read: silent


def test_train_fixture_numbers():
    tr = _fixture("trace_train.json")
    dev, host, w0, w1 = trace.summarize(tr)
    mod = load_module(os.path.join(tiny.CHIP, "metrics",
                                   "flash_attn_roofline.py"), "m_fa")
    fa = trace.matching(dev[0], mod.NAMES)
    names = {n.rsplit(".", 1)[0] for n, _, _ in fa}
    assert names == {"flash_attention_fwd_flat", "flash_attention_bwd_flat"}
    # no kernel event holds another: their times add
    assert len(trace.leaves(fa)) == len(fa)
    assert trace.busy_ns(dev[0]) / (w1 - w0) > 0.95
