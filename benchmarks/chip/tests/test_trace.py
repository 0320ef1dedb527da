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


def test_collectives_by_hand():
    assert trace.subtract([(0.0, 10.0), (20.0, 30.0)], [(5.0, 25.0)]) == 10.0
    assert trace.subtract([(0.0, 10.0)], []) == 10.0
    assert trace.subtract([(2.0, 4.0)], [(0.0, 1.0), (1.5, 5.0)]) == 0.0
    assert trace.op_kind("%psum.7 = bf16[1,8]{1,0:T(8,128)(2,1)} all-reduce("
                         "bf16[1,8]{1,0} %fusion.3), to_apply=%add") == "all-reduce"
    assert trace.op_kind(
        "%collective-permute-start.2 = (bf16[8]{0:T(256)}, bf16[8]{0:T(256)"
        "S(1)}, u32[]{:S(2)}) collective-permute-start(bf16[8]{0} %x)"
    ) == "collective-permute-start"
    assert trace.op_kind("fusion.3") == "fusion.3"
    ops = [("while.3", 0.0, 400.0),                    # a loop: a container
           ("fusion.1", 0.0, 100.0),
           ("all-reduce.1", 100.0, 50.0),              # sync: 50 exposed
           ("collective-permute-start.1", 150.0, 5.0),
           ("fusion.2", 160.0, 40.0),
           ("collective-permute-done.1", 230.0, 10.0),  # 150-240: 10 + 40
           ("fusion.3", 300.0, 50.0),
           ("psum.7", 385.0, 10.0)]                    # an all-reduce by kind
    asyn = [("all-gather-start.2", 320.0, 60.0),       # 350-380 exposed
            ("copy-start.1", 0.0, 400.0)]              # no collective
    kinds = {"psum.7": "all-reduce"}
    spans = trace.collective_spans(ops, asyn, kinds)
    assert sorted((s, d) for _, s, d in spans) == [
        (100.0, 50.0), (150.0, 90.0), (320.0, 60.0), (385.0, 10.0)]
    ctx = types.SimpleNamespace(device={0: ops, 1: [("fusion.9", 0.0, 400.0)]},
                                async_ops={0: asyn}, op_kinds={0: kinds},
                                window_s=400e-9)
    assert trace.collective_exposed(ctx) == pytest.approx(100 * (140 / 400) / 2)
    ctx.op_kinds = {}                                # psum.7 is then compute
    assert trace.collective_exposed(ctx) == pytest.approx(100 * (130 / 400) / 2)
    ctx.device, ctx.async_ops = {0: ops[:2]}, {}
    assert trace.collective_exposed(ctx) is None     # nothing to read


def _pp_fixture():
    with open(os.path.join(FIX, "trace_pp2tp2.json")) as f:
        d = json.load(f)
    as_events = lambda t: {int(k): [tuple(e) for e in v] for k, v in t.items()}  # noqa: E731
    return (d["window"], as_events(d["device"]), as_events(d["async"]),
            {int(k): v for k, v in d["kinds"].items()})


def _sweep_exposed(ops, kinds):
    """Exposed collective ns by a sweep over the innermost ops' boundaries:
    a count of collective-permutes in flight (up at a start's beginning,
    down at a done's end), of synchronous collectives and of compute ops."""
    pts = []
    for name, s, d in trace.leaves(ops):
        kind = kinds.get(name, "compute")
        if kind.endswith("-start"):
            pts.append((s, "flight", 1))
        elif kind.endswith("-done"):
            pts.append((s + d, "flight", -1))
        else:
            key = "sync" if kind != "compute" else "compute"
            pts += [(s, key, 1), (s + d, key, -1)]
    pts.sort(key=lambda p: (p[0], -p[2]))
    depth = {"flight": 0, "sync": 0, "compute": 0}
    exposed, last = 0.0, None
    for t, key, step in pts:
        if last is not None and (depth["flight"] or depth["sync"]) \
                and not depth["compute"]:
            exposed += t - last
        depth[key] += step
        last = t
    return exposed


def test_collective_exposed_on_a_four_chip_trace():
    """Chip 0 by hand (ns from the stretch's start): the forward permute
    from 0 (its start blocks until 136,809) to its done's end at 497,035,
    the backward one from 136,811 to 856,572, a copy inside them from
    136,854 to 301,632, and the TP all-reduce ``psum.184`` alone from
    2,345,960 to 2,732,783: (856,572 - 164,778) + 386,823 = 1,078,617 ns
    exposed of 2,800,000. The other chips against a plain sweep."""
    (a, b), dev, asyn, kinds = _pp_fixture()
    spans = trace.union(trace.collective_spans(dev[0], asyn[0], kinds[0]))
    assert [(s - a, e - a) for s, e in spans] == [
        (0.0, 856572.0), (2345960.0, 2732783.0)]
    assert kinds[0]["psum.184"] == "all-reduce"
    ctx = types.SimpleNamespace(device=dev, async_ops=asyn, op_kinds=kinds,
                                window_s=(b - a) / 1e9)
    exposed = {d: _sweep_exposed(ops, kinds[d]) for d, ops in dev.items()}
    assert exposed[0] == 1078617.0
    assert len(dev) == 4 and all(0 < v < b - a for v in exposed.values())
    assert trace.collective_exposed(ctx) == pytest.approx(
        100 * sum(exposed.values()) / 4 / (b - a), rel=1e-12)
    # the async line alone (chip 0 only) covers the same permutes
    assert trace.union(e for e in asyn[0] if trace.is_collective(e[0])) == \
        [(a, a + 856572.0)]
