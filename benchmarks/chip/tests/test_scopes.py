"""The region reader (``chipbench.scopes``) and the three metrics built on
it: scope paths by hand, the HLO protos of a real CPU trace of the
program's own train step, and a trimmed TPU v5 lite trace of the training
cell, whose region map is checked against a second, plain parse of the
paths and whose readings are checked against hand-computed values."""
import json
import os
import re
import types

import pytest

import tiny

from chipbench import scopes, trace  # noqa: E402
from chipbench.common import load_module  # noqa: E402

FIX = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
READERS = ("optimizer_ms.train", "mlp_roofline.train", "lm_head_roofline.train")


def _reader(name):
    return load_module(os.path.join(tiny.CHIP, "metrics", name + ".py"),
                       "m_" + name.replace(".", "_"))


def test_region_of_by_hand():
    base = "jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint"
    assert scopes.region_of(base + "/mlp/dot_general") == "mlp"
    assert scopes.region_of(base + "/mlp/norm/jit(rmsnorm_pallas)/x") == "norm"
    assert scopes.region_of("jit(f)/transpose(jvp(lm_head))/gather") == "lm_head"
    assert scopes.region_of("jit(train_step)/vmap(jvp(embed))/take") == "embed"
    # a function named like a region is no scope
    assert scopes.region_of("jit(train_step)/optimizer/jit(norm)/sqrt") == "optimizer"
    assert scopes.region_of(base + "/dynamic_slice") is None
    assert scopes.region_of("") is None


def test_ambiguous_or_empty_paths_read_nothing():
    ok = {"fusion.1": ["a/mlp/dot"], "copy.2": ["a/while/body"]}
    assert scopes.region_map(ok) == {"fusion.1": "mlp"}
    assert scopes.region_map({"fusion.1": ["a/mlp/dot", "a/attn/dot"]}) is None
    assert scopes.region_map({"fusion.1": ["a/while/body/dot"]}) is None
    assert scopes.region_map({}) is None


def test_paths_of_a_cpu_trace(tmp_path):
    """The HLO protos of the profiler's metadata plane, from a CPU trace of
    the program's train step: every region is found, in that module only."""
    import jax
    import jax.numpy as jnp

    from repro.configs import launch_config
    from repro.data import DataPipeline
    from repro.optim import get as get_opt
    from repro.train import TrainConfig, make_state, make_train_step

    cfg = launch_config("granite-8b", layers=2)
    tc = TrainConfig(precision="bf16", remat="full")
    opt = get_opt(tc.optimizer, tc.lr)
    step = make_train_step(cfg, opt, tc)
    state = make_state(cfg, opt, tc)
    data = DataPipeline(cfg, batch_size=2, seq_len=32, seed=0)
    batch = {k: jnp.asarray(v) for k, v in dict(next(data)).items()}
    data.close()
    other = jax.jit(lambda x: x * 2)
    jax.block_until_ready((step(state, batch), other(1.0)))
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready((step(state, batch), other(1.0)))
    path = trace.latest_xplane(str(tmp_path))
    paths = scopes.op_paths(path)
    assert paths and all(len(ps) == 1 for ps in paths.values())
    assert not any("<lambda>" in ps[0] for ps in paths.values())
    assert any(ps[0].startswith("jit(train_step)/") for ps in paths.values())
    regions = scopes.region_map(paths)
    assert set(regions.values()) == set(scopes.REGIONS)


# ---------------------------------------------------------- the chip trace
def _fixture():
    with open(os.path.join(FIX, "trace_train_scopes.json")) as f:
        d = json.load(f)
    dev = {int(k): [tuple(e) for e in v] for k, v in d["device"].items()}
    dev = {k: trace.clip(v, *d["window"]) for k, v in dev.items()}
    return d, dev


def _plain_region(path):
    """A second parse: unwrap transform markers over the whole string until
    none is left, then take the last region among the components."""
    prev = None
    while prev != path:
        prev = path
        path = re.sub(r"\b(?:jvp|transpose|vmap)\(([^()]*)\)", r"\1", path)
    hits = [c for c in path.split("/") if c in scopes.REGIONS]
    return hits[-1] if hits else None


def _ctx(d, dev, monkeypatch):
    monkeypatch.setattr(scopes, "cell_trace", lambda ctx: "fixture")
    monkeypatch.setattr(scopes, "op_paths", lambda path: d["paths"])
    return types.SimpleNamespace(
        device=dev, config=d["config"], mix=d["mix"],
        inputs={"steps": d["steps"]}, peaks={"bf16_flops": 197e12},
        n_devices=len(dev))


def test_fixture_region_map_matches_a_plain_parse():
    d, _ = _fixture()
    regions = scopes.region_map(d["paths"])
    plain = {op: _plain_region(ps[0]) for op, ps in d["paths"].items()}
    assert regions == {op: r for op, r in plain.items() if r is not None}
    assert set(regions.values()) == set(scopes.REGIONS)


def test_fixture_readings_by_hand(monkeypatch):
    """Two steps: optimizer 83.712392 device ms; mlp 8.6592e12 FLOP a step
    over 117.91 ms at 197 TFLOP/s; head 4.9478e12 FLOP a step over 75.674
    ms (the leaves' durations per region, summed by a separate script)."""
    d, dev = _fixture()
    ctx = _ctx(d, dev, monkeypatch)
    got = {name: _reader(name).read(ctx) for name in READERS}
    assert got == pytest.approx({"optimizer_ms.train": 41.856196,
                                 "mlp_roofline.train": 74.55294895434248,
                                 "lm_head_roofline.train": 66.3784902529108},
                                rel=1e-9)


def test_fixture_without_regions_reads_nothing(monkeypatch):
    d, dev = _fixture()
    bare = dict(d, paths={op: [re.sub(r"\b(?:%s)\b" % "|".join(scopes.REGIONS),
                                      "x", ps[0])] for op, ps in d["paths"].items()})
    ctx = _ctx(bare, dev, monkeypatch)
    assert all(_reader(name).read(ctx) is None for name in READERS)
    amb = dict(d, paths=dict(d["paths"]))
    op = next(op for op, ps in d["paths"].items()
              if scopes.region_of(ps[0]) == "mlp")
    amb["paths"][op] = d["paths"][op] + [d["paths"][op][0].replace("/mlp/", "/attn/")]
    ctx = _ctx(amb, dev, monkeypatch)
    assert all(_reader(name).read(ctx) is None for name in READERS)


def test_no_trace_reads_nothing(monkeypatch):
    monkeypatch.setattr(scopes, "cell_trace", lambda ctx: None)
    ctx = types.SimpleNamespace(device={0: []}, config={"name": "x"}, mix={},
                                inputs={"steps": 3}, peaks={}, n_devices=1)
    assert all(_reader(name).read(ctx) is None for name in READERS)


def test_cell_trace_is_the_cells_own(tmp_path, monkeypatch):
    """Two cells of one configuration: each context reads its own cell's
    trace, whichever was written last; a cell with no trace reads None."""
    here = tmp_path / "benchmarks" / "chip"
    cfg, mixes = {"name": "c"}, {"a": {"seq_len": 1}, "b": {"seq_len": 2}}
    (here / "configs").mkdir(parents=True)
    (here / "traffic").mkdir()
    (here / "configs" / "c.json").write_text(json.dumps(cfg))
    for t, mix in mixes.items():
        (here / "traffic" / f"{t}.json").write_text(json.dumps(mix))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({"workloads": [
        {"name": f"c.{t}", "config": "c", "traffic": t} for t in mixes]}))
    monkeypatch.setattr(scopes, "HERE", str(here))

    def ctx(t):
        return types.SimpleNamespace(config=cfg, mix=mixes[t])

    assert scopes.cell_trace(ctx("a")) is None
    want = {}
    for t in ("a", "b"):
        d = here / ".trace" / f"c.{t}" / "plugins" / "profile" / "run"
        d.mkdir(parents=True)
        want[t] = d / "host.xplane.pb"
        want[t].write_bytes(b"")
    assert {t: scopes.cell_trace(ctx(t)) for t in mixes} == {
        t: str(p) for t, p in want.items()}
    assert scopes.cell_trace(types.SimpleNamespace(config=cfg, mix={})) is None
