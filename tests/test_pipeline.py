"""Pipeline schedules: simulator invariants (Table 4), executable GPipe,
tick tables for the manual-backward runner, and ParallelPlan validation."""
import os
import subprocess
import sys
import textwrap

from _subproc import REPO_ROOT, subprocess_env

import pytest

import numpy as np

from repro.core.pipeline import (
    OP_B,
    OP_B_EARLY,
    OP_F,
    OP_IDLE,
    OP_SEND,
    SCHEDULES,
    runner_ops,
    simulate,
    tick_table,
)

TABLE_SHAPES = [(2, 4), (2, 8), (4, 4), (4, 16), (3, 5), (1, 4)]


def test_gpipe_bubble_closed_form():
    # classic GPipe bubble with t_bwd = 2*t_fwd: (P-1)*(tf+tb)/(M*(tf+tb)+(P-1)*(tf+tb))
    P, M = 4, 8
    r = simulate("gpipe", P, M, t_fwd=1.0, t_bwd=2.0)
    expect = (P - 1) * 3.0 / (M * 3.0 + (P - 1) * 3.0)
    assert r.bubble_fraction == pytest.approx(expect, abs=1e-6)


def test_1f1b_memory_better_than_gpipe():
    P, M = 4, 16
    g = simulate("gpipe", P, M)
    f = simulate("1f1b", P, M)
    assert f.peak_activations <= P  # bounded by stages, not microbatches
    assert g.peak_activations == M  # stores all microbatches
    assert f.peak_activations < g.peak_activations


def test_1f1b_same_bubble_as_gpipe():
    P, M = 4, 8
    g = simulate("gpipe", P, M)
    f = simulate("1f1b", P, M)
    assert f.bubble_fraction == pytest.approx(g.bubble_fraction, abs=0.02)


def test_interleaved_reduces_bubble():
    P, M = 4, 8
    base = simulate("1f1b", P, M)
    inter = simulate("interleaved", P, M, v=2)
    assert inter.bubble_fraction < base.bubble_fraction + 1e-9, (inter, base)


def test_async_rows_report_staleness_and_versions():
    pd = simulate("pipedream", 4, 8)
    assert not pd.synchronous and pd.weight_versions == 4 and pd.max_staleness == 3
    pd2 = simulate("pipedream_2bw", 4, 8)
    assert pd2.weight_versions == 2 and pd2.max_staleness == 1


def test_all_schedules_complete():
    for name in SCHEDULES:
        r = simulate(name, 4, 8)
        assert r.makespan > 0
        assert 0 <= r.bubble_fraction < 1


def test_more_microbatches_shrink_bubble():
    b8 = simulate("gpipe", 4, 8).bubble_fraction
    b32 = simulate("gpipe", 4, 32).bubble_fraction
    assert b32 < b8


# ---------------------------------------------------------------- tick tables
@pytest.mark.parametrize("sched", ["gpipe", "1f1b"])
@pytest.mark.parametrize("P,M", TABLE_SHAPES)
def test_tick_table_matches_simulator(sched, P, M):
    """The executable table IS the simulator schedule: same bubble, and the
    greedy slot allocation reproduces Table 4's peak-activation column."""
    t = tick_table(sched, P, M)
    sim = simulate(sched, P, M, t_fwd=1.0, t_bwd=1.0)
    assert t.bubble_fraction == pytest.approx(sim.bubble_fraction, abs=1e-9)
    assert t.n_act_slots == sim.peak_activations
    # every microbatch appears exactly once as F and once as B per stage
    for s in range(P):
        assert sorted(m for m in t.f_mb[:, s] if m >= 0) == list(range(M))
        assert sorted(m for m in t.b_mb[:, s] if m >= 0) == list(range(M))


@pytest.mark.parametrize("sched", ["gpipe", "1f1b"])
@pytest.mark.parametrize("P,M", TABLE_SHAPES)
def test_runner_ops_follow_table(sched, P, M):
    """The runner's branch per (tick, stage): the table's op everywhere but
    on the last stage under 1F1B, where each microbatch's backward runs at
    its F tick and its B tick only sends the kept cotangent."""
    t = tick_table(sched, P, M)
    ops = runner_ops(t)
    table_ops = np.where(t.f_mb >= 0, OP_F, np.where(t.b_mb >= 0, OP_B, OP_IDLE))
    assert ops.shape == (t.n_ticks, P)
    if sched == "gpipe":
        np.testing.assert_array_equal(ops, table_ops)
        assert (ops == OP_B_EARLY).sum() == 0
        return
    last = P - 1
    np.testing.assert_array_equal(ops[:, :last], table_ops[:, :last])
    for m in range(M):
        (tf,) = np.flatnonzero(t.f_mb[:, last] == m)
        (tb,) = np.flatnonzero(t.b_mb[:, last] == m)
        assert ops[tf, last] == OP_B_EARLY and ops[tb, last] == OP_SEND
        assert tb == tf + 1
    col = ops[:, last]
    assert set(col.tolist()) <= {OP_IDLE, OP_B_EARLY, OP_SEND}
    pending = np.cumsum((col == OP_B_EARLY).astype(int) - (col == OP_SEND))
    assert pending.min() >= 0 and pending.max() <= 1 and pending[-1] == 0
    assert (ops == OP_B_EARLY).sum() == M


def test_tick_table_1f1b_memory_bound():
    """1F1B buffers are O(P); GPipe's are O(M) — strict gap at M >= 2P."""
    for P in (2, 4):
        M = 2 * P
        f, g = tick_table("1f1b", P, M), tick_table("gpipe", P, M)
        assert f.n_act_slots == min(P, M)
        assert g.n_act_slots == M
        assert f.peak_activation_bytes(1) < g.peak_activation_bytes(1)
        # same schedule length -> same bubble, less memory
        assert f.bubble_fraction == pytest.approx(g.bubble_fraction, abs=1e-9)


def test_tick_table_rejects_simulator_only_schedules():
    with pytest.raises(ValueError):
        tick_table("pipedream", 4, 8)


# --------------------------------------------------------------- ParallelPlan
def test_parallel_plan_validation():
    from repro.configs import SURVEY_DEMO, reduced
    from repro.core.partitioner import ParallelPlan, auto_plan

    cfg = reduced(SURVEY_DEMO, n_layers=4, n_heads=4, n_kv_heads=2, d_ff=256)
    ParallelPlan(dp=2, tp=2, pp=2, microbatches=4).validate(cfg)
    with pytest.raises(ValueError):  # async rows are simulator-only
        ParallelPlan(pp=2, schedule="pipedream").validate(cfg)
    with pytest.raises(ValueError):  # 4 layers don't split into 3 stages
        ParallelPlan(pp=3, microbatches=2).validate(cfg)
    with pytest.raises(ValueError):  # kv heads not divisible by tp
        ParallelPlan(tp=4, microbatches=2).validate(cfg)
    with pytest.raises(ValueError):  # MoE composes with EP, not manual TP
        moe = reduced(SURVEY_DEMO, n_layers=4, n_heads=4, n_kv_heads=2,
                      d_ff=256, ffn_kind="moe", n_experts=4, experts_top_k=2)
        ParallelPlan(tp=2, microbatches=2).validate(moe)


def test_auto_plan_respects_batch_cap():
    """With dp capped by the batch, spare devices go to the pipeline."""
    from repro.configs import SURVEY_DEMO, reduced
    from repro.core.partitioner import auto_plan

    cfg = reduced(SURVEY_DEMO, n_layers=8, n_heads=4, n_kv_heads=2, d_ff=256)
    free = auto_plan(cfg, 8, microbatches=4)
    assert (free.dp, free.pp) == (8, 1)      # perfect-DP model: dp wins
    capped = auto_plan(cfg, 8, microbatches=4, max_dp=4)
    assert capped.pp > 1 and capped.dp <= 4
    assert capped.n_devices == 8
    # boundaries are uniform (executable constraint)
    b = capped.stage_boundaries(cfg.n_layers)
    sizes = {b[i + 1] - b[i] for i in range(len(b) - 1)}
    assert len(sizes) == 1


RUNNER_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.pipeline import pipeline_apply

    P, M, D = 4, 8, 16
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("pipe",))
    rng = np.random.RandomState(0)
    stage_params = {"w": jnp.asarray(rng.randn(P, D, D) * 0.3, jnp.float32),
                    "b": jnp.asarray(rng.randn(P, D) * 0.1, jnp.float32)}
    mbs = jnp.asarray(rng.randn(M, 2, D), jnp.float32)

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    out = pipeline_apply(stage_fn, stage_params, mbs, mesh=mesh)

    # sequential reference
    ref = mbs
    for s in range(P):
        ps = {k: v[s] for k, v in stage_params.items()}
        ref = jax.vmap(lambda x: stage_fn(ps, x))(ref)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)

    # gradients flow through the pipeline (AD-reversed schedule)
    def loss(sp):
        y = pipeline_apply(stage_fn, sp, mbs, mesh=mesh)
        return jnp.mean(y ** 2)

    def loss_ref(sp):
        r = mbs
        for s in range(P):
            ps = {k: v[s] for k, v in sp.items()}
            r = jax.vmap(lambda x: stage_fn(ps, x))(r)
        return jnp.mean(r ** 2)

    g = jax.grad(loss)(stage_params)
    gr = jax.grad(loss_ref)(stage_params)
    for k in g:
        np.testing.assert_allclose(np.asarray(g[k]), np.asarray(gr[k]),
                                   rtol=1e-4, atol=1e-5)
    print("PIPELINE_OK")
    """
)


@pytest.mark.multidevice
def test_executable_gpipe_matches_sequential():
    r = subprocess.run(
        [sys.executable, "-c", RUNNER_SCRIPT],
        capture_output=True, text=True, timeout=300,
        env=subprocess_env(),
        cwd=REPO_ROOT,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "PIPELINE_OK" in r.stdout
