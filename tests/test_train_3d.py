"""3D (data x model x pipe) training subprocess suite.

Covers the executable-pipeline acceptance bar: 3D meshes train with losses
matching the single-device step, 1F1B gradients are bitwise-equal to GPipe
on anchored shapes (with the O(P)-vs-O(M) activation-slot gap), and a
checkpoint saved under one ParallelPlan restores into a different
(dp, tp, pp) layout (reshard-on-load).
"""
import subprocess
import sys
import textwrap

from _subproc import REPO_ROOT, subprocess_env

import pytest

pytestmark = pytest.mark.multidevice

PRELUDE = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import SURVEY_DEMO, ShapeSpec, reduced
    import repro.configs.registry as registry
    from repro.core.partitioner import ParallelPlan
    from repro.data import DataPipeline
    from repro.launch.mesh import make_train_mesh
    from repro.launch.train import build_train, build_train_pipeline
    from repro.optim import get as get_opt
    from repro.train import TrainConfig, make_state, make_train_step

    TINY = reduced(SURVEY_DEMO, n_layers=4, d_model=128, n_heads=4,
                   n_kv_heads=2, d_ff=256, vocab_size=512)
    registry.ARCHITECTURES[TINY.name] = TINY
    B, SEQ, M = 8, 32, 4
    tc = TrainConfig(precision="f32", remat="none", log_every=1)
    opt = get_opt(tc.optimizer, tc.lr)

    def batches(steps, seed=0):
        data = DataPipeline(TINY, batch_size=B, seq_len=SEQ, seed=seed)
        out = [{k: np.asarray(v) for k, v in dict(next(data)).items()}
               for _ in range(steps)]
        data.close()
        return out

    def put(tree, structs):
        return jax.tree.map(
            lambda v, st: jax.device_put(jnp.asarray(v), st.sharding),
            tree, structs)
    """
)


def run(script: str, marker: str, timeout: int = 900) -> None:
    r = subprocess.run(
        [sys.executable, "-c", PRELUDE + textwrap.dedent(script)],
        capture_output=True, text=True, timeout=timeout,
        env=subprocess_env(), cwd=REPO_ROOT,
    )
    assert r.returncode == 0, r.stderr[-4000:]
    assert marker in r.stdout, r.stdout[-2000:]


def test_3d_losses_match_single_device():
    """2x1x2 / 1x2x2 / 2x2x2 plans track the single-device trajectory."""
    run(
        """
        STEPS = 5
        BATCHES = batches(STEPS)
        step1 = make_train_step(TINY, opt, tc)
        state1 = make_state(TINY, opt, tc)
        ref = []
        for b in BATCHES:
            state1, m = step1(state1, {k: jnp.asarray(v) for k, v in b.items()})
            ref.append(float(m["loss"]))
        for (dp, tp, pp) in [(2, 1, 2), (1, 2, 2), (2, 2, 2)]:
            plan = ParallelPlan(dp=dp, tp=tp, pp=pp, microbatches=M,
                                schedule="1f1b").validate(TINY)
            mesh = make_train_mesh(dp, tp, pp)
            jitted, (s_struct, b_struct) = build_train_pipeline(
                TINY.name, mesh, plan, tc, ShapeSpec("t", SEQ, B, "train"))
            state = put(make_state(TINY, opt, tc), s_struct)
            losses = []
            for b in BATCHES:
                state, m = jitted(state, put(dict(b), b_struct))
                losses.append(float(m["loss"]))
            np.testing.assert_allclose(losses, ref, rtol=2e-3, atol=2e-4)
            print(f"{dp}x{tp}x{pp} ok", losses[-1])
        print("LOSSES_3D_OK")
        """,
        "LOSSES_3D_OK",
    )


def test_1f1b_matches_gpipe_bitwise():
    """Same params/batch: 1F1B grads == GPipe grads exactly, O(P) slots."""
    run(
        """
        from repro.core.pipeline import tick_table
        PP, MM = 2, 8   # M >= 2*P: the memory gap regime
        t1, tg = tick_table("1f1b", PP, MM), tick_table("gpipe", PP, MM)
        assert t1.n_act_slots < tg.n_act_slots, (t1.n_act_slots, tg.n_act_slots)
        assert t1.n_act_slots == min(PP, MM) and tg.n_act_slots == MM

        BATCH = batches(1)[0]
        outs = {}
        for sched in ("gpipe", "1f1b"):
            plan = ParallelPlan(dp=2, tp=2, pp=PP, microbatches=MM,
                                schedule=sched).validate(TINY)
            mesh = make_train_mesh(2, 2, PP)
            jitted, (s_struct, b_struct) = build_train_pipeline(
                TINY.name, mesh, plan, tc, ShapeSpec("t", SEQ, B, "train"))
            state = put(make_state(TINY, opt, tc), s_struct)
            new_state, m = jitted(state, put(dict(BATCH), b_struct))
            outs[sched] = (
                jax.tree.map(np.asarray, new_state["params"]),
                float(m["loss"]), float(m["grad_norm"]),
            )
        assert outs["gpipe"][1] == outs["1f1b"][1], "loss not bitwise equal"
        assert outs["gpipe"][2] == outs["1f1b"][2], "grad_norm not bitwise equal"
        ga, gb = outs["gpipe"][0], outs["1f1b"][0]
        for (pa, a), (pb, bb) in zip(
            jax.tree_util.tree_flatten_with_path(ga)[0],
            jax.tree_util.tree_flatten_with_path(gb)[0],
        ):
            np.testing.assert_array_equal(a, bb, err_msg=str(pa))
        print("BITWISE_OK")
        """,
        "BITWISE_OK",
    )


def test_1f1b_matches_gpipe_bitwise_quant_cotangents():
    """Under an int8 stash that also codes the cotangents, 1F1B (whose last
    stage runs each backward at its forward tick) stays bitwise GPipe."""
    run(
        """
        PP = 2
        BATCH = batches(1)[0]
        outs = {}
        for sched in ("gpipe", "1f1b"):
            plan = ParallelPlan(dp=1, tp=2, pp=PP, microbatches=M,
                                schedule=sched, stash="int8",
                                stash_cot=True).validate(TINY)
            mesh = make_train_mesh(1, 2, PP)
            jitted, (s_struct, b_struct) = build_train_pipeline(
                TINY.name, mesh, plan, tc, ShapeSpec("t", SEQ, B, "train"))
            state = put(make_state(TINY, opt, tc), s_struct)
            new_state, m = jitted(state, put(dict(BATCH), b_struct))
            outs[sched] = (jax.tree.map(np.asarray, new_state["params"]),
                           float(m["loss"]), float(m["grad_norm"]))
        assert outs["gpipe"][1:] == outs["1f1b"][1:], (outs["gpipe"][1:],
                                                       outs["1f1b"][1:])
        for a, bb in zip(jax.tree.leaves(outs["gpipe"][0]),
                         jax.tree.leaves(outs["1f1b"][0])):
            np.testing.assert_array_equal(a, bb)
        print("QUANT_COT_BITWISE_OK")
        """,
        "QUANT_COT_BITWISE_OK",
    )


def test_checkpoint_reshard_on_load():
    """Save under one plan, restore into another (dp, tp, pp) and into the
    2D trainer; both continue with identical step outputs."""
    run(
        """
        import tempfile
        from repro.checkpoint import restore_resharded, save

        shape = ShapeSpec("t", SEQ, B, "train")
        BATCHES = batches(3)

        plan_a = ParallelPlan(dp=2, tp=1, pp=2, microbatches=M).validate(TINY)
        mesh_a = make_train_mesh(2, 1, 2)
        jit_a, (sa_struct, ba_struct) = build_train_pipeline(
            TINY.name, mesh_a, plan_a, tc, shape)
        state = put(make_state(TINY, opt, tc), sa_struct)
        state, _ = jit_a(state, put(dict(BATCHES[0]), ba_struct))

        with tempfile.TemporaryDirectory() as d:
            save(d, 1, jax.tree.map(np.asarray, state))

            # restore into a different 3D plan
            plan_b = ParallelPlan(dp=1, tp=2, pp=2, microbatches=M).validate(TINY)
            mesh_b = make_train_mesh(1, 2, 2)
            jit_b, (sb_struct, bb_struct) = build_train_pipeline(
                TINY.name, mesh_b, plan_b, tc, shape)
            state_b = restore_resharded(d, sb_struct)
            for la, lb in zip(jax.tree.leaves(state), jax.tree.leaves(state_b)):
                np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))

            # and into the plain 2D pjit trainer
            from repro.launch.mesh import make_mesh
            mesh_c = make_mesh((2, 2), ("data", "model"))
            jit_c, (sc_struct, bc_struct) = build_train(
                TINY.name, mesh_c, tc, shape)
            state_c = restore_resharded(d, sc_struct)

            state_b, mb_ = jit_b(state_b, put(dict(BATCHES[1]), bb_struct))
            state_c, mc_ = jit_c(state_c, put(dict(BATCHES[1]), bc_struct))
            np.testing.assert_allclose(
                float(mb_["loss"]), float(mc_["loss"]), rtol=2e-3)
        print("RESHARD_OK")
        """,
        "RESHARD_OK",
    )
