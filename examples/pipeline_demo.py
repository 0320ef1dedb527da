"""Pipeline-parallelism demo (survey §3): planner -> simulator -> execution.

1. Partition granite-8b's 36 layers into 4 stages (dyn-prog vs heuristic).
2. Simulate every Table-4 schedule on that partition.
3. Run ``dp_pp_search`` (batch-capped, uniform stages) to pick an
   executable ParallelPlan for 4 devices.
4. Execute that plan end-to-end as a REAL 1F1B pipeline on 4 simulated
   devices (subprocess): `build_train_pipeline` streams microbatches
   through the tick-table runner and the loss matches the single-device
   step on the same batch.

    PYTHONPATH=src python examples/pipeline_demo.py [--stash fp8]
"""
import argparse
import os
import subprocess
import sys
import textwrap

from repro.configs import get_config, get_reduced
from repro.core.partitioner import (
    auto_plan, dp_pp_search, dynprog_partition, heuristic_partition,
    layer_costs_from_config,
)
from repro.core.pipeline import SCHEDULES, simulate, tick_table

def _subprocess_env():
    """Inherit the environment (a bare env hangs jax backend probing on CPU
    containers), pinned to the CPU: the children fake host devices, and a
    child that reached for the TPU would fight its parent for the chip.
    Scripts set their own XLA_FLAGS."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env




def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--stash", default="raw", choices=("raw", "int8", "fp8"),
                    help="activation-slot storage for the executable run "
                         "(core.stash; quantized slots loosen the "
                         "single-device loss match)")
    args = ap.parse_args()
    cfg = get_config("granite-8b")
    costs = layer_costs_from_config(cfg)
    P, M = 4, 16
    dp = dynprog_partition(costs, P)
    he = heuristic_partition(costs, P)
    print(f"partitioning {cfg.name} ({cfg.n_layers} layers) into {P} stages:")
    print(f"  dynprog  : bounds={dp.boundaries} bottleneck={dp.bottleneck:.3g}")
    print(f"  heuristic: bounds={he.boundaries} bottleneck={he.bottleneck:.3g}")

    choice = dp_pp_search(costs, n_devices=16, microbatches=M)
    print(f"  best (dp, pp) on 16 devices @ M={M}: ({choice.dp}, {choice.pp})")

    print(f"\nschedules @ P={P}, M={M} (t_bwd = 2 t_fwd):")
    for name in SCHEDULES:
        r = simulate(name, P, M)
        sync = "sync " if r.synchronous else f"async(stale<={r.max_staleness})"
        print(f"  {name:14s} bubble={r.bubble_fraction:.3f} "
              f"peak_act={r.peak_activations:3d} wcopies={r.weight_versions} {sync}")

    # planner -> executable plan for the 4 simulated devices below; the
    # batch cap (dp <= batch/microbatches) is what pushes devices into pp
    tiny = get_reduced("granite-8b")
    plan = auto_plan(tiny, 4, microbatches=4, schedule="1f1b", max_dp=2,
                     stash=args.stash)
    tt = tick_table(plan.schedule, plan.pp, plan.microbatches)
    print(f"\nauto plan for 4 devices (batch-capped dp<=2): {plan.describe()}")
    print(f"  1f1b act slots/device: {tt.n_act_slots} "
          f"(gpipe would hold {plan.microbatches})")
    rep = plan.stash_report(tiny, global_batch=8, seq_len=64, itemsize=4)
    print(f"  stash={rep['backend']}: {rep['bytes_per_slot']} B/slot "
          f"(raw {rep['raw_bytes_per_slot']} B), "
          f"capacity {rep['capacity_factor']:.2f}x raw")

    print("\nexecutable 1F1B on 4 simulated devices (plan above):")
    r = subprocess.run(
        [sys.executable, "-c", _RUNNER.format(
            dp=plan.dp, tp=plan.tp, pp=plan.pp, M=plan.microbatches,
            stash=plan.stash, rtol=2e-3 if plan.stash == "raw" else 5e-2)],
        text=True, timeout=900,
        env=_subprocess_env(),
    )
    assert r.returncode == 0
    print("pipeline_demo OK")


_RUNNER = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import ShapeSpec, get_reduced
    import repro.configs.registry as registry
    from repro.core.partitioner import ParallelPlan
    from repro.data import DataPipeline
    from repro.launch.mesh import make_train_mesh
    from repro.launch.train import build_train_pipeline
    from repro.optim import get as get_opt
    from repro.train import TrainConfig, make_state, make_train_step

    cfg = get_reduced("granite-8b")
    registry.ARCHITECTURES[cfg.name] = cfg
    B, SEQ = 8, 64
    plan = ParallelPlan(dp={dp}, tp={tp}, pp={pp}, microbatches={M},
                        schedule="1f1b", stash="{stash}").validate(cfg)
    tc = TrainConfig(precision="f32", log_every=1)
    opt = get_opt(tc.optimizer, tc.lr)
    data = DataPipeline(cfg, batch_size=B, seq_len=SEQ, seed=0)
    batch_np = {{k: np.asarray(v) for k, v in dict(next(data)).items()}}
    data.close()

    mesh = make_train_mesh(plan.dp, plan.tp, plan.pp)
    jitted, (s_struct, b_struct) = build_train_pipeline(
        cfg.name, mesh, plan, tc, ShapeSpec("t", SEQ, B, "train"))
    state = jax.tree.map(lambda x, st: jax.device_put(x, st.sharding),
                         make_state(cfg, opt, tc), s_struct)
    batch = jax.tree.map(
        lambda v, st: jax.device_put(jnp.asarray(v), st.sharding),
        batch_np, b_struct)
    _, m3d = jitted(state, batch)

    step1 = make_train_step(cfg, opt, tc)
    _, m1 = step1(make_state(cfg, opt, tc),
                  {{k: jnp.asarray(v) for k, v in batch_np.items()}})
    l3d, l1 = float(m3d["loss"]), float(m1["loss"])
    assert abs(l3d - l1) < {rtol} * abs(l1), (l3d, l1)
    print(f"  1F1B on {{plan.describe()}}: loss={{l3d:.4f}} "
          f"(single-device: {{l1:.4f}})")
    """
)

if __name__ == "__main__":
    main()
