"""ZeRO/FSDP demo on 8 simulated devices (survey §4.1).

Spawns a subprocess with 8 fake CPU devices (so the parent process keeps its
single-device view), builds the distributed trainer at every ZeRO stage on a
(4 data x 2 model) mesh, runs REAL steps, and prints per-device memory +
collective traffic per stage.

    PYTHONPATH=src python examples/zero_fsdp_demo.py
"""
import os
import subprocess
import sys
import textwrap

def _subprocess_env():
    """Inherit the environment (a bare env hangs jax backend probing on CPU
    containers), pinned to the CPU: the children fake host devices, and a
    child that reached for the TPU would fight its parent for the chip.
    Scripts set their own XLA_FLAGS."""
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env



SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_reduced, ShapeSpec
    import repro.configs.registry as registry
    from repro.launch.train import build_train
    from repro.train import TrainConfig
    from repro.data import DataPipeline
    from repro.roofline.analysis import collective_bytes

    cfg = get_reduced("granite-8b")
    registry.ARCHITECTURES[cfg.name] = cfg
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2), ("data", "model"))
    shape = ShapeSpec("demo", 64, 16, "train")

    data = DataPipeline(cfg, shape.global_batch, shape.seq_len, seed=0)
    raw = next(data); data.close()

    for stage in (0, 1, 2, 3):
        tc = TrainConfig(precision="f32", zero_stage=stage, log_every=1)
        jitted, (s_struct, b_struct) = build_train(cfg.name, mesh, tc, shape)

        # materialize real sharded state from the structs
        from repro.optim import get as get_opt
        from repro.train import make_state
        state = make_state(cfg, get_opt(tc.optimizer, tc.lr), tc)
        state = jax.tree.map(
            lambda x, st: jax.device_put(x, st.sharding), state, s_struct)
        batch = jax.tree.map(
            lambda v, st: jax.device_put(jnp.asarray(v), st.sharding),
            dict(raw), b_struct)

        compiled = jitted.lower(s_struct, b_struct).compile()
        mem = compiled.memory_analysis()
        wire = collective_bytes(compiled.as_text(), 8, cfg.n_layers).total_bytes
        losses = []
        for i in range(3):
            state, metrics = jitted(state, batch)
            losses.append(float(metrics["loss"]))
        print(f"stage{stage}: args={float(mem.argument_size_in_bytes)/2**20:8.1f}MiB "
              f"wire={wire/2**20:8.1f}MiB losses={[round(l,3) for l in losses]}")
    print("ZERO_DEMO_OK")
    """
)


def main() -> None:
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], text=True, timeout=1800,
        env=_subprocess_env(),
        cwd=".",
    )
    assert r.returncode == 0


if __name__ == "__main__":
    main()
