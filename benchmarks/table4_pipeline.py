"""Survey Table 4 — pipeline-parallel schedules.

Simulator: bubble fraction, peak in-flight activations, weight versions and
staleness per schedule (the columns of Table 4). Executable: the shard_map
GPipe runner timed on 4 fake devices (subprocess keeps this process at 1
device).
"""
from __future__ import annotations

import os

import subprocess
import sys
import textwrap

from benchmarks.common import emit, header, subprocess_env
from repro.core.pipeline import SCHEDULES, simulate



def main() -> None:
    header("Table 4: model/pipeline parallelism strategies")
    P = 8
    for M in (8, 32):
        for name in SCHEDULES:
            r = simulate(name, P, M, v=2)
            emit(
                f"table4/{name}@P{P}M{M}", r.makespan * 1e3,
                f"bubble={r.bubble_fraction:.3f} peak_act={r.peak_activations} "
                f"wcopies={r.weight_versions} "
                f"{'sync' if r.synchronous else f'async(stale<={r.max_staleness})'}",
            )
    _executable()


SCRIPT = textwrap.dedent(
    """
    import os, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core.pipeline import pipeline_apply
    P, M, D, B = 4, 16, 256, 8
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((P,), ("pipe",))
    rng = np.random.RandomState(0)
    sp = {"w": jnp.asarray(rng.randn(P, D, D) * 0.1, jnp.float32)}
    mbs = jnp.asarray(rng.randn(M, B, D), jnp.float32)
    fn = jax.jit(lambda sp, mbs: pipeline_apply(
        lambda p, x: jnp.tanh(x @ p["w"]), sp, mbs, mesh=mesh))
    out = fn(sp, mbs); jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(5):
        jax.block_until_ready(fn(sp, mbs))
    print(f"USPC {(time.perf_counter()-t0)/5*1e6:.1f}")
    """
)


def _executable() -> None:
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=600, env=subprocess_env(),
        cwd="/root/repo",
    )
    us = 0.0
    for ln in r.stdout.splitlines():
        if ln.startswith("USPC"):
            us = float(ln.split()[1])
    emit("table4/executable_gpipe_4stage", us,
         f"shard_map+ppermute runner rc={r.returncode}")


if __name__ == "__main__":
    main()
