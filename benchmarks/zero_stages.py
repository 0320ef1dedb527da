"""ZeRO stages (survey §4.1): per-device memory + collective bytes by stage.

Analytic table for the assigned archs on the production mesh (the survey's
"Partitioning: optim state / + gradients / + parameters" rows), plus a
compiled small-mesh (2x2, subprocess) measurement showing the collective
pattern change: stage 0 all-reduces grads; stage 3 adds per-layer
all-gathers of params (ZeRO's documented comm overhead).
"""
from __future__ import annotations

import os

import subprocess
import sys
import textwrap

from benchmarks.common import emit, header, subprocess_env
from repro.configs import get_config



def analytic() -> None:
    dp, tp = 16, 16
    for arch in ("granite-8b", "granite-34b", "arctic-480b"):
        cfg = get_config(arch)
        n = cfg.param_count()["total"]
        for stage in range(4):
            p = n * 4 / tp / (dp if stage >= 3 else 1)
            g = n * 4 / tp / (dp if stage >= 2 else 1)
            o = n * 8 / tp / (dp if stage >= 1 else 1)
            emit(
                f"zero/analytic/{arch}/stage{stage}", 0.0,
                f"params={p/2**30:.2f}GiB grads={g/2**30:.2f}GiB "
                f"opt={o/2**30:.2f}GiB total={(p+g+o)/2**30:.2f}GiB",
            )


SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    from repro.configs import get_reduced, ShapeSpec
    from repro.launch.train import build_train
    from repro.train import TrainConfig
    from repro.roofline.analysis import collective_bytes

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2), ("data", "model"))
    shape = ShapeSpec("bench", 128, 8, "train")
    cfg_name = "granite-8b"
    import repro.launch.train as LT
    import repro.configs as C
    cfg = C.get_reduced(cfg_name)
    C.registry.ARCHITECTURES[cfg.name] = cfg
    for stage in (0, 1, 2, 3):
        tc = TrainConfig(precision="bf16", remat="none", zero_stage=stage)
        jitted, (s, b) = build_train(cfg.name, mesh, tc, shape)
        compiled = jitted.lower(s, b).compile()
        stats = collective_bytes(compiled.as_text(), 4, trip_hint=cfg.n_layers)
        per = {k: int(v) for k, v in stats.bytes_by_kind.items() if v}
        print(f"STAGE {stage} wire={int(stats.total_bytes)} {per}")
    """
)


def compiled_small_mesh() -> None:
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=900, env=subprocess_env(),
        cwd="/root/repo",
    )
    for ln in r.stdout.splitlines():
        if ln.startswith("STAGE"):
            parts = ln.split(maxsplit=3)
            emit(f"zero/compiled_2x2/stage{parts[1]}", 0.0,
                 f"wire={parts[2].split('=')[1]}B {parts[3]}")
    if r.returncode != 0:
        emit("zero/compiled_2x2/FAILED", 0.0, r.stderr.strip()[-200:])


def main() -> None:
    header("ZeRO stages (survey s4.1)")
    analytic()
    compiled_small_mesh()


if __name__ == "__main__":
    main()
