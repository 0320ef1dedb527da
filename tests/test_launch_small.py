"""Launch-path integration on a small (2x2) mesh via subprocess:
reduced archs x all four shape kinds must lower + compile + RUN a step.

This is the executable twin of the 512-device dry-run: same build_train /
build_prefill / build_decode code, real numerics on 4 fake devices.
"""
import os
import subprocess
import sys
import textwrap

from _subproc import REPO_ROOT, subprocess_env

import pytest

pytestmark = pytest.mark.multidevice


SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_reduced, ShapeSpec
    import repro.configs.registry as registry
    from repro.launch.train import build_decode, build_prefill, build_train
    from repro.train import TrainConfig
    from repro.optim import get as get_opt
    from repro.train import make_state

    ARCH = "{arch}"
    cfg = get_reduced(ARCH)
    registry.ARCHITECTURES[cfg.name] = cfg
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 2), ("data", "model"))
    tc = TrainConfig(precision="f32", remat="full", zero_stage={zero})

    # ---- train ----
    shape = ShapeSpec("t", 64, 8, "train")
    jitted, (s_struct, b_struct) = build_train(cfg.name, mesh, tc, shape)
    state = make_state(cfg, get_opt(tc.optimizer, tc.lr), tc)
    state = jax.tree.map(lambda x, st: jax.device_put(x, st.sharding), state, s_struct)
    rng = np.random.RandomState(0)
    batch = {{
        "tokens": rng.randint(0, cfg.vocab_size, (8, 64)).astype(np.int32),
        "labels": rng.randint(0, cfg.vocab_size, (8, 64)).astype(np.int32),
    }}
    if cfg.frontend is not None:
        batch["frontend_embeds"] = rng.randn(8, cfg.frontend_tokens, cfg.d_model).astype(np.float32)
    batch = jax.tree.map(lambda v, st: jax.device_put(jnp.asarray(v), st.sharding), batch, b_struct)
    state2, metrics = jitted(state, batch)   # donates `state`
    loss1 = float(metrics["loss"])
    state3, metrics = jitted(state2, batch)  # donates `state2`
    assert np.isfinite(loss1) and np.isfinite(float(metrics["loss"]))
    assert float(metrics["loss"]) < loss1 + 1.0

    # ---- prefill ----
    pshape = ShapeSpec("p", 64, 4, "prefill")
    jit_p, (p_struct, pb_struct) = build_prefill(cfg.name, mesh, pshape, tc)
    params = jax.tree.map(lambda x, st: jax.device_put(x, st.sharding),
                          state3["params"], p_struct)
    pb = {{"tokens": batch["tokens"][:4]}}
    if cfg.frontend is not None:
        pb["frontend_embeds"] = batch["frontend_embeds"][:4]
    pb = jax.tree.map(lambda v, st: jax.device_put(jnp.asarray(v), st.sharding), pb, pb_struct)
    logits = jit_p(params, pb)
    assert np.isfinite(np.asarray(logits)).all()

    # ---- decode ----
    dshape = ShapeSpec("d", 64, 4, "decode")
    jit_d, (pd_struct, c_struct, t_struct) = build_decode(cfg.name, mesh, dshape, tc)
    cache = jax.tree.map(lambda st: jnp.zeros(st.shape, st.dtype), c_struct)
    cache = jax.tree.map(lambda x, st: jax.device_put(x, st.sharding), cache, c_struct)
    tok = jax.device_put(jnp.zeros((4,), jnp.int32), t_struct.sharding)
    logits, new_cache = jit_d(params, cache, tok)
    assert np.isfinite(np.asarray(logits)).all()
    print("LAUNCH_OK", ARCH)
    """
)


@pytest.mark.parametrize(
    "arch,zero",
    [
        ("granite-8b", 1),
        ("gemma3-1b", 3),
        ("qwen3-moe-30b-a3b", 2),
        ("falcon-mamba-7b", 1),
        ("recurrentgemma-2b", 0),
        ("seamless-m4t-medium", 1),
        ("phi-3-vision-4.2b", 3),
    ],
)
def test_launch_small_mesh(arch, zero):
    r = subprocess.run(
        [sys.executable, "-c", SCRIPT.format(arch=arch, zero=zero)],
        capture_output=True, text=True, timeout=1200,
        env=subprocess_env(),
        cwd=REPO_ROOT,
    )
    assert r.returncode == 0, r.stderr[-4000:]
    assert f"LAUNCH_OK {arch}" in r.stdout
