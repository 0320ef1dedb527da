"""Gradient compression: loopback correctness, EF accumulation, bytes, and
multi-device sync via subprocess shard_map (8 fake devices)."""
import subprocess
import sys
import textwrap

from _subproc import REPO_ROOT, subprocess_env

from _hyp_compat import hypothesis, st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.compression import (
    PowerSGD,
    QSGD,
    SignEF,
    TopK,
    init_state,
    sync,
    wire_bytes_dense,
)

PARAMS = {"w": jnp.zeros((64, 64)), "b": jnp.zeros((8,))}


def grads_like(seed=0, scale=1.0):
    rng = np.random.RandomState(seed)
    return {
        "w": jnp.asarray(rng.randn(64, 64).astype(np.float32)) * scale,
        "b": jnp.asarray(rng.randn(8).astype(np.float32)),
    }


@pytest.mark.parametrize(
    "method", [TopK(0.05), QSGD(8), SignEF(), PowerSGD(rank=8)],
    ids=["topk", "qsgd", "sign", "powersgd"],
)
def test_loopback_reasonable_approximation(method):
    g = grads_like()
    st_ = init_state(method, PARAMS)
    ghat, st2, nbytes = sync(method, g, st_, axis_name=None)
    # small leaf rides psum untouched
    np.testing.assert_allclose(np.asarray(ghat["b"]), np.asarray(g["b"]))
    # compressed leaf correlates with the true gradient
    a = np.asarray(ghat["w"]).ravel()
    b = np.asarray(g["w"]).ravel()
    cos = (a @ b) / (np.linalg.norm(a) * np.linalg.norm(b) + 1e-12)
    assert cos > 0.3, cos
    assert float(nbytes) < wire_bytes_dense(g)


def test_qsgd_high_bits_near_exact():
    g = grads_like()
    ghat, _, _ = sync(QSGD(8), g, None, axis_name=None)
    rel = np.linalg.norm(np.asarray(ghat["w"] - g["w"])) / np.linalg.norm(
        np.asarray(g["w"])
    )
    assert rel < 0.05, rel


def test_error_feedback_accumulates_dropped_mass():
    """With EF, repeatedly compressing the SAME gradient must converge:
    sum of transmitted approximations -> the true gradient direction."""
    method = TopK(0.02)
    g = grads_like(3)
    state = init_state(method, PARAMS)
    acc = jnp.zeros_like(g["w"])
    for _ in range(60):
        ghat, state, _ = sync(method, g, state, axis_name=None)
        acc = acc + ghat["w"]
    # mean transmitted ~ g after enough rounds (EF theorem)
    mean = np.asarray(acc / 60)
    rel = np.linalg.norm(mean - np.asarray(g["w"])) / np.linalg.norm(
        np.asarray(g["w"])
    )
    assert rel < 0.35, rel


def test_powersgd_rank_recovers_lowrank_gradient():
    u = np.random.RandomState(0).randn(64, 4).astype(np.float32)
    v = np.random.RandomState(1).randn(4, 64).astype(np.float32)
    g = {"w": jnp.asarray(u @ v), "b": jnp.zeros(8)}
    method = PowerSGD(rank=8)
    state = init_state(method, {"w": g["w"], "b": g["b"]})
    ghat = g
    for _ in range(3):  # a few power iterations via repeated sync
        ghat, state, _ = sync(method, g, state, axis_name=None)
    rel = np.linalg.norm(np.asarray(ghat["w"] - g["w"])) / np.linalg.norm(
        np.asarray(g["w"])
    )
    assert rel < 0.05, rel


def test_bytes_accounting_ordering():
    g = grads_like()
    dense = wire_bytes_dense(g)
    got = {}
    for m in [TopK(0.01), QSGD(8), SignEF(), PowerSGD(4)]:
        st_ = init_state(m, PARAMS)
        _, _, b = sync(m, g, st_, axis_name=None)
        got[m.name] = float(b)
    # topk@1% sends ~1% of elements (8B each) — below even 1-bit sign
    assert got["topk"] < got["sign"] < got["qsgd"] < dense
    assert got["qsgd"] < dense / 3.9  # ~4x from f32->int8
    assert got["powersgd"] < dense / 4


@hypothesis.given(
    ratio=st.floats(0.01, 0.5), seed=st.integers(0, 20), scale=st.floats(1e-3, 1e3)
)
@hypothesis.settings(max_examples=15, deadline=None)
def test_property_topk_never_increases_norm(ratio, seed, scale):
    g = grads_like(seed, scale)
    method = TopK(ratio)
    ghat, _, _ = sync(method, g, init_state(method, PARAMS), axis_name=None)
    assert float(jnp.linalg.norm(ghat["w"])) <= float(jnp.linalg.norm(g["w"])) * 1.001


MULTIDEV_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.core.compression import TopK, QSGD, init_state, sync

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("data",))
    g_global = jnp.asarray(np.random.RandomState(0).randn(8, 64, 64), jnp.float32)

    def per_shard(g):   # g: (1, 64, 64) local shard
        grads = {"w": g[0]}
        ghat, _, _ = sync(QSGD(8), grads, None, axis_name="data")
        return ghat["w"][None]

    fn = jax.jit(shard_map(per_shard, mesh=mesh,
        in_specs=P("data"), out_specs=P("data"), check_vma=False))
    out = np.asarray(fn(g_global))
    want = np.asarray(jnp.mean(g_global, 0))
    for i in range(8):
        rel = np.linalg.norm(out[i] - want) / np.linalg.norm(want)
        assert rel < 0.05, rel
    # all shards agree (it was a collective mean)
    assert np.allclose(out[0], out[7], atol=1e-5)
    print("MULTIDEV_OK")
    """
)


@pytest.mark.multidevice
def test_multidevice_compressed_sync_subprocess():
    r = subprocess.run(
        [sys.executable, "-c", MULTIDEV_SCRIPT],
        capture_output=True, text=True, timeout=300,
        env=subprocess_env(),
        cwd=REPO_ROOT,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "MULTIDEV_OK" in r.stdout
