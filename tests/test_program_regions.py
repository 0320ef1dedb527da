"""The train programs carry the names the device trace is read by.

Each region of the train step runs under a fixed ``jax.named_scope``
(``embed``, ``attn``, ``mlp``, ``norm``, ``lm_head``, ``optimizer``); XLA
keeps the scope path as every instruction's ``op_name``, which the chip
benchmark maps to per-region device time. The jitted programs have stable
module names, and every Pallas call passes its ``name=`` (the name of its
events in the trace). Checked here on the CPU at reduced widths, on the
compiled single-device step and the 1F1B pipeline step.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.extend import core as jex

from repro.configs import ShapeSpec, launch_config
from repro.core.partitioner import ParallelPlan
from repro.launch.mesh import make_mesh, make_train_mesh
from repro.launch.train import build_train, build_train_pipeline
from repro.train import TrainConfig

REGIONS = ("embed", "attn", "mlp", "norm", "lm_head", "optimizer")
SHAPE = ShapeSpec("regions", 64, 2, "train")


@pytest.fixture(scope="module")
def programs():
    """{program: compiled HLO text} at granite-8b's reduced widths."""
    cfg = launch_config("granite-8b", layers=2)
    tc = TrainConfig(precision="bf16", remat="full", fused_backward=True)
    mesh = make_mesh((1, 1), ("data", "model"))
    step, (s, b) = build_train(cfg.name, mesh, tc, SHAPE)
    plan = ParallelPlan(dp=1, tp=1, pp=1, microbatches=2, schedule="1f1b",
                        remat="full").validate(cfg)
    ptc = TrainConfig(precision="bf16", remat="full", pipe=1, microbatches=2)
    pstep, (ps, pb) = build_train_pipeline(cfg.name, make_train_mesh(1, 1, 1),
                                           plan, ptc, SHAPE)
    return {"train_step": step.lower(s, b).compile().as_text(),
            "pipeline_train_step": pstep.lower(ps, pb).compile().as_text()}


@pytest.mark.parametrize("program", ["train_step", "pipeline_train_step"])
def test_module_name(programs, program):
    assert re.match(rf"HloModule jit_{program}\b", programs[program])


@pytest.mark.parametrize("region", REGIONS)
@pytest.mark.parametrize("program", ["train_step", "pipeline_train_step"])
def test_region_scope(programs, program, region):
    """Some op's path holds the region as a scope of its own, bare or in a
    transform marker (``jvp(mlp)``, ``transpose(jvp(mlp))``)."""
    paths = set(re.findall(r'op_name="([^"]*)"', programs[program]))
    scope = re.compile(rf"(^|/|\(){region}(\)|/|$)")
    assert any(scope.search(p) for p in paths)


def _pallas_names(fn, *args):
    """The ``name`` of every pallas_call in ``fn``'s jaxpr, nested ones too."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for v in eqn.params.values():
                for sub in v if isinstance(v, (list, tuple)) else [v]:
                    if isinstance(sub, jex.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jex.Jaxpr):
                        walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


def _flash_fwd():
    from repro.kernels.flash_attention.kernel import flash_attention_fwd_flat

    q = jnp.ones((4, 128, 64), jnp.float32)
    kv = jnp.ones((2, 128, 64), jnp.float32)
    return (lambda q, k, v: flash_attention_fwd_flat(q, k, v, group=2,
                                                     interpret=True),
            q, kv, kv)


def _flash_bwd():
    from repro.kernels.flash_attention.kernel_bwd import flash_attention_bwd_flat

    q = jnp.ones((4, 128, 64), jnp.float32)
    kv = jnp.ones((2, 128, 64), jnp.float32)
    lse = jnp.zeros((4, 1, 128), jnp.float32)
    return (lambda q, k, v, o, lse, do: flash_attention_bwd_flat(
        q, k, v, o, lse, do, group=2, interpret=True), q, kv, kv, q, lse, q)


def _rmsnorm(bwd):
    from repro.kernels.rmsnorm.kernel import rmsnorm_bwd_pallas, rmsnorm_pallas

    x, s = jnp.ones((16, 256), jnp.float32), jnp.ones((256,), jnp.float32)
    if bwd:
        return (lambda x, s, g: rmsnorm_bwd_pallas(x, s, g, interpret=True),
                x, s, x)
    return lambda x, s: rmsnorm_pallas(x, s, interpret=True), x, s


def _paged():
    from repro.kernels.paged_attention.kernel import paged_prefill_attention_kernel

    B, N, KV, PAGE, HD, G, P = 2, 8, 2, 16, 128, 2, 4
    q = jnp.ones((B, 1, KV, G, HD), jnp.float32)
    pool = jnp.ones((N, KV, PAGE, HD), jnp.float32)
    tables = jnp.zeros((B, P), jnp.int32)
    rows = jnp.ones((B,), jnp.int32)
    return (lambda q, k, v, t, s, n: paged_prefill_attention_kernel(
        q, k, v, t, s, n, interpret=True), q, pool, pool, tables, rows, rows)


def _quant(which):
    from repro.kernels.blockwise_quant import kernel as bq

    x = jnp.ones((bq.TILE_ROWS * bq.BLOCK,), jnp.float32)
    if which == "quantize_pallas":
        return lambda x: bq.quantize_pallas(x, interpret=True), x
    if which == "dequantize_pallas":
        idx = jnp.zeros((bq.TILE_ROWS * bq.BLOCK,), jnp.uint8)
        scale = jnp.ones((bq.TILE_ROWS,), jnp.float32)
        return lambda i, s: bq.dequantize_pallas(i, s, interpret=True), idx, scale
    xb = jnp.ones((8, bq.BLOCK), jnp.float32)
    if which == "stash_quantize_pallas":
        return lambda x: bq.stash_quantize_pallas(x, interpret=True), xb
    codes = jnp.zeros((8, bq.BLOCK), jnp.int8)
    return (lambda c, s: bq.stash_dequantize_pallas(c, s, interpret=True),
            codes, jnp.ones((8,), jnp.float32))


KERNELS = {
    "flash_attention_fwd_flat": (_flash_fwd, ["flash_attention_fwd_flat"]),
    "flash_attention_bwd_flat": (_flash_bwd, [
        "flash_attention_bwd_flat_delta", "flash_attention_bwd_flat_dq",
        "flash_attention_bwd_flat_dkv"]),
    "rmsnorm_pallas": (lambda: _rmsnorm(False), ["rmsnorm_pallas"]),
    "rmsnorm_bwd_pallas": (lambda: _rmsnorm(True), ["rmsnorm_bwd_pallas"]),
    "paged_prefill_attention_kernel": (_paged, ["paged_prefill_attention_kernel"]),
    **{k: (lambda k=k: _quant(k), [k]) for k in (
        "quantize_pallas", "dequantize_pallas", "stash_quantize_pallas",
        "stash_dequantize_pallas")},
}


@pytest.mark.parametrize("kernel", list(KERNELS))
def test_pallas_call_names(kernel):
    make, want = KERNELS[kernel]
    fn, *args = make()
    assert _pallas_names(fn, *args) == want
