"""Training loop: composes model, precision, remat, compression, optimizer.

``make_train_step`` builds a jitted step for three execution modes:

* ``single``        — one device (smoke tests / examples).
* ``dp_compressed`` — shard_map over a ``data`` mesh axis with replicated
                      params and compressed gradient sync (survey §4.3's
                      data-parallel setting; see DESIGN.md §4).
* distributed pjit (TP x DP x ZeRO) lives in ``repro.launch.train`` — it
  needs mesh/sharding context this module stays free of.

The loop itself (``fit``) is mode-agnostic: it pulls batches, calls the
step, handles checkpoints and logging.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.configs.base import ArchConfig
from repro.core import compression as comp_mod
from repro.core.precision import (
    PrecisionPolicy,
    init_scale_state,
    scale_loss,
    unscale_and_check,
)
from repro.models import Runtime, init_params, loss_fn
from repro.optim import Optimizer, apply_updates, clip_by_global_norm


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "adamw"
    lr: Any = 3e-4
    grad_clip: float = 1.0
    precision: str = "f32"            # f32 | bf16 | fp16
    remat: str = "none"               # none | full | dots | offload
    remat_period: int = 1             # checkpoint every k-th scan unit (§2.1 plans)
    fused_backward: bool = False      # fused Pallas backwards + chunked-CE head
    compression: Any = None           # repro.core.compression method or None
    zero_stage: int = 0               # used by the distributed trainer
    moe_mode: str = "auto"            # auto (pjit) | ep (shard_map expert-parallel)
    seq_shard: str = ""               # activation sharding: "" | "seq" | "hidden"
    scan_mode: str = "assoc"          # mamba scan: assoc | chunked
    ssm_seqpar: bool = False          # distributed selective scan over 'model'
    # 3D pipeline training (repro.launch.train.build_train_pipeline):
    # pipe > 1 runs the executable 1F1B/GPipe schedule over a `pipe` mesh
    # axis, streaming `microbatches` per step (the degrees become a
    # core.partitioner.ParallelPlan).
    pipe: int = 1                     # pipeline stages (pp degree)
    microbatches: int = 1             # microbatches per step (pipeline mode)
    schedule: str = "1f1b"            # executable schedule: 1f1b | gpipe
    stash: str = "raw"                # activation-slot storage: raw|int8|fp8|host
    fused_stash: bool = False         # stash codec via the fused Pallas kernels
    stash_cot: bool = False           # quantize cotangent slots too (int8/fp8)
    log_every: int = 10
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 0


def make_state(
    cfg: ArchConfig, opt: Optimizer, tc: TrainConfig, seed: int = 0
) -> Dict[str, Any]:
    params = init_params(cfg, jax.random.PRNGKey(seed))
    policy = getattr(PrecisionPolicy, tc.precision)()
    return {
        "params": params,
        "opt": opt.init(params),
        "scale": init_scale_state(policy),
        "comp": comp_mod.init_state(tc.compression, params),
        "step": jnp.zeros((), jnp.int32),
    }


def _runtime(cfg: ArchConfig, tc: TrainConfig) -> Runtime:
    policy = getattr(PrecisionPolicy, tc.precision)()
    return Runtime(dtype=policy.compute_dtype, remat=tc.remat,
                   remat_period=tc.remat_period,
                   fused_backward=tc.fused_backward,
                   use_flash_kernel=tc.fused_backward,
                   fused_stash=tc.fused_stash)


@jax.named_scope("optimizer")
def finish_step(
    state: Dict[str, Any],
    grads: Any,
    metrics: Dict[str, jax.Array],
    tc: TrainConfig,
    policy,
    opt: Optimizer,
    axis_name: Optional[str] = None,
) -> Tuple[Dict[str, Any], Dict[str, jax.Array]]:
    """Shared train-step tail: unscale/check grads, sync (pmean or
    compressed), clip, optimizer update with the non-finite guard, rebuild
    state, finalize metrics. Used by ``core_step`` here and by the 3D
    pipeline step (repro.launch.train), whose grads arrive pre-reduced."""
    grads, scale_state, finite = unscale_and_check(grads, state["scale"], policy)

    if axis_name is not None and tc.compression is None:
        grads = jax.tree.map(lambda g: jax.lax.pmean(g, axis_name), grads)
        comp_state = state["comp"]
        wire = jnp.asarray(comp_mod.wire_bytes_dense(grads), jnp.float32)
    elif tc.compression is not None:
        grads, comp_state, wire = comp_mod.sync(
            tc.compression, grads, state["comp"], axis_name
        )
    else:
        comp_state = state["comp"]
        wire = jnp.zeros((), jnp.float32)

    if tc.grad_clip:
        grads, gnorm = clip_by_global_norm(grads, tc.grad_clip)
    else:
        gnorm = jnp.zeros((), jnp.float32)

    updates, opt_state = opt.update(grads, state["opt"], state["params"])
    # skip the update on non-finite grads (fp16 loss-scaling path)
    new_params = apply_updates(state["params"], updates)
    new_params = jax.tree.map(
        lambda n, o: jnp.where(finite, n, o), new_params, state["params"]
    )
    opt_state = jax.tree.map(
        lambda n, o: jnp.where(finite, n, o) if n.shape == o.shape else n,
        opt_state, state["opt"],
    )
    new_state = {
        "params": new_params,
        "opt": opt_state,
        "scale": scale_state,
        "comp": comp_state,
        "step": state["step"] + 1,
    }
    metrics = dict(metrics, grad_norm=gnorm, wire_bytes=wire,
                   loss_scale=scale_state["scale"])
    if axis_name is not None:
        metrics = {k: jax.lax.pmean(v, axis_name) for k, v in metrics.items()}
    return new_state, metrics


def make_train_step(
    cfg: ArchConfig,
    opt: Optimizer,
    tc: TrainConfig,
    mode: str = "single",
    mesh=None,
    data_axis: str = "data",
    rt: Optional[Runtime] = None,
) -> Callable:
    policy = getattr(PrecisionPolicy, tc.precision)()
    rt = rt if rt is not None else _runtime(cfg, tc)

    def core_step(state, batch, axis_name=None):
        def scaled_loss(p):
            loss, metrics = loss_fn(cfg, p, batch, rt)
            return scale_loss(loss, state["scale"]), metrics

        (loss_s, metrics), grads = jax.value_and_grad(scaled_loss, has_aux=True)(
            state["params"]
        )
        return finish_step(state, grads, metrics, tc, policy, opt, axis_name)

    def train_step(state, batch):
        return core_step(state, batch, None)

    if mode == "single":
        return jax.jit(train_step)

    if mode == "core":
        # unjitted step on GLOBAL arrays — the distributed trainer jits it
        # with explicit in/out shardings (pjit handles the data-parallel mean
        # through the global-batch loss; no axis_name needed)
        return train_step

    if mode == "dp_compressed":
        assert mesh is not None
        from jax.sharding import PartitionSpec as P

        def wrapped(state, batch):
            def inner(state, batch):
                return core_step(state, batch, data_axis)

            bspec = jax.tree.map(lambda _: P(data_axis), batch)
            sspec = jax.tree.map(lambda _: P(), state)
            fn = shard_map(
                inner, mesh=mesh,
                in_specs=(sspec, bspec),
                out_specs=(sspec, jax.tree.map(lambda _: P(), _metric_struct())),
                check_vma=False,
            )
            return fn(state, batch)

        return jax.jit(wrapped)

    raise ValueError(mode)


def _metric_struct():
    z = jnp.zeros(())
    return {
        "loss": z, "xent": z, "aux": z, "z_loss": z,
        "grad_norm": z, "wire_bytes": z, "loss_scale": z,
    }


def fit(
    cfg: ArchConfig,
    tc: TrainConfig,
    data: Iterable[Dict[str, Any]],
    steps: int,
    opt: Optimizer,
    state: Optional[Dict[str, Any]] = None,
    step_fn: Optional[Callable] = None,
    log: Callable[[str], None] = print,
) -> Tuple[Dict[str, Any], list]:
    if state is None:
        state = make_state(cfg, opt, tc)
    if step_fn is None:
        step_fn = make_train_step(cfg, opt, tc)
    history = []
    it = iter(data)
    t0 = time.time()
    for i in range(steps):
        batch = {k: jnp.asarray(v) for k, v in next(it).items()}
        state, metrics = step_fn(state, batch)
        if (i + 1) % tc.log_every == 0 or i == 0:
            m = {k: float(v) for k, v in metrics.items()}
            history.append({"step": i + 1, **m})
            log(
                f"step {i+1:5d} loss={m['loss']:.4f} xent={m['xent']:.4f} "
                f"gnorm={m['grad_norm']:.2f} ({(time.time()-t0)/(i+1):.2f}s/it)"
            )
        if tc.ckpt_dir and tc.ckpt_every and (i + 1) % tc.ckpt_every == 0:
            from repro.checkpoint import ckpt

            ckpt.save(tc.ckpt_dir, i + 1, state)
    return state, history
