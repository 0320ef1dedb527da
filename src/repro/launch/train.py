"""Distributed trainer/server builder: pjit + TP specs + ZeRO overlays.

``build_train`` / ``build_prefill`` / ``build_decode`` return
(step_fn_jitted, input ShapeDtypeStructs with shardings attached) — used by
the multi-pod dry-run (lower+compile only) and by the real trainer entry
point (``main``) on whatever devices exist. ``build_train_pipeline`` is the
3D sibling: it executes a ``core.partitioner.ParallelPlan`` — an
(data, model, pipe) mesh whose pipe axis streams the executable 1F1B/GPipe
schedule (repro.core.pipeline tick tables) while TP follows the same
Megatron specs sliced per stage and ZeRO overlays shard optimizer state
over ``data`` within each stage.

Sharding recipe (DESIGN.md §4):
  batch        over ("pod", "data")        [whichever axes divide it]
  params       TP over "model" (sharding/specs.py) + ZeRO-3 adds "data";
               stacked layer params add "pipe" on the layer axis (3D mesh)
  grads        ZeRO-2+ adds "data"
  opt state    ZeRO-1+ adds "data"
  kv caches    kv-heads over "model", else sequence-parallel over "model"
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ArchConfig, ShapeSpec, get_config, get_shape
from repro.core import zero as zero_mod
from repro.models import Runtime, decode_step, init_decode_state, prefill
from repro.models.runtime import Runtime as RuntimeT
from repro.optim import get as get_opt
from repro.sharding import specs as S
from repro.train import TrainConfig, make_state, make_train_step


def _ns(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def _struct_with(shardings, structs):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        structs, shardings,
    )


def make_runtime(cfg: ArchConfig, mesh, shape: ShapeSpec, tc: TrainConfig) -> Runtime:
    from repro.core.precision import PrecisionPolicy

    policy = getattr(PrecisionPolicy, tc.precision)()
    return Runtime(
        dtype=policy.compute_dtype,
        remat=tc.remat,
        moe_mode=tc.moe_mode,
        mesh=mesh,
        batch_axes=S.batch_axes(mesh, shape.global_batch),
        long_variant=(shape.name == "long_500k"),
        seq_shard=tc.seq_shard,
        scan_mode=tc.scan_mode,
        ssm_seqpar=tc.ssm_seqpar,
        remat_period=tc.remat_period,
        fused_backward=tc.fused_backward,
        use_flash_kernel=tc.fused_backward,
    )


def _batch_struct(cfg: ArchConfig, shape: ShapeSpec) -> Dict[str, jax.ShapeDtypeStruct]:
    B, L = shape.global_batch, shape.seq_len
    text_len = L - (cfg.frontend_tokens if cfg.frontend == "vision" else 0)
    out = {
        "tokens": jax.ShapeDtypeStruct((B, text_len), jnp.int32),
        "labels": jax.ShapeDtypeStruct((B, text_len), jnp.int32),
    }
    if cfg.frontend is not None:
        out["frontend_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.frontend_tokens, cfg.d_model), jnp.float32
        )
    return out


def state_specs(cfg: ArchConfig, state_struct: Any, mesh, zero_stage: int) -> Any:
    """Sharding specs for the full train state (params/opt/scale/comp/step)."""
    pspecs = S.param_specs(cfg, state_struct["params"], mesh)
    p_shapes = state_struct["params"]
    p_over, g_over, o_over = zero_mod.overlay(zero_stage, pspecs, p_shapes, mesh)

    def opt_specs(opt_struct):
        # m/v/mu mirror the params tree; scalars replicate
        out = {}
        for k, v in opt_struct.items():
            if k in ("m", "v", "mu"):
                out[k] = o_over
            elif k == "slots":
                out[k] = jax.tree.map(lambda _: P(), v)
            else:
                out[k] = jax.tree.map(lambda _: P(), v) if isinstance(v, dict) else P()
        return out

    return {
        "params": p_over,
        "opt": opt_specs(state_struct["opt"]),
        "scale": jax.tree.map(lambda _: P(), state_struct["scale"]),
        "comp": jax.tree.map(lambda _: P(), state_struct["comp"]),
        "step": P(),
    }


METRIC_SPECS = {
    "loss": P(), "xent": P(), "aux": P(), "z_loss": P(),
    "grad_norm": P(), "wire_bytes": P(), "loss_scale": P(),
}


def build_train(
    arch: str, mesh, tc: Optional[TrainConfig] = None,
    shape: Optional[ShapeSpec] = None,
) -> Tuple[Callable, Tuple[Any, Any]]:
    """Returns (jitted step, (state_struct, batch_struct)) for train_4k-style
    shapes. Structs carry shardings — pass them to .lower() for the dry-run
    or build real arrays with those shardings for execution."""
    cfg = get_config(arch)
    tc = tc or TrainConfig(precision="bf16", remat="full")
    shape = shape or get_shape("train_4k")
    opt = get_opt(tc.optimizer, tc.lr)
    rt = make_runtime(cfg, mesh, shape, tc)

    state_struct = jax.eval_shape(lambda: make_state(cfg, opt, tc))
    batch_struct = _batch_struct(cfg, shape)

    sspecs = state_specs(cfg, state_struct, mesh, tc.zero_stage)
    bspecs = S.batch_specs(batch_struct, mesh, shape.global_batch)

    s_shard, b_shard = _ns(mesh, sspecs), _ns(mesh, bspecs)
    step = make_train_step(cfg, opt, tc, mode="core", rt=rt)
    jitted = jax.jit(
        step,
        in_shardings=(s_shard, b_shard),
        out_shardings=(s_shard, _ns(mesh, METRIC_SPECS)),
        donate_argnums=(0,),
    )
    return jitted, (
        _struct_with(s_shard, state_struct),
        _struct_with(b_shard, batch_struct),
    )


def make_pipeline_step(cfg: ArchConfig, mesh, plan, tc: TrainConfig, opt):
    """Unjitted (state, batch) -> (state, metrics) executing ``plan``.

    The gradient computation runs through the manual-backward pipeline
    runner (repro.core.pipeline.pipeline_grads): the batch splits into
    ``plan.microbatches`` microbatches, the stacked layer params are already
    pipe-sharded by ``sharding.specs.param_specs`` (stage slices land on
    their devices with no relayout), shared params ride in replicated, and
    the returned grads re-enter the standard step tail
    (``train.loop.finish_step``: unscale, clip, ZeRO-sharded optimizer
    update). State layout is IDENTICAL to the 2D trainer's — only shardings
    differ — which is what makes checkpoint reshard-on-load trivial
    (checkpoint.ckpt.restore_resharded).
    """
    from repro.core.pipeline import tick_table
    from repro.core.precision import PrecisionPolicy
    from repro.core.stash import get_backend
    from repro.models.lm import pipeline_fns
    from repro.train.loop import finish_step

    plan.validate(cfg)
    stash_backend = get_backend(
        plan.stash, fused=tc.fused_stash,
        cotangents=plan.stash_cot or tc.stash_cot,
    )
    if not stash_backend.scan_capable:
        raise ValueError(
            f"stash={plan.stash!r} is host-driven; use "
            "build_train_pipeline_host (single-device eager runner)"
        )
    if tc.compression is not None:
        raise ValueError("pipeline mode composes with ZeRO, not compressed DP")
    if tc.fused_backward:
        raise ValueError(
            "pipeline mode does not route the fused Pallas backward / "
            "chunked-CE head (the runner owns the backward); drop "
            "fused_backward"
        )
    policy = getattr(PrecisionPolicy, tc.precision)()
    rt = RuntimeT(dtype=policy.compute_dtype, remat=plan.remat,
                  fused_stash=tc.fused_stash)
    table = tick_table(plan.schedule, plan.pp, plan.microbatches)
    first_fn, stage_fn, last_fn = pipeline_fns(cfg, rt, plan.tp)
    M = plan.microbatches
    dp_full = mesh.shape["data"]

    from repro.core.pipeline import pipeline_grads

    def pipeline_train_step(state, batch):
        params = state["params"]
        stack = params["stack"]
        shared = {k: v for k, v in params.items() if k != "stack"}
        B, seq = batch["tokens"].shape
        assert B % M == 0, f"batch {B} not divisible into {M} microbatches"
        mbs = jax.tree.map(
            lambda a: a.reshape((M, B // M) + a.shape[1:]), batch
        )
        mb_specs = S.microbatch_specs(mbs, mesh, B // M)
        ba = S.batch_axes(mesh, B // M)
        b_local = (B // M) // S._size(mesh, ba)
        x_struct = jax.ShapeDtypeStruct((b_local, seq, cfg.d_model), rt.dtype)
        metrics_struct = {
            "xent": jax.ShapeDtypeStruct((), jnp.float32),
            "z_loss": jax.ShapeDtypeStruct((), jnp.float32),
        }
        stage_specs = S.param_specs(cfg, params, mesh)["stack"]
        norm = M * dp_full
        seed = state["scale"]["scale"] / norm
        loss_sum, msum, stack_g, shared_g = pipeline_grads(
            first_fn, stage_fn, last_fn, stack, shared, mbs,
            mesh=mesh, table=table, x_struct=x_struct,
            metrics_struct=metrics_struct, stage_specs=stage_specs,
            mb_specs=mb_specs, seed=seed, data_axis="data",
            stash=stash_backend,
        )
        grads = dict(shared_g, stack=stack_g)
        loss = loss_sum / norm
        xent = msum["xent"] / norm
        zl = msum["z_loss"] / norm
        aux = (
            (loss - xent - zl) / cfg.router_aux_coef
            if cfg.router_aux_coef else jnp.zeros((), jnp.float32)
        )
        metrics = {"loss": loss, "xent": xent, "z_loss": zl, "aux": aux}
        return finish_step(state, grads, metrics, tc, policy, opt)

    return pipeline_train_step


def build_train_pipeline(
    arch: str, mesh, plan, tc: Optional[TrainConfig] = None,
    shape: Optional[ShapeSpec] = None,
) -> Tuple[Callable, Tuple[Any, Any]]:
    """3D pipelined twin of ``build_train``: same state/batch structs and
    sharding plumbing, step from ``make_pipeline_step``. ``mesh`` must carry
    (data, model, pipe) axes matching ``plan``'s degrees."""
    cfg = get_config(arch)
    tc = tc or TrainConfig(precision="bf16")
    shape = shape or get_shape("train_4k")
    for ax, deg in (("data", plan.dp), ("model", plan.tp), ("pipe", plan.pp)):
        if mesh.shape.get(ax) != deg:
            raise ValueError(f"mesh {dict(mesh.shape)} != plan {plan.describe()}")
    opt = get_opt(tc.optimizer, tc.lr)

    state_struct = jax.eval_shape(lambda: make_state(cfg, opt, tc))
    batch_struct = _batch_struct(cfg, shape)

    sspecs = state_specs(cfg, state_struct, mesh, tc.zero_stage)
    bspecs = S.batch_specs(batch_struct, mesh, shape.global_batch)
    s_shard, b_shard = _ns(mesh, sspecs), _ns(mesh, bspecs)

    step = make_pipeline_step(cfg, mesh, plan, tc, opt)
    jitted = jax.jit(
        step,
        in_shardings=(s_shard, b_shard),
        out_shardings=(s_shard, _ns(mesh, METRIC_SPECS)),
        donate_argnums=(0,),
    )
    return jitted, (
        _struct_with(s_shard, state_struct),
        _struct_with(b_shard, batch_struct),
    )


def build_train_pipeline_host(
    arch: str, plan, tc: Optional[TrainConfig] = None,
    shape: Optional[ShapeSpec] = None, host_window: int = 2,
    lookahead: int = 2,
) -> Tuple[Callable, Tuple[Any, Any], Any]:
    """Host-driven twin of ``build_train_pipeline`` for ``stash='host'``:
    the per-tick runner (core.pipeline.pipeline_grads_host) on ONE device
    (dp = tp = 1), with the HostStash evicting activation slots to host RAM
    between a microbatch's forward and backward. ``lookahead`` ticks of the
    table's B-entries are prefetched ahead of use so host->device loads
    overlap compute (0 = eager baseline; results are bitwise-equal either
    way). Returns (unjitted step, (state_struct, batch_struct),
    stash_backend) — the backend handle exposes ``stats()`` (overlap /
    stall counters) for exit reporting."""
    from repro.core.pipeline import pipeline_grads_host, tick_table
    from repro.core.precision import PrecisionPolicy
    from repro.core.stash import get_backend
    from repro.models.lm import pipeline_fns
    from repro.train.loop import finish_step

    cfg = get_config(arch)
    tc = tc or TrainConfig(precision="bf16")
    shape = shape or get_shape("train_4k")
    plan.validate(cfg)   # host stash requires dp == tp == 1
    opt = get_opt(tc.optimizer, tc.lr)
    policy = getattr(PrecisionPolicy, tc.precision)()
    rt = RuntimeT(dtype=policy.compute_dtype, remat=plan.remat)
    table = tick_table(plan.schedule, plan.pp, plan.microbatches)
    first_fn, stage_fn, last_fn = pipeline_fns(cfg, rt, 1)
    M = plan.microbatches
    backend = get_backend(plan.stash, host_window=host_window)

    def step(state, batch):
        params = state["params"]
        stack = params["stack"]
        shared = {k: v for k, v in params.items() if k != "stack"}
        B, seq = batch["tokens"].shape
        assert B % M == 0, f"batch {B} not divisible into {M} microbatches"
        mbs = jax.tree.map(
            lambda a: a.reshape((M, B // M) + a.shape[1:]), batch
        )
        x_struct = jax.ShapeDtypeStruct((B // M, seq, cfg.d_model), rt.dtype)
        metrics_struct = {
            "xent": jax.ShapeDtypeStruct((), jnp.float32),
            "z_loss": jax.ShapeDtypeStruct((), jnp.float32),
        }
        norm = M
        seed = state["scale"]["scale"] / norm
        loss_sum, msum, stack_g, shared_g = pipeline_grads_host(
            first_fn, stage_fn, last_fn, stack, shared, mbs,
            table=table, x_struct=x_struct,
            metrics_struct=metrics_struct, seed=seed, stash=backend,
            lookahead=lookahead,
        )
        grads = dict(shared_g, stack=stack_g)
        loss = loss_sum / norm
        xent = msum["xent"] / norm
        zl = msum["z_loss"] / norm
        aux = (
            (loss - xent - zl) / cfg.router_aux_coef
            if cfg.router_aux_coef else jnp.zeros((), jnp.float32)
        )
        metrics = {"loss": loss, "xent": xent, "z_loss": zl, "aux": aux}
        return finish_step(state, grads, metrics, tc, policy, opt)

    state_struct = jax.eval_shape(lambda: make_state(cfg, opt, tc))
    batch_struct = _batch_struct(cfg, shape)
    return step, (state_struct, batch_struct), backend


def init_train_state(cfg: ArchConfig, tc: TrainConfig, state_struct: Any,
                     seed: int = 0) -> Dict[str, Any]:
    """``make_state`` as one jitted program laid out by the shardings of
    ``state_struct`` (from ``build_train``/``build_train_pipeline``), so a
    published-width state is built in place on the devices, never on the
    host or whole on one device."""
    opt = get_opt(tc.optimizer, tc.lr)
    shardings = jax.tree.map(lambda s: s.sharding, state_struct)
    return jax.jit(
        lambda: make_state(cfg, opt, tc, seed), out_shardings=shardings
    )()


def _params_struct_and_shard(cfg: ArchConfig, mesh, zero3: bool = False):
    from repro.models import init_params

    p_struct = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    pspecs = S.param_specs(cfg, p_struct, mesh)
    if zero3:
        pspecs, _, _ = zero_mod.overlay(3, pspecs, p_struct, mesh)
    return p_struct, _ns(mesh, pspecs)


def build_prefill(
    arch: str, mesh, shape: Optional[ShapeSpec] = None,
    tc: Optional[TrainConfig] = None, zero3_params: bool = False,
) -> Tuple[Callable, Tuple[Any, Any]]:
    cfg = get_config(arch)
    tc = tc or TrainConfig(precision="bf16", remat="none")
    shape = shape or get_shape("prefill_32k")
    rt = make_runtime(cfg, mesh, shape, tc)

    p_struct, p_shard = _params_struct_and_shard(cfg, mesh, zero3_params)
    batch_struct = _batch_struct(cfg, shape)
    batch_struct.pop("labels")
    b_shard = _ns(mesh, S.batch_specs(batch_struct, mesh, shape.global_batch))

    def fn(params, batch):
        logits, state = prefill(cfg, params, batch, rt)
        return logits

    jitted = jax.jit(fn, in_shardings=(p_shard, b_shard))
    return jitted, (
        _struct_with(p_shard, p_struct),
        _struct_with(b_shard, batch_struct),
    )


def main() -> None:
    """Real trainer entry point on whatever devices exist.

        PYTHONPATH=src python -m repro.launch.train --arch granite-8b \
            --steps 20 --batch 16 --seq 128 --zero 2

        # published widths, depth cut to one layer, on one TPU chip:
        PYTHONPATH=src python -m repro.launch.train --arch granite-8b \
            --full --layers 1 --steps 5 --batch 2 --seq 2048 \
            --precision bf16 --remat full --fused-backward

    2D (default): pjit step via build_train on a (data x model) mesh.
    3D pipelined: ``--pipe P --microbatches M --schedule {gpipe,1f1b}``
    executes the plan through build_train_pipeline on a
    (data, model, pipe) mesh; ``--plan auto`` instead runs
    ``core.partitioner.dp_pp_search`` over the real device count (at the
    given ``--tp``) and executes the winning (dp, pp) split. Multi-host
    wiring: set jax.distributed + per-host DataPipeline shard (repro.data).
    By default the smoke-size ``reduced()`` variant of the architecture
    runs, small enough for a CPU; ``--full`` keeps the published widths and
    ``--layers N`` cuts the depth (``configs.launch_config``).
    """
    import argparse

    import numpy as np

    from repro.configs import ASSIGNED, launch_config
    from repro.core.partitioner import ParallelPlan, auto_plan
    from repro.data import DataPipeline
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh, make_train_mesh

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b", choices=ASSIGNED)
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="smoke-size reduced() widths (the default)")
    ap.add_argument("--full", dest="reduced", action="store_false",
                    help="the published widths")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to N layers (0 = the config's own)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--zero", type=int, default=1)
    ap.add_argument("--precision", default="f32")
    ap.add_argument("--remat", default="none")
    ap.add_argument("--fused-backward", action="store_true",
                    help="fused Pallas backwards + chunked-CE head")
    ap.add_argument("--tp", type=int, default=0,
                    help="model-axis size (0 = auto: largest of 4/2/1 that "
                         "divides the devices — and, in pipeline mode, that "
                         "the arch supports under manual TP)")
    ap.add_argument("--pipe", type=int, default=1,
                    help="pipeline stages; > 1 selects the 3D trainer")
    ap.add_argument("--microbatches", type=int, default=0,
                    help="microbatches per step (default 2*pipe)")
    ap.add_argument("--schedule", default="1f1b", choices=("1f1b", "gpipe"))
    ap.add_argument("--plan", default="", choices=("", "auto"),
                    help="'auto': dp_pp_search picks (dp, pp) for the "
                         "device count")
    ap.add_argument("--stash", default="raw",
                    choices=("raw", "int8", "fp8", "host"),
                    help="pipeline activation-slot storage (core.stash): "
                         "int8/fp8 compress slots in-scan, host evicts "
                         "them to host RAM (single-device eager runner)")
    ap.add_argument("--act-budget-mb", type=float, default=0.0,
                    help="per-device activation-state budget in MiB; with "
                         "--plan auto the search walks the (stash, remat) "
                         "ladder: raw -> fp8 slot+cotangent compression, "
                         "then per-stage full remat")
    ap.add_argument("--fused-stash", action="store_true",
                    help="route the int8/fp8 stash codec through the fused "
                         "Pallas kernels where they compile (bitwise-"
                         "identical to the jnp path)")
    ap.add_argument("--stash-cot", action="store_true",
                    help="store pipeline cotangent slots through the stash "
                         "codec too (int8/fp8 only)")
    ap.add_argument("--stash-lookahead", type=int, default=2,
                    help="host-runner prefetch window in ticks (0 = eager; "
                         "stash=host only)")
    args = ap.parse_args()

    enable_compile_cache()
    n = len(jax.devices())
    cfg = launch_config(args.arch, full=not args.reduced, layers=args.layers)
    shape = ShapeSpec("cli", args.seq, args.batch, "train")

    def tp_auto(budget: int) -> int:
        """Largest of 4/2/1 that divides the budget AND that the arch can
        actually run under manual pipeline TP (head divisibility etc.)."""
        from repro.models.stack import pipeline_incompatibility

        for cand in (4, 2, 1):
            if budget % cand == 0 and pipeline_incompatibility(cfg, cand) is None:
                return cand
        return 1

    from repro.core.precision import PrecisionPolicy

    itemsize = jnp.dtype(
        getattr(PrecisionPolicy, args.precision)().compute_dtype
    ).itemsize
    act_budget = int(args.act_budget_mb * 2**20) or None

    plan = None
    if args.stash == "host" and args.pipe <= 1 and args.plan != "auto":
        raise SystemExit("--stash host needs the pipeline trainer (--pipe P)")
    if args.plan == "auto":
        if args.pipe > 1:
            raise SystemExit(
                "--plan auto searches (dp, pp) itself; drop --pipe (or set "
                "--pipe without --plan to fix the degrees by hand)"
            )
        tp = args.tp or tp_auto(n)
        # microbatch count is a free knob: if the requested (or default)
        # count leaves no feasible (dp, pp) under the batch cap
        # dp <= batch/M — or a plan the batch can't divide into — halve it
        mb = args.microbatches or 8
        while plan is None:
            try:
                plan = auto_plan(
                    cfg, n, microbatches=mb, tp=tp,
                    schedule=args.schedule, remat=args.remat,
                    max_dp=max(args.batch // mb, 1),
                    stash=args.stash, act_budget=act_budget,
                    global_batch=args.batch, seq_len=args.seq,
                    itemsize=itemsize,
                )
            except AssertionError:
                plan = None
            except ValueError:
                if act_budget is None:   # budget misses retry at smaller M
                    raise
                plan = None
            if plan is not None and args.batch % (mb * plan.dp):
                plan = None
            if plan is None:
                if mb == 1:
                    raise SystemExit(
                        f"no feasible plan for {n} devices at batch "
                        f"{args.batch} (try a larger --batch)"
                    )
                mb //= 2
    elif args.pipe > 1:
        host = args.stash == "host"
        tp = 1 if host else (args.tp or tp_auto(n // args.pipe))
        if not host and n % (tp * args.pipe):
            raise SystemExit(
                f"{n} devices don't factor into tp={tp} x pipe={args.pipe}"
            )
        plan = ParallelPlan(
            dp=1 if host else n // (tp * args.pipe), tp=tp, pp=args.pipe,
            microbatches=args.microbatches or 2 * args.pipe,
            schedule=args.schedule, remat=args.remat, stash=args.stash,
            stash_cot=args.stash_cot,
        ).validate(cfg, global_batch=args.batch, seq_len=args.seq,
                   act_budget=act_budget, itemsize=itemsize)

    tc = TrainConfig(precision=args.precision,
                     remat=plan.remat if plan else args.remat,
                     zero_stage=args.zero,
                     fused_backward=args.fused_backward,
                     pipe=plan.pp if plan else 1,
                     schedule=args.schedule,
                     microbatches=plan.microbatches if plan else 1,
                     stash=plan.stash if plan else "raw",
                     fused_stash=args.fused_stash,
                     stash_cot=plan.stash_cot if plan else False)

    stash_backend = None
    if plan is not None:
        if args.batch % (plan.microbatches * plan.dp):
            raise SystemExit(
                f"--batch {args.batch} must divide into "
                f"microbatches*dp = {plan.microbatches}x{plan.dp}"
            )
        if plan.stash == "host":
            print(f"devices={n} host-driven runner (1 device) "
                  f"plan: {plan.describe()}")
            jitted, (s_struct, b_struct), stash_backend = (
                build_train_pipeline_host(
                    cfg.name, plan, tc, shape,
                    lookahead=args.stash_lookahead,
                )
            )
        else:
            mesh = make_train_mesh(plan.dp, plan.tp, plan.pp)
            print(f"devices={n} mesh=({plan.dp} data x {plan.tp} model x "
                  f"{plan.pp} pipe) plan: {plan.describe()}")
            jitted, (s_struct, b_struct) = build_train_pipeline(
                cfg.name, mesh, plan, tc, shape
            )
    else:
        model_ax = args.tp or 1
        if not args.tp:
            for cand in (4, 2, 1):
                if n % cand == 0 and cand <= n:
                    model_ax = cand
                    break
        mesh = make_mesh((n // model_ax, model_ax), ("data", "model"))
        print(f"devices={n} mesh=({n//model_ax} data x {model_ax} model)")
        jitted, (s_struct, b_struct) = build_train(cfg.name, mesh, tc, shape)

    if stash_backend is None:
        state = init_train_state(cfg, tc, s_struct)
    else:   # the host-driven runner is unjitted: its state stays uncommitted
        state = make_state(cfg, get_opt(tc.optimizer, tc.lr), tc)
    data = DataPipeline(cfg, args.batch, args.seq, seed=0)
    try:
        import time

        t0 = time.time()
        for i in range(args.steps):
            raw = next(data)
            batch = jax.tree.map(
                lambda v, st: jax.device_put(jnp.asarray(v), st.sharding),
                dict(raw), b_struct,
            )
            state, metrics = jitted(state, batch)
            if (i + 1) % 5 == 0 or i == 0:
                print(f"step {i+1:4d} loss={float(metrics['loss']):.4f} "
                      f"({(time.time()-t0)/(i+1):.2f}s/it)")
    finally:
        data.close()
    if plan is not None:
        rep = plan.stash_report(
            cfg, global_batch=args.batch, seq_len=args.seq, itemsize=itemsize
        )
        print(f"stash={rep['backend']} bytes/slot={rep['bytes_per_slot']} "
              f"(raw {rep['raw_bytes_per_slot']}) "
              f"act high-water={rep['n_act_slots']} slots "
              f"device bytes={rep['device_bytes']} "
              f"host bytes={rep['host_bytes']} "
              f"transient bytes={rep['transient_bytes']} (remat={rep['remat']}) "
              f"capacity={rep['capacity_factor']:.2f}x raw")
        if stash_backend is not None:
            stats = stash_backend.stats()
            host_hits = max(stats.get("host_hits", 0), 1)
            print(f"host stash stats: {stats}")
            print(f"host overlap: stall fraction="
                  f"{stats.get('stalled_gets', 0) / host_hits:.2f} "
                  f"prefetch hit rate="
                  f"{stats.get('prefetch_hits', 0) / host_hits:.2f} "
                  f"(of {stats.get('host_hits', 0)} off-window gets)")
    print("train main OK")


def build_decode(
    arch: str, mesh, shape: Optional[ShapeSpec] = None,
    tc: Optional[TrainConfig] = None, zero3_params: bool = False,
) -> Tuple[Callable, Tuple[Any, Any, Any]]:
    """serve_step: ONE new token against a seq_len-deep cache."""
    cfg = get_config(arch)
    tc = tc or TrainConfig(precision="bf16", remat="none")
    shape = shape or get_shape("decode_32k")
    rt = make_runtime(cfg, mesh, shape, tc)
    B = shape.global_batch

    p_struct, p_shard = _params_struct_and_shard(cfg, mesh, zero3_params)
    cache_struct = jax.eval_shape(
        lambda p: init_decode_state(cfg, p, B, shape.seq_len, rt), p_struct
    )
    c_shard = _ns(
        mesh, S.cache_specs(cfg, cache_struct, mesh, shape.global_batch)
    )
    tok_struct = jax.ShapeDtypeStruct((B,), jnp.int32)
    ba = S.batch_axes(mesh, B)
    t_shard = NamedSharding(mesh, P(tuple(ba) if ba else None))

    def fn(params, state, token):
        logits, new_state = decode_step(cfg, params, state, token, rt, shape.seq_len)
        return logits, new_state

    jitted = jax.jit(
        fn,
        in_shardings=(p_shard, c_shard, t_shard),
        donate_argnums=(1,),
    )
    return jitted, (
        _struct_with(p_shard, p_struct),
        _struct_with(c_shard, cache_struct),
        jax.ShapeDtypeStruct(tok_struct.shape, tok_struct.dtype, sharding=t_shard),
    )
if __name__ == "__main__":
    main()
