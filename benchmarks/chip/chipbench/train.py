"""Step loop for the trainer, and its check against the f32 reference.

Set-up builds one object, the compiled step with its state, and drives it
through its first ``check_steps`` steps on distinct batches, through the
same call the window uses; the window then goes on with that object. The
state is the benchmark's: f32 master weights made from the seed
(``chipbench.weights``), zero optimizer moments.

Compared with the configuration's reference (``references/<reference>.py``),
which follows the same steps with its own AdamW and clipping in f32, its
state spread over the cell's devices:

* ``loss_gap``: per step, |loss - reference| / |reference|, the worst step;
* ``grad_gap``: the first gradient as the optimizer got it, read from the
  first moment after one step (m = (1 - b1) g); per leaf, the gap between
  the program's norm and the reference's, over the larger of the
  reference's norm of that leaf and of the median leaf; the worst leaf;
* ``update_gap``: the same for each leaf's change over the checked steps.

Leaves whose reference gradient is under a thousandth of the median leaf's
are left out of both (they move by round-off alone under Adam).
"""
from __future__ import annotations

import time
from typing import Any, Dict, Sequence

import numpy as np

from chipbench import traffic, weights
from chipbench.common import CellRun, Span, reference_module


def leaf_gaps(prog: Sequence[float], ref: Sequence[float], keep) -> float:
    ref = np.asarray(ref, np.float64)
    prog = np.asarray(prog, np.float64)
    med = float(np.median(ref[keep]))
    den = np.maximum(ref, med)
    return float(np.max(np.abs(prog - ref)[keep] / den[keep]))


def compare(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    g_ref = np.asarray(ref["grad_norms"])
    keep = g_ref >= 1e-3 * np.median(g_ref)
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"]))
    return {"loss_gap": float(loss),
            "grad_gap": leaf_gaps(prog["grad_norms"], g_ref, keep),
            "update_gap": leaf_gaps(prog["update_norms"], ref["update_norms"],
                                    keep)}


def build_state(s_struct, p_struct, seed: int):
    """The train state laid out by ``s_struct``'s shardings: generated f32
    params, every other leaf zero but the loss scale (one)."""
    import jax
    import jax.numpy as jnp

    p_shard = jax.tree.map(lambda s: s.sharding, s_struct["params"])
    params = weights.generate(p_struct, seed, jnp.float32, p_shard)
    rest = {k: v for k, v in s_struct.items() if k != "params"}
    shard = jax.tree.map(lambda s: s.sharding, rest)

    def zeros():
        z = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), rest)
        z["scale"]["scale"] = jnp.ones((), z["scale"]["scale"].dtype)
        return z

    return dict(jax.jit(zeros, out_shardings=shard)(), params=params)


def _adam_update(job, t: int):
    """Params after step ``t`` of AdamW from zero moments, given the clipped
    gradients of steps 1..t (the moments are sums over them)."""
    import jax
    import jax.numpy as jnp

    b1, b2, eps, lr = job["b1"], job["b2"], job["eps"], job["lr"]

    def leaf(p, *gs):
        m = sum((1 - b1) * b1 ** (t - 1 - j) * g for j, g in enumerate(gs))
        v = sum((1 - b2) * b2 ** (t - 1 - j) * g * g for j, g in enumerate(gs))
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        return p - lr * u

    return jax.jit(lambda p, gs: jax.tree.map(leaf, p, *gs),
                   donate_argnums=(0,))


def reference_shardings(p_struct, devices):
    """Where the reference keeps its f32 state on the cell's ``devices``: a
    1-D mesh over them; each param leaf split along its last dimension that
    their number divides (else whole on each), the batch by rows. On one
    device every leaf is whole there."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("d",))
    n = len(devices)

    def leaf(s):
        dims = [i for i, k in enumerate(s.shape) if n > 1 and k % n == 0]
        spec = [None] * len(s.shape)
        if dims:
            spec[dims[-1]] = "d"
        return NamedSharding(mesh, P(*spec))

    return (jax.tree.map(leaf, p_struct),
            lambda rows: NamedSharding(mesh, P("d" if rows % n == 0 else None)))


def reference_steps(c, job, p_struct, seed: int, batches, mode: str = "f32",
                    rows=None, devices=None) -> Dict[str, Any]:
    """The reference trainer over ``batches`` (each a host dict), from the
    generated weights: losses, first-gradient leaf norms as clipped, and
    leaf norms of the change after all the steps. ``rows`` keeps only those
    rows of each batch (a planted fault). The params and one clipped
    gradient per step are held, spread over ``devices`` (the cell's; the
    default device if None) by ``reference_shardings``, so two steps fit
    beside a published-width layer stack."""
    import jax
    import jax.numpy as jnp

    ref = reference_module(c["reference"])
    p_shard, batch_shard = reference_shardings(
        p_struct, devices or jax.devices()[:1])
    sq = jax.jit(lambda t: [jnp.sum(jnp.square(x)) for x in
                            jax.tree_util.tree_leaves(t)])
    scale_by = jax.jit(lambda t, s: jax.tree.map(lambda x: x * s, t),
                       donate_argnums=(0,))
    params = weights.generate(p_struct, seed, jnp.float32, p_shard)
    losses, grads = [], []
    for k, b in enumerate(batches):
        toks, labs = b["tokens"], b["labels"]
        if rows is not None:
            toks, labs = toks[rows], labs[rows]
        put = batch_shard(toks.shape[0])
        loss, g = ref.loss_and_grad(c, params, jax.device_put(toks, put),
                                    jax.device_put(labs, put), mode,
                                    job["z_loss"])
        losses.append(float(loss))
        gnorm = float(np.sqrt(sum(float(x) for x in sq(g))))
        grads.append(scale_by(g, min(1.0, job["grad_clip"] / (gnorm + 1e-9))))
        params = _adam_update(job, k + 1)(params, grads)
    grad_norms = [float(np.sqrt(float(x))) for x in sq(grads[0])]
    del grads
    upd = weights.change_norms(p_struct, seed, params)
    return {"losses": losses, "grad_norms": grad_norms, "update_norms": upd}


class Trainer:
    def __init__(self, run: CellRun):
        self.run = run
        self.c = run.config
        self.job = self.c["train"]
        self.mix = run.mix

    def build(self) -> None:
        import jax

        from repro.configs import ShapeSpec
        from repro.models import init_params
        from repro.train import TrainConfig

        c, job, mix = self.c, self.job, self.mix
        self.cfg = self.run.program_config()
        dp, tp, pp = job["mesh"]
        tc = TrainConfig(
            optimizer="adamw", lr=job["lr"], grad_clip=job["grad_clip"],
            precision=job["precision"], remat=job["remat"],
            fused_backward=job["fused_backward"], pipe=pp,
            microbatches=job.get("microbatches", 1),
            schedule=job.get("schedule", "1f1b"), stash=job.get("stash", "raw"),
        )
        shape = ShapeSpec(self.run.name, mix["seq_len"], mix["global_batch"],
                          "train")
        if pp > 1:
            from repro.core.partitioner import ParallelPlan
            from repro.launch.mesh import make_train_mesh
            from repro.launch.train import build_train_pipeline

            mesh = make_train_mesh(dp, tp, pp)
            plan = ParallelPlan(dp=dp, tp=tp, pp=pp,
                                microbatches=tc.microbatches,
                                schedule=tc.schedule, remat=tc.remat,
                                stash=tc.stash).validate(self.cfg)
            jitted, (s_struct, b_struct) = build_train_pipeline(
                self.cfg.name, mesh, plan, tc, shape)
        else:
            from repro.launch.mesh import make_mesh
            from repro.launch.train import build_train

            mesh = make_mesh((dp, tp), ("data", "model"),
                             devices=self.run.devices[: dp * tp])
            jitted, (s_struct, b_struct) = build_train(
                self.cfg.name, mesh, tc, shape)
        t0 = time.perf_counter()
        self.step = jitted.lower(s_struct, b_struct).compile()
        mem = self.step.memory_analysis()
        self.run.note(
            f"train step compiled in {time.perf_counter() - t0:.1f} s; "
            f"memory_analysis per device: arguments "
            f"{mem.argument_size_in_bytes / 2**30:.3f} GiB, temporaries "
            f"{mem.temp_size_in_bytes / 2**30:.3f} GiB, outputs "
            f"{mem.output_size_in_bytes / 2**30:.3f} GiB")
        self.p_struct = jax.eval_shape(
            lambda: init_params(self.cfg, jax.random.PRNGKey(0)))
        t0 = time.perf_counter()
        self.state = build_state(s_struct, self.p_struct, self.run.seed)
        host = traffic.train_batches(mix, self.run.seed, self.c["vocab_size"])
        self.host_batches = host
        self.batches = [jax.tree.map(lambda a, s: jax.device_put(a, s.sharding),
                                     b, b_struct) for b in host]
        jax.block_until_ready((self.state, self.batches))
        self.run.read_memory()
        self.run.note(f"state and batches made in "
                      f"{time.perf_counter() - t0:.1f} s; device peak before "
                      f"the first step {self.run.memory_peak_bytes / 2**30:.3f} GiB")

    def first_steps(self) -> Dict[str, Any]:
        """The checked steps, through the window's own call."""
        import jax
        import jax.numpy as jnp

        n, b1 = self.mix["check_steps"], self.job["b1"]
        leaf_norm = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32)))) for x in jax.tree_util.tree_leaves(t)])
        losses, grad_norms = [], None
        for k in range(n):
            self.state, m = self.step(self.state, self.batches[k])
            losses.append(float(m["loss"]))
            if k == 0:
                grad_norms = [float(x) / (1 - b1) for x in
                              leaf_norm(self.state["opt"]["m"])]
        t0 = time.perf_counter()
        upd = weights.change_norms(self.p_struct, self.run.seed,
                                   self.state["params"])
        self.run.note(f"checked steps done; change norms read in "
                      f"{time.perf_counter() - t0:.1f} s")
        return {"losses": losses, "grad_norms": grad_norms,
                "update_norms": upd}

    def window(self) -> Dict[str, Any]:
        import jax

        run, K = self.run, len(self.batches)
        i = self.mix["check_steps"]
        run.start_window()
        t0 = time.perf_counter()
        steps, prev = 0, None
        with Span("bench.window"):
            while True:
                with Span("bench.step"):
                    self.state, m = self.step(self.state, self.batches[i % K])
                i += 1
                steps += 1
                if prev is not None:
                    with Span("bench.sync"):
                        prev.block_until_ready()
                prev = m["loss"]
                if time.perf_counter() - t0 >= run.seconds:
                    break
            with Span("bench.sync"):
                jax.block_until_ready(self.state)
        t1 = time.perf_counter()
        run.close_window()
        tokens = steps * self.mix["global_batch"] * self.mix["seq_len"]
        run.note(f"window: {steps} steps in {t1 - t0:.3f} s, last loss "
                 f"{float(prev):.4f}")
        return {"steps": steps, "seconds": t1 - t0,
                "tokens_per_s": tokens / (t1 - t0)}


def run_cell(run: CellRun) -> Dict[str, Any]:
    import gc

    t = Trainer(run)
    t.build()
    prog = t.first_steps()
    w = t.window()
    run.read_memory()
    run.per_layer_inputs(steps=w["steps"])
    p_struct, host = t.p_struct, t.host_batches
    t.state = t.batches = t.step = None
    gc.collect()
    n = run.mix["check_steps"]
    t0 = time.perf_counter()
    args = (run.config, run.config["train"], p_struct, run.seed, host[:n])
    ref = reference_steps(*args, devices=run.devices)
    run.note(f"reference: {n} steps in {time.perf_counter() - t0:.1f} s; "
             f"losses program {prog['losses']} reference {ref['losses']}")
    gaps = compare(prog, ref)
    lim = run.config["limits"]
    checks = [(k, gaps[k], lim[k]) for k in ("loss_gap", "grad_gap",
                                             "update_gap")]
    if run.control:
        ctl = compare(reference_steps(*args, "fp8", devices=run.devices), ref)
        B = run.mix["global_batch"]
        half = compare(reference_steps(*args, rows=slice(0, B // 2),
                                       devices=run.devices), ref)
        checks += [("control_fp8_" + k, ctl[k], lim[k]) for k in ctl]
        checks += [("fault_half_batch_" + k, half[k], lim[k]) for k in half]
    ok = all(v <= l for _, v, l in checks[:3])
    metrics = {"train_tokens_per_s": w["tokens_per_s"],
               "train_peak_hbm_gib": run.memory_peak_bytes / 2**30}
    return {"metrics": metrics, "correct": ok, "attempted": w["steps"],
            "failed": 0, "checks": checks}
