"""Pipeline partitioners + the executable ParallelPlan (survey Table 4).

Given per-layer costs, split L layers into P contiguous stages:

* ``dynprog_partition`` — minimize the bottleneck stage time (the PipeDream /
  DAPPLE planner objective): classic minimax DP, optimal, O(L^2 P).
* ``heuristic_partition`` — Megatron-style equal-count split (the survey's
  "Heuristic" rows).
* ``dp_pp_search``     — joint (data, pipeline) degree search for a device
  budget (PipeDream's outer loop / Varuna's brute force): for each (dp, pp)
  with dp*pp == N, partition with the DP and score throughput under the
  1F1B bubble model from repro.core.pipeline; returns the argmax.
  ``uniform=True`` restricts to equal-count stages, the executable-runner
  constraint (SPMD stages share one program, so stage param blocks must be
  shape-uniform).

The planner output is no longer score-only: ``ParallelPlan`` is the object
the 3D trainer executes — (dp, tp, pp) degrees over the (data, model, pipe)
mesh, microbatch count, executable schedule, stage boundaries, and the
per-stage remat policy. ``auto_plan`` runs the search on a real device
count and returns a validated plan (``launch.train --plan auto``).

Costs can come from anywhere; ``layer_costs_from_config`` derives analytic
per-layer FLOP weights from an ArchConfig (MoE/dense/mixer aware), which is
what the benchmark uses.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

from repro.configs.base import ArchConfig


@dataclasses.dataclass(frozen=True)
class Partition:
    boundaries: Tuple[int, ...]   # stage s = layers [boundaries[s], boundaries[s+1])
    stage_costs: Tuple[float, ...]
    bottleneck: float

    @property
    def n_stages(self) -> int:
        return len(self.stage_costs)


def _stage_costs(costs: Sequence[float], bounds: Sequence[int]) -> List[float]:
    return [sum(costs[bounds[i] : bounds[i + 1]]) for i in range(len(bounds) - 1)]


def heuristic_partition(costs: Sequence[float], P: int) -> Partition:
    """Equal layer-count split (Megatron heuristic)."""
    L = len(costs)
    base, rem = divmod(L, P)
    bounds = [0]
    for s in range(P):
        bounds.append(bounds[-1] + base + (1 if s < rem else 0))
    sc = _stage_costs(costs, bounds)
    return Partition(tuple(bounds), tuple(sc), max(sc))


def dynprog_partition(costs: Sequence[float], P: int) -> Partition:
    """Minimax contiguous partition via DP (optimal bottleneck)."""
    L = len(costs)
    P = min(P, L)
    prefix = [0.0]
    for c in costs:
        prefix.append(prefix[-1] + c)

    def span(i: int, j: int) -> float:
        return prefix[j] - prefix[i]

    INF = float("inf")
    # dp[p][j] = min bottleneck for first j layers in p stages
    dp = [[INF] * (L + 1) for _ in range(P + 1)]
    cut = [[0] * (L + 1) for _ in range(P + 1)]
    dp[0][0] = 0.0
    for p in range(1, P + 1):
        for j in range(p, L + 1):
            for i in range(p - 1, j):
                cand = max(dp[p - 1][i], span(i, j))
                if cand < dp[p][j]:
                    dp[p][j] = cand
                    cut[p][j] = i
    bounds = [L]
    p, j = P, L
    while p > 0:
        i = cut[p][j]
        bounds.append(i)
        p, j = p - 1, i
    bounds.reverse()
    sc = _stage_costs(costs, bounds)
    return Partition(tuple(bounds), tuple(sc), max(sc))


def brute_force_partition(costs: Sequence[float], P: int) -> Partition:
    """Exponential reference for tests (L <= ~14)."""
    import itertools

    L = len(costs)
    best: Optional[Partition] = None
    for combo in itertools.combinations(range(1, L), P - 1):
        bounds = (0,) + combo + (L,)
        sc = _stage_costs(costs, bounds)
        cand = Partition(bounds, tuple(sc), max(sc))
        if best is None or cand.bottleneck < best.bottleneck:
            best = cand
    assert best is not None
    return best


def layer_costs_from_config(cfg: ArchConfig) -> List[float]:
    """Analytic per-layer FLOP weights (relative; embedding/head excluded)."""
    d, dff, hd = cfg.d_model, cfg.d_ff, cfg.head_dim
    out: List[float] = []
    for kind in cfg.mixer_kinds():
        if kind in ("attn", "local"):
            mix = 2 * d * (cfg.n_heads + 2 * cfg.n_kv_heads) * hd + \
                2 * cfg.n_heads * hd * d
        elif kind == "mamba":
            di = cfg.d_inner
            mix = 2 * d * 2 * di + 2 * di * d + 2 * di * (di // 16 + 2 * cfg.ssm_state)
        else:  # rglru
            w = cfg.rglru_width or d
            mix = 2 * d * 2 * w + 2 * w * d
        if cfg.ffn_kind == "dense":
            ffn = (3 if cfg.mlp_gated else 2) * 2 * d * dff
        elif cfg.ffn_kind == "moe":
            ffn = cfg.experts_top_k * (3 if cfg.mlp_gated else 2) * 2 * d * dff
            ffn += cfg.n_shared_experts * 3 * 2 * d * dff
            if cfg.dense_residual:
                ffn += 3 * 2 * d * cfg.residual_d_ff
        else:
            ffn = 0
        out.append(float(mix + ffn))
    return out


@dataclasses.dataclass(frozen=True)
class DPPPChoice:
    dp: int
    pp: int
    partition: Partition
    est_step_time: float   # bottleneck * (M + P - 1) / dp  (1F1B fill model)


def dp_pp_search(
    costs: Sequence[float],
    n_devices: int,
    microbatches: int,
    *,
    uniform: bool = False,
    max_dp: Optional[int] = None,
) -> DPPPChoice:
    """Joint (dp, pp) degree search (PipeDream / Varuna outer loop).

    ``uniform=True`` restricts candidates to equal-layer-count stages
    (pp | L, heuristic split) — the executable runner's constraint.
    ``max_dp`` caps the data-parallel degree (Varuna's batch-size limit:
    dp beyond global_batch / microbatch_size replicates idle work); under
    the cap, extra devices go to the pipeline instead.
    """
    best: Optional[DPPPChoice] = None
    for pp in range(1, min(n_devices, len(costs)) + 1):
        if n_devices % pp:
            continue
        if uniform and len(costs) % pp:
            continue
        dp = n_devices // pp
        if max_dp is not None and dp > max_dp:
            continue
        part = (
            heuristic_partition(costs, pp) if uniform
            else dynprog_partition(costs, pp)
        )
        t = part.bottleneck * (microbatches + pp - 1) / (microbatches * dp)
        cand = DPPPChoice(dp, pp, part, t)
        if best is None or t < best.est_step_time:
            best = cand
    assert best is not None, "no feasible (dp, pp) split (max_dp too tight?)"
    return best


# --------------------------------------------------------- executable plans
@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    """One plan object from planner to hardware (the 3D trainer executes it).

    dp/tp/pp are the (data, model, pipe) mesh degrees; ``microbatches`` and
    ``schedule`` drive the executable pipeline (repro.core.pipeline
    tick tables); ``boundaries`` are the contiguous stage cut points over
    layers (must be equal-count — SPMD stages share one compiled program);
    ``remat`` is the remat policy applied inside every stage's layer scan
    (the per-stage knob of the §2.1 plans — the runner itself already
    recomputes each stage forward from its stored input, so this controls
    the *within-stage* transient only); ``stash`` picks the activation-slot
    storage backend (core.stash: raw | int8 | fp8 | host) — the capacity
    knob that can make an otherwise-OOM plan feasible; ``stash_cot``
    additionally stores the pipeline's cotangent slots through the same
    codec (quantized backends only — the second capacity knob
    ``auto_plan`` prices against per-stage remat).
    """
    dp: int = 1
    tp: int = 1
    pp: int = 1
    microbatches: int = 1
    schedule: str = "1f1b"
    boundaries: Tuple[int, ...] = ()
    remat: str = "none"
    stash: str = "raw"
    stash_cot: bool = False

    @property
    def n_devices(self) -> int:
        return self.dp * self.tp * self.pp

    def stage_boundaries(self, n_layers: int) -> Tuple[int, ...]:
        if self.boundaries:
            return self.boundaries
        step = n_layers // self.pp
        return tuple(range(0, n_layers + 1, step))

    def validate(
        self,
        cfg: ArchConfig,
        *,
        global_batch: Optional[int] = None,
        seq_len: Optional[int] = None,
        act_budget: Optional[int] = None,
        itemsize: int = 2,
    ) -> "ParallelPlan":
        """Check executability against ``cfg``; returns self (chainable).

        With ``act_budget`` (device bytes available for pipeline activation
        state; requires ``global_batch``/``seq_len``), also checks that the
        stash fits — the capacity constraint a compressed or host stash can
        unlock for a plan that is infeasible at raw width.
        """
        from repro.core.pipeline import EXECUTABLE_SCHEDULES
        from repro.core.stash import normalize_stash
        from repro.models.stack import pipeline_incompatibility

        normalize_stash(self.stash)
        if self.schedule not in EXECUTABLE_SCHEDULES:
            raise ValueError(
                f"schedule {self.schedule!r} is simulator-only; executable: "
                f"{EXECUTABLE_SCHEDULES} (async rows need weight versioning "
                "that SPMD JAX does not express)"
            )
        if min(self.dp, self.tp, self.pp, self.microbatches) < 1:
            raise ValueError(f"degenerate plan {self}")
        if normalize_stash(self.stash) == "host" and (self.dp, self.tp) != (1, 1):
            raise ValueError(
                "stash='host' uses the host-driven runner (single device "
                f"per stage); got dp={self.dp} tp={self.tp}"
            )
        if self.stash_cot and normalize_stash(self.stash) not in ("int8", "fp8"):
            raise ValueError(
                f"stash_cot=True needs a quantized stash, got {self.stash!r}"
            )
        if cfg.n_layers % self.pp:
            raise ValueError(
                f"{cfg.n_layers} layers not divisible into pp={self.pp} stages"
            )
        b = self.stage_boundaries(cfg.n_layers)
        sizes = {b[i + 1] - b[i] for i in range(len(b) - 1)}
        if len(b) != self.pp + 1 or len(sizes) != 1:
            raise ValueError(f"non-uniform stage boundaries {b} for pp={self.pp}")
        why = pipeline_incompatibility(cfg, self.tp)
        if why is not None:
            raise ValueError(f"plan incompatible with {cfg.name}: {why}")
        if act_budget is not None:
            if global_batch is None or seq_len is None:
                raise ValueError("act_budget check needs global_batch and seq_len")
            rep = self.stash_report(
                cfg, global_batch=global_batch, seq_len=seq_len,
                itemsize=itemsize,
            )
            if rep["total_bytes"] > act_budget:
                raise ValueError(
                    f"activation state {rep['total_bytes']} B (slots "
                    f"{rep['act_bytes']} B + within-stage transient "
                    f"{rep['transient_bytes']} B) exceeds budget "
                    f"{act_budget} B at stash={rep['backend']} "
                    f"remat={self.remat} "
                    f"(raw slots would need {rep['raw_act_bytes']} B; "
                    f"capacity factor {rep['capacity_factor']:.2f}x)"
                )
        return self

    def stash_report(
        self,
        cfg: ArchConfig,
        *,
        global_batch: int,
        seq_len: int,
        itemsize: int = 2,
    ) -> dict:
        """Predicted per-device pipeline activation-state bytes under this
        plan's stash backend (roofline.analysis closed forms; the bench
        reconciles these against measured buffer sizes).

        ``act_bytes`` (alias ``device_bytes``) is the device-resident slot
        state; ``host_bytes`` the host-RAM high water a host stash spills;
        ``transient_bytes`` the within-stage backward transient the
        ``remat`` policy controls; ``total_bytes`` = device slots +
        transient is what ``validate(act_budget=...)`` gates on."""
        from repro.core.pipeline import OP_B_EARLY, runner_ops, tick_table
        from repro.core.stash import normalize_stash
        from repro.roofline.analysis import (
            predicted_pipeline_stash_bytes,
            predicted_stage_transient_bytes,
            predicted_stash_host_bytes,
            stash_bytes_per_slot,
        )

        s = normalize_stash(self.stash)
        cot_s = s if (self.stash_cot and s in ("int8", "fp8")) else "raw"
        table = tick_table(self.schedule, self.pp, self.microbatches)
        mb = global_batch // (self.dp * self.microbatches)
        n_elems = mb * seq_len * cfg.d_model // self.tp
        raw_slot = stash_bytes_per_slot(n_elems, "raw", itemsize)
        act = predicted_pipeline_stash_bytes(
            n_elems, table.n_act_slots, table.n_cot_slots, s, itemsize,
            cot_stash=cot_s,
        )
        raw = predicted_pipeline_stash_bytes(
            n_elems, table.n_act_slots, table.n_cot_slots, "raw", itemsize
        )
        host = predicted_stash_host_bytes(
            n_elems, table.n_act_slots, s, itemsize
        )
        transient = predicted_stage_transient_bytes(
            n_elems, cfg.n_layers // self.pp, self.remat, itemsize
        )
        return {
            "backend": s,
            "remat": self.remat,
            "stash_cot": cot_s != "raw",
            "n_act_slots": table.n_act_slots,
            "n_cot_slots": table.n_cot_slots,
            "early_backwards": int((runner_ops(table) == OP_B_EARLY).sum()),
            "bytes_per_slot": stash_bytes_per_slot(n_elems, s, itemsize),
            "raw_bytes_per_slot": raw_slot,
            "act_bytes": act,
            "device_bytes": act,
            "host_bytes": host,
            "transient_bytes": transient,
            "total_bytes": act + transient,
            "raw_act_bytes": raw,
            "capacity_factor": raw / max(act, 1),
        }

    def describe(self) -> str:
        return (
            f"dp={self.dp} tp={self.tp} pp={self.pp} "
            f"M={self.microbatches} schedule={self.schedule} "
            f"remat={self.remat} stash={self.stash}"
        )


def auto_plan(
    cfg: ArchConfig,
    n_devices: int,
    *,
    microbatches: int = 8,
    tp: int = 1,
    schedule: str = "1f1b",
    remat: str = "none",
    max_dp: Optional[int] = None,
    stash: str = "raw",
    act_budget: Optional[int] = None,
    global_batch: Optional[int] = None,
    seq_len: Optional[int] = None,
    itemsize: int = 2,
) -> ParallelPlan:
    """Search (dp, pp) for ``n_devices`` and return an executable plan.

    tp is fixed by the caller (head-divisibility is a model property, not a
    search dimension); the remaining budget goes through ``dp_pp_search``
    with the uniform-stage constraint. ``max_dp`` typically comes from the
    global batch: dp <= batch / microbatches.

    With ``act_budget`` the plan is stash-aware AND remat-aware: if the
    throughput-optimal split does not fit the activation budget at the
    requested ``stash``/``remat``, the search walks a (stash, remat)
    ladder — slot compression first (raw -> fp8; int8 stores the same
    bytes, so fp8 is the whole compressed rung, and the compressed rungs
    also compress cotangent slots via ``stash_cot``), then per-stage
    remat ("full" collapses the within-stage transient to one layer), then
    both. Compression is tried before remat because it costs ~1x step time
    (BENCH_train_stash) while full remat recomputes every stage layer.
    The returned plan's ``stash``/``stash_cot``/``remat`` fields report
    which rung unlocked it.
    """
    if n_devices % tp:
        raise ValueError(f"{n_devices} devices not divisible by tp={tp}")
    costs = layer_costs_from_config(cfg)
    choice = dp_pp_search(
        costs, n_devices // tp, microbatches, uniform=True, max_dp=max_dp
    )
    plan = ParallelPlan(
        dp=choice.dp, tp=tp, pp=choice.pp, microbatches=microbatches,
        schedule=schedule, boundaries=choice.partition.boundaries,
        remat=remat, stash=stash,
    )
    if act_budget is None:
        return plan.validate(cfg)
    from repro.core.stash import normalize_stash

    s0 = normalize_stash(stash)
    sq = s0 if s0 in ("int8", "fp8") else "fp8"   # the compressed rung
    ladder = [(s0, False, remat), (sq, True, remat)]
    if remat != "full":
        ladder += [(s0, False, "full"), (sq, True, "full")]
    ladder = list(dict.fromkeys(ladder))
    last_err: Optional[ValueError] = None
    for rung_stash, rung_cot, rung_remat in ladder:
        cand = dataclasses.replace(
            plan, stash=rung_stash, stash_cot=rung_cot, remat=rung_remat
        )
        try:
            return cand.validate(
                cfg, global_batch=global_batch, seq_len=seq_len,
                act_budget=act_budget, itemsize=itemsize,
            )
        except ValueError as e:
            last_err = e
    assert last_err is not None
    raise ValueError(
        f"no stash/remat rung fits act_budget={act_budget}: {last_err}"
    )
