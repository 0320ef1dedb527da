"""Pipeline parallelism: Table-4 schedules + executable 1F1B/GPipe runners.

Three parts:

1. **Schedule generators + event-driven simulator** (pure Python) covering
   the survey's Table 4 rows: GPipe, 1F1B (DAPPLE/Megatron), interleaved
   (Megatron-LM), PipeDream (async), PipeDream-2BW, Chimera (bidirectional),
   GEMS. The simulator respects fwd/bwd dependencies and device
   serialization and reports bubble fraction, peak in-flight activations per
   device, and weight versions — the quantities Table 4 compares. Async
   schedules also report weight staleness. Interleaved/Chimera use a greedy
   ready-op scheduler over virtual stages (documented approximation).

2. **Executable GPipe via AD** (``pipeline_apply``) on a ``pipe`` mesh axis:
   microbatch stream scanned over ticks, stage transfer via ``ppermute``,
   backward from AD through the ppermutes. Simple, but AD stores every
   microbatch's activations — O(M) live memory per device.

3. **Executable manual-backward runner** (``tick_table`` +
   ``pipeline_grads``): the same event-driven simulator, run at unit op
   cost, is compiled into integer *tick tables* — per (tick, stage): which
   microbatch to forward/backward, which activation slot to read/write, and
   where arriving ppermute traffic lands. The runner streams those tables
   through one ``lax.scan`` inside a fully-manual ``shard_map`` over a
   (data, model, pipe) mesh and computes the backward itself (``jax.vjp``
   per microbatch inside the schedule, gradients accumulated as-you-go), so
   live activations are exactly the schedule's slot count: O(P) for 1F1B vs
   O(M) for GPipe at identical math. Backward recomputes each stage forward
   from its stored stage *input* — per-stage rematerialization (Chen'16,
   1604.06174) composed with the schedule by construction. GPipe and 1F1B
   run the identical per-microbatch code in the identical per-stage
   accumulation order, so their gradients are bitwise equal — asserted in
   tests/benchmarks.

TPU adaptation (DESIGN.md §3): asynchronous weight versioning (PipeDream)
does not exist in SPMD-synchronous JAX; async rows are simulator +
convergence-model only. The executable family is synchronous: GPipe and
1F1B, which share the same math and differ only in op order and peak
memory.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

# =====================================================================
# Part 1: schedules + simulator
# =====================================================================

F, B = "F", "B"


@dataclasses.dataclass(frozen=True)
class Op:
    stage: int           # virtual stage index in [0, P*v)
    mb: int
    kind: str            # F | B


@dataclasses.dataclass(frozen=True)
class SimResult:
    name: str
    makespan: float
    bubble_fraction: float
    peak_activations: int        # per device, in microbatch-activation units
    weight_versions: int
    synchronous: bool
    max_staleness: int           # in optimizer steps (async only)


def _device_of(vstage: int, P: int, placement: str, v: int) -> int:
    if placement == "interleaved":
        return vstage % P
    if placement == "bidirectional":      # chimera/gems: chunk0 = s, chunk1 = P-1-s
        chunk, s = divmod(vstage, P)
        return s if chunk == 0 else P - 1 - s
    return vstage  # plain: one stage per device


def _op_order(name: str, P: int, M: int, v: int) -> Tuple[List[List[Op]], str, int]:
    """Per-DEVICE preferred op order. Returns (orders, placement, n_vstages)."""
    if name == "gpipe":
        orders = [
            [Op(s, m, F) for m in range(M)] + [Op(s, m, B) for m in range(M)]
            for s in range(P)
        ]
        return orders, "plain", P
    if name in ("1f1b", "dapple", "pipedream", "pipedream_2bw", "varuna"):
        orders = []
        for s in range(P):
            warm = min(P - s, M)
            ops: List[Op] = [Op(s, m, F) for m in range(warm)]
            nf, nb = warm, 0
            while nb < M:
                ops.append(Op(s, nb, B))
                nb += 1
                if nf < M:
                    ops.append(Op(s, nf, F))
                    nf += 1
            orders.append(ops)
        return orders, "plain", P
    if name == "interleaved":
        # derive per-device orders from a virtual 1F1B execution on P*v
        # virtual devices (one per model chunk), then merge each real
        # device's chunk streams by virtual start time
        V = P * v
        times = _virtual_1f1b_times(V, M)
        orders = [[] for _ in range(P)]
        for d in range(P):
            ops = [
                (times[(vs, m, k)], Op(vs, m, k))
                for vs in range(d, V, P)
                for m in range(M)
                for k in (F, B)
            ]
            ops.sort(key=lambda x: (x[0], x[1].kind == F, x[1].stage))
            orders[d] = [o for _, o in ops]
        return orders, "interleaved", V
    if name in ("chimera", "gems"):
        # bidirectional: 2 virtual pipelines; each device hosts vstage s and
        # vstage P + (P-1-s). Chimera splits microbatches between directions.
        V = 2 * P
        half = M // 2 if name == "chimera" else M
        orders = [[] for _ in range(P)]
        for dev in range(P):
            up, down = dev, 2 * P - 1 - dev  # wait: see _device_of mapping
            down = P + (P - 1 - dev)
            ops: List[Op] = []
            mbs_up = range(0, half)
            mbs_down = range(half, M) if name == "chimera" else range(0)
            for m_u, m_d in zip(list(mbs_up) + [None] * M, list(mbs_down) + [None] * M):
                if m_u is not None:
                    ops.append(Op(up, m_u, F))
                if m_d is not None:
                    ops.append(Op(down, m_d, F))
            for m_u, m_d in zip(list(mbs_up) + [None] * M, list(mbs_down) + [None] * M):
                if m_u is not None:
                    ops.append(Op(up, m_u, B))
                if m_d is not None:
                    ops.append(Op(down, m_d, B))
            orders[dev] = [o for o in ops if o.mb is not None]
        return orders, "bidirectional", 2 * P
    raise ValueError(f"unknown schedule {name!r}")


def _virtual_1f1b_times(V: int, M: int, tf: float = 1.0, tb: float = 2.0):
    """Start time of every (vstage, mb, kind) under 1F1B with V devices."""
    orders, _, _ = _op_order("1f1b", V, M, 1)
    ready_f = np.full((V, M), np.inf)
    ready_b = np.full((V, M), np.inf)
    ready_f[0, :] = 0.0
    done_f = np.full((V, M), np.inf)
    dev_time = np.zeros(V)
    queues = [list(o) for o in orders]
    times: Dict[Tuple[int, int, str], float] = {}
    remaining = sum(len(q) for q in queues)
    while remaining:
        for d in range(V):
            if not queues[d]:
                continue
            for qi, op in enumerate(queues[d]):
                if op.kind == F:
                    t_in, dur = ready_f[op.stage, op.mb], tf
                else:
                    t_in = (
                        done_f[op.stage, op.mb]
                        if op.stage == V - 1
                        else max(done_f[op.stage, op.mb], ready_b[op.stage, op.mb])
                    )
                    dur = tb
                if not np.isfinite(t_in):
                    continue
                start = max(dev_time[d], t_in)
                end = start + dur
                dev_time[d] = end
                times[(op.stage, op.mb, op.kind)] = start
                if op.kind == F:
                    done_f[op.stage, op.mb] = end
                    if op.stage + 1 < V:
                        ready_f[op.stage + 1, op.mb] = end
                    else:
                        ready_b[op.stage, op.mb] = end
                else:
                    if op.stage > 0:
                        ready_b[op.stage - 1, op.mb] = end
                queues[d].pop(qi)
                remaining -= 1
                break
    return times


def _execute_schedule(
    name: str,
    P: int,
    M: int,
    *,
    v: int = 2,
    t_fwd: float = 1.0,
    t_bwd: float = 2.0,
    t_comm: float = 0.0,
):
    """Run the event-driven scheduler; returns (executed, dev_time, placement,
    V, chunks, t_fwd, t_bwd) where ``executed[d]`` is the per-device list of
    (start, end, Op). Shared engine behind ``simulate`` and ``tick_table``."""
    orders, placement, V = _op_order(name, P, M, v)
    chunks = V // P if placement != "plain" else 1
    if placement == "interleaved":
        # v chunks per device, each 1/v of the model: per-op time scales down
        t_fwd, t_bwd = t_fwd / chunks, t_bwd / chunks
    if placement == "bidirectional":
        # two half-depth pipelines: each vstage is half the per-device model
        t_fwd, t_bwd = t_fwd / 2, t_bwd / 2

    ready_f = np.full((V, M), np.inf)  # time input available
    ready_b = np.full((V, M), np.inf)
    for m in range(M):
        ready_f[0, m] = 0.0
        if placement == "bidirectional":
            ready_f[P, m] = 0.0        # reverse pipeline entry
    done_f = np.full((V, M), np.inf)
    done_b = np.full((V, M), np.inf)

    dev_time = np.zeros(P)
    queues = [list(o) for o in orders]
    executed = [[] for _ in range(P)]  # (start, end, op)

    total_ops = sum(len(q) for q in queues)
    n_exec = 0
    stall_guard = 0
    while n_exec < total_ops:
        progressed = False
        for d in range(P):
            if not queues[d]:
                continue
            # execute the first READY op in the device's preferred order
            # (greedy relaxation — exact for gpipe/1f1b whose orders are
            # dependency-consistent; documented approximation otherwise)
            pick = None
            for qi, op in enumerate(queues[d]):
                if op.kind == F:
                    t_in = ready_f[op.stage, op.mb]
                    dur = t_fwd
                else:
                    t_in = (
                        done_f[op.stage, op.mb]
                        if _is_last(op.stage, V, placement, P)
                        else max(done_f[op.stage, op.mb], ready_b[op.stage, op.mb])
                    )
                    dur = t_bwd
                if np.isfinite(t_in):
                    pick = (qi, op, t_in, dur)
                    break
            if pick is None:
                continue
            qi, op, t_in, dur = pick
            start = max(dev_time[d], t_in)
            end = start + dur
            dev_time[d] = end
            executed[d].append((start, end, op))
            if op.kind == F:
                done_f[op.stage, op.mb] = end
                nxt = _next_stage(op.stage, V, placement, P)
                if nxt is not None:
                    ready_f[nxt, op.mb] = end + t_comm
                else:
                    ready_b[op.stage, op.mb] = end  # loss -> own bwd
            else:
                done_b[op.stage, op.mb] = end
                prv = _prev_stage(op.stage, V, placement, P)
                if prv is not None:
                    ready_b[prv, op.mb] = end + t_comm
            queues[d].pop(qi)
            n_exec += 1
            progressed = True
        if not progressed:
            stall_guard += 1
            if stall_guard > total_ops * 4:
                raise RuntimeError(f"schedule {name} deadlocked")
        else:
            stall_guard = 0

    return executed, dev_time, placement, V, chunks, t_fwd, t_bwd


def simulate(
    name: str,
    P: int,
    M: int,
    *,
    v: int = 2,
    t_fwd: float = 1.0,
    t_bwd: float = 2.0,
    t_comm: float = 0.0,
) -> SimResult:
    """Event-driven simulation of a pipeline schedule."""
    asynchronous = name in ("pipedream", "pipedream_2bw", "varuna")
    executed, dev_time, placement, V, chunks, t_fwd, t_bwd = _execute_schedule(
        name, P, M, v=v, t_fwd=t_fwd, t_bwd=t_bwd, t_comm=t_comm
    )

    makespan = float(dev_time.max())
    work = M * (t_fwd + t_bwd) * chunks
    if placement == "bidirectional" and name == "chimera":
        work = M * (t_fwd + t_bwd)  # each direction carries M/2 microbatches
    bubble = 1.0 - work / makespan if makespan > 0 else 0.0

    # peak in-flight activations per device: fwd done, bwd not yet done
    peak = 0
    for d in range(P):
        events = []
        for (s0, e0, op) in executed[d]:
            if op.kind == F:
                events.append((e0, +1))
            else:
                events.append((e0, -1))
        cur = 0
        for _, delta in sorted(events):
            cur += delta
            peak = max(peak, cur)

    versions = {"pipedream": P, "pipedream_2bw": 2}.get(name, 1)
    staleness = {"pipedream": P - 1, "pipedream_2bw": 1}.get(name, 0)
    return SimResult(
        name=name,
        makespan=makespan,
        bubble_fraction=max(bubble, 0.0),
        peak_activations=peak,
        weight_versions=versions,
        synchronous=not asynchronous,
        max_staleness=staleness,
    )


def _is_last(vs: int, V: int, placement: str, P: int) -> bool:
    if placement == "bidirectional":
        return vs == P - 1 or vs == 2 * P - 1
    return vs == V - 1


def _next_stage(vs: int, V: int, placement: str, P: int) -> Optional[int]:
    if placement == "bidirectional":
        if vs == P - 1 or vs == 2 * P - 1:
            return None
        return vs + 1
    return vs + 1 if vs + 1 < V else None


def _prev_stage(vs: int, V: int, placement: str, P: int) -> Optional[int]:
    if placement == "bidirectional":
        if vs == 0 or vs == P:
            return None
        return vs - 1
    return vs - 1 if vs > 0 else None


SCHEDULES = (
    "gpipe", "1f1b", "interleaved", "pipedream", "pipedream_2bw",
    "chimera", "gems",
)


# =====================================================================
# Part 2: executable GPipe on a mesh axis
# =====================================================================
def pipeline_apply(
    stage_fn: Callable,
    stage_params: Any,
    microbatches: Any,
    *,
    mesh,
    axis: str = "pipe",
):
    """Run ``stage_fn`` as a GPipe pipeline over mesh axis ``axis``.

    stage_params: pytree with leading dim P (sharded over ``axis``).
    microbatches: pytree with leading dim M (replicated).
    stage_fn(params_for_stage, x) -> y, with y.shape == x.shape.

    Returns outputs with leading dim M (replicated over ``axis``). Backward
    through this function is the AD-reversed pipeline (GPipe semantics).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as Pspec
    from jax import shard_map

    P_count = mesh.shape[axis]
    x0 = jax.tree.map(lambda m: m[0], microbatches)
    M = jax.tree.leaves(microbatches)[0].shape[0]
    T = M + P_count - 1

    def inner(params, mbs):
        params = jax.tree.map(lambda p: p[0], params)  # local stage params
        stage = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % P_count) for i in range(P_count)]

        def tick(carry, t):
            state = carry
            inject = jax.tree.map(
                lambda m: m[jnp.minimum(t, M - 1)], mbs
            )
            xin = jax.tree.map(
                lambda s, i: jnp.where(stage == 0, i, s), state, inject
            )
            out = stage_fn(params, xin)
            contrib = jax.tree.map(
                lambda o: jnp.where(stage == P_count - 1, o, 0.0), out
            )
            emitted = jax.tree.map(lambda c: jax.lax.psum(c, axis), contrib)
            nxt = jax.tree.map(
                lambda o: jax.lax.ppermute(o, axis, perm), out
            )
            return nxt, emitted

        zeros = jax.tree.map(jnp.zeros_like, x0)
        _, ys = jax.lax.scan(tick, zeros, jnp.arange(T))
        # output for microbatch m emerges at tick m + P - 1
        return jax.tree.map(lambda y: y[P_count - 1 :], ys)

    pspec = jax.tree.map(lambda _: Pspec(axis), stage_params)
    mspec = jax.tree.map(lambda _: Pspec(), microbatches)
    ospec = jax.tree.map(lambda _: Pspec(), microbatches)
    fn = shard_map(
        inner, mesh=mesh, in_specs=(pspec, mspec), out_specs=ospec,
        check_vma=False,
    )
    return fn(stage_params, microbatches)


# =====================================================================
# Part 3: tick tables + manual-backward runner (1F1B and GPipe)
# =====================================================================
EXECUTABLE_SCHEDULES = ("gpipe", "1f1b")


@dataclasses.dataclass(frozen=True)
class TickTable:
    """Integer-tick execution tables compiled from the event simulator.

    The simulator is run at unit op cost (t_fwd = t_bwd = 1, t_comm = 0), so
    op start times are a global integer tick clock on which every device
    executes at most one op per tick and a value ppermuted at the end of
    tick ``t`` is available at tick ``t + 1``. Tables are (n_ticks, P)
    int32, entry -1 = nothing this tick:

      f_mb / b_mb    microbatch to forward / backward
      f_slot         activation-buffer slot holding (or to hold) the stage
                     INPUT of that microbatch
      b_slot         activation slot to read for the backward (same slot
                     its forward stored; freed afterwards)
      b_cot          cotangent slot carrying the arriving upstream gradient
                     (-1 on the last stage, which seeds from the loss)
      arr_f / arr_b  slot into which this tick's arriving ppermute traffic
                     (activation / cotangent) must be stored

    ``n_act_slots`` is the greedy-allocated per-device activation buffer
    depth — the executable form of Table 4's "peak in-flight activations":
    O(M) for GPipe, O(P) for 1F1B. ``bubble_fraction`` is exact for the
    executable schedule (each device computes 2M of n_ticks op slots) and
    must agree with ``simulate(name, P, M, t_fwd=1, t_bwd=1)`` — the bench
    asserts this simulator-vs-executable accounting row.
    """
    schedule: str
    n_stages: int
    n_microbatches: int
    n_ticks: int
    n_act_slots: int
    n_cot_slots: int
    f_mb: np.ndarray
    f_slot: np.ndarray
    b_mb: np.ndarray
    b_slot: np.ndarray
    b_cot: np.ndarray
    arr_f: np.ndarray
    arr_b: np.ndarray

    @property
    def bubble_fraction(self) -> float:
        return 1.0 - 2.0 * self.n_microbatches / self.n_ticks

    def peak_activation_bytes(self, act_bytes: int) -> int:
        """Live pipeline-state bytes per device for one microbatch size."""
        return (self.n_act_slots + self.n_cot_slots) * act_bytes


def _alloc_slots(avail: Dict, last_use: Dict) -> Tuple[Dict, int]:
    """Greedy per-stage interval slot allocation: a slot is live from its
    value's arrival tick through its last-use tick (inclusive; arrivals at a
    tick are stored before that tick's op reads, so reuse needs end < start)."""
    slots: Dict = {}
    depth = 0
    by_stage: Dict[int, List] = {}
    for key in avail:
        by_stage.setdefault(key[0], []).append(key)
    for s, keys in by_stage.items():
        keys.sort(key=lambda k: (avail[k], k[1]))
        busy: List[Tuple[int, int]] = []   # (last_use, slot)
        free: List[int] = []
        used = 0
        for k in keys:
            t0 = avail[k]
            free += [sl for end, sl in busy if end < t0]
            busy = [(end, sl) for end, sl in busy if end >= t0]
            free.sort()
            if free:
                sl = free.pop(0)
            else:
                sl = used
                used += 1
            slots[k] = sl
            busy.append((last_use[k], sl))
        depth = max(depth, used)
    return slots, depth


def tick_table(schedule: str, P: int, M: int) -> TickTable:
    """Compile ``schedule`` into integer tick tables (see TickTable)."""
    if schedule not in EXECUTABLE_SCHEDULES:
        raise ValueError(
            f"executable schedules are {EXECUTABLE_SCHEDULES}, got {schedule!r}"
        )
    executed, _, _, _, _, _, _ = _execute_schedule(
        schedule, P, M, v=1, t_fwd=1.0, t_bwd=1.0, t_comm=0.0
    )
    f_tick: Dict[Tuple[int, int], int] = {}
    b_tick: Dict[Tuple[int, int], int] = {}
    for evs in executed:
        for (s0, e0, op) in evs:
            t = int(round(s0))
            assert abs(s0 - t) < 1e-9 and abs(e0 - t - 1) < 1e-9, (s0, e0, op)
            (f_tick if op.kind == F else b_tick)[(op.stage, op.mb)] = t
    T = 1 + max(b_tick.values())

    # availability: when the stage input / upstream cotangent lands locally
    avail_f = {
        (s, m): (t if s == 0 else f_tick[(s - 1, m)] + 1)
        for (s, m), t in f_tick.items()
    }
    avail_b = {
        (s, m): b_tick[(s + 1, m)] + 1 for (s, m) in b_tick if s < P - 1
    }
    for k, t in f_tick.items():
        assert avail_f[k] <= t, ("fwd before input available", k)
        if k in avail_b:
            assert avail_b[k] <= b_tick[k], ("bwd before cotangent", k)

    act_slot, n_act = _alloc_slots(avail_f, b_tick)
    cot_slot, n_cot = _alloc_slots(avail_b, {k: b_tick[k] for k in avail_b})
    n_cot = max(n_cot, 1)

    tables = {
        name: np.full((T, P), -1, np.int32)
        for name in ("f_mb", "f_slot", "b_mb", "b_slot", "b_cot",
                     "arr_f", "arr_b")
    }
    for (s, m), t in f_tick.items():
        tables["f_mb"][t, s] = m
        tables["f_slot"][t, s] = act_slot[(s, m)]
        if s > 0:
            ta = avail_f[(s, m)]
            assert tables["arr_f"][ta, s] == -1, "two fwd arrivals in one tick"
            tables["arr_f"][ta, s] = act_slot[(s, m)]
    for (s, m), t in b_tick.items():
        tables["b_mb"][t, s] = m
        tables["b_slot"][t, s] = act_slot[(s, m)]
        if s < P - 1:
            tables["b_cot"][t, s] = cot_slot[(s, m)]
            ta = avail_b[(s, m)]
            assert tables["arr_b"][ta, s] == -1, "two bwd arrivals in one tick"
            tables["arr_b"][ta, s] = cot_slot[(s, m)]
    return TickTable(
        schedule=schedule, n_stages=P, n_microbatches=M, n_ticks=T,
        n_act_slots=n_act, n_cot_slots=n_cot, **tables,
    )


def pipeline_grads(
    first_fn: Callable,
    stage_fn: Callable,
    last_fn: Callable,
    stage_params: Any,
    shared_params: Any,
    microbatches: Any,
    *,
    mesh,
    table: TickTable,
    x_struct,
    metrics_struct: Any,
    stage_specs: Any,
    mb_specs: Any,
    seed=None,
    axis: str = "pipe",
    data_axis: Optional[str] = None,
    stash=None,
):
    """Run one pipelined forward+backward; returns (loss, metrics, grads).

    The schedule in ``table`` is executed tick-by-tick inside a fully-manual
    ``shard_map`` over ``mesh``; the backward is computed by this runner
    (``jax.vjp`` per microbatch, recomputing the stage forward from the
    stored stage input — per-stage remat by construction), NOT by AD through
    the scan, so live state is exactly the table's slot buffers.

    Callables (all executed per device, per microbatch):
      first_fn(shared, mb)    -> x            stage-0 input (e.g. embedding)
      stage_fn(stage_p, x)    -> (y, aux)     this stage's layers; ``aux`` is
                                              a scalar loss term (router aux)
                                              seeded on EVERY stage
      last_fn(shared, y, mb)  -> (loss, metrics)  head + loss on stage P-1

    ``stage_params`` is the canonical stacked-layer tree whose leading layer
    axis is sharded over ``axis`` per ``stage_specs`` (each device sees its
    stage's layer slice); ``shared_params`` (embedding/head/final norm) are
    replicated over ``axis`` — their grads are psum'd over it, which also
    resolves tied embeddings used at both ends. ``microbatches`` leaves are
    (M, B, ...) with specs ``mb_specs`` (batch dim over ``data_axis``).
    ``x_struct`` is the per-device inter-stage activation
    ShapeDtypeStruct; ``seed`` the loss cotangent (loss scaling /
    microbatch normalization — caller bakes in 1/(M*dp)).

    Mesh-collective safety: the per-tick op branches contain collectives
    over the ``model`` axis only (manual tensor parallelism inside
    ``stage_fn``). All devices sharing a pipe coordinate run the SAME branch
    every tick (tables depend only on (tick, stage)), so model-axis groups
    never diverge across a collective. ``ppermute`` transfers sit outside
    the branches and run every tick.

    Returns (loss_sum, metrics_sums, stage_grads, shared_grads) as global
    arrays: loss/metrics are summed over microbatches and data shards
    (caller normalizes by M*dp); grads are psum'd over ``data_axis`` (and
    ``axis`` for shared) but NOT over model — model-sharded leaves carry
    distinct shards, replicated leaves identical values.

    ``stash`` is the activation-slot storage backend (core.stash): every
    slot write/read goes through ``stash.put``/``stash.get`` on an explicit
    state carried by the scan. The default RawStash reproduces the
    pre-stash runner bitwise; QuantStash stores int8/fp8 codes + per-block
    scales. Every stage's forward consumes the DEQUANTIZED slot value —
    stage 0 writes its embedding output and reads it back, and the
    backward's stage-0 recompute applies the same perturbation via the
    straight-through ``stash.roundtrip`` — so the vjp grads are exact
    grads of the (slightly perturbed) forward that actually ran, and
    1F1B == GPipe bitwise still holds per backend. Cotangent slots stay at
    the native dtype by default (they are consumed the tick after they
    arrive — compressing them buys little capacity); a backend constructed
    with ``cotangents=True`` (``QuantStash``) routes them through the same
    codec as activation slots, which matters when interleaved schedules
    hold several cotangents live (the remat-vs-compression trade
    ``auto_plan`` prices).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as Pspec
    from jax import shard_map

    from repro.core.stash import RawStash

    backend = stash if stash is not None else RawStash()
    if not backend.scan_capable:
        raise ValueError(
            f"stash backend {backend.name!r} is host-driven; use "
            "pipeline_grads_host (the in-scan runner cannot issue host "
            "transfers per slot)"
        )
    # static Python bool: picks the cotangent-buffer representation at
    # trace time (raw native-dtype buffers vs the backend's codec state)
    quant_cot = bool(getattr(backend, "cotangents", False))

    P_count = table.n_stages
    assert mesh.shape[axis] == P_count, (mesh.shape, P_count)
    fwd_perm = [(i, (i + 1) % P_count) for i in range(P_count)]
    bwd_perm = [(i, (i - 1) % P_count) for i in range(P_count)]
    Wa, Wc = table.n_act_slots, table.n_cot_slots
    rows = {
        k: jnp.asarray(getattr(table, k))
        for k in ("f_mb", "f_slot", "b_mb", "b_slot", "b_cot", "arr_f", "arr_b")
    }
    zero_metrics = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), metrics_struct
    )
    if seed is None:
        seed = jnp.ones((), jnp.float32)

    def inner(sid, sp, shared, mbs, seed_):
        stage = sid[0]
        is_first = stage == 0
        is_last = stage == P_count - 1
        x_zero = jnp.zeros(x_struct.shape, x_struct.dtype)

        def mb_slice(m):
            return jax.tree.map(lambda a: a[m], mbs)

        def full_fn(sp_, sh_, xs_, m):
            mb = mb_slice(m)
            # stage 0 recomputes its input from first_fn; the roundtrip STE
            # re-applies the stash perturbation so recompute matches the
            # put-then-get forward bitwise (identity for RawStash)
            x = jax.lax.cond(
                is_first,
                lambda: backend.roundtrip(
                    first_fn(sh_, mb).astype(x_struct.dtype)
                ),
                lambda: xs_,
            )
            y, aux = stage_fn(sp_, x)
            tail, metrics = jax.lax.cond(
                is_last,
                lambda: last_fn(sh_, y, mb),
                lambda: (jnp.zeros((), jnp.float32), zero_metrics),
            )
            return (y, aux.astype(jnp.float32) + tail), metrics

        def tick(carry, row):
            act, cot, gacc, sacc, lacc, macc, fwd_in, bwd_in = carry
            g = {k: row[k][stage] for k in rows}
            # arrivals land before this tick's op reads the buffers (slot
            # writes route through the stash backend; -1 -> trash slot Wa)
            act = backend.put(
                act, jnp.where(g["arr_f"] >= 0, g["arr_f"], Wa), fwd_in
            )
            cot_w = jnp.where(g["arr_b"] >= 0, g["arr_b"], Wc)
            if quant_cot:
                cot = backend.put(cot, cot_w, bwd_in)
            else:
                cot = cot.at[cot_w].set(bwd_in)
            opk = jnp.where(g["f_mb"] >= 0, 1, jnp.where(g["b_mb"] >= 0, 2, 0))

            def idle_op(act, cot, gacc, sacc, lacc, macc):
                return act, cot, gacc, sacc, lacc, macc, x_zero, x_zero

            def f_op(act, cot, gacc, sacc, lacc, macc):
                m = g["f_mb"]
                slot = jnp.where(g["f_slot"] >= 0, g["f_slot"], Wa)
                # stage 0 stashes its own first_fn output (other stages'
                # slots were filled by the ppermute arrival above); ALL
                # stages then compute on the slot's stored value, so the
                # forward consumes exactly what the backward will read
                act = jax.lax.cond(
                    is_first,
                    lambda a: backend.put(
                        a, slot,
                        first_fn(shared, mb_slice(m)).astype(x_struct.dtype),
                    ),
                    lambda a: a,
                    act,
                )
                x_in = backend.get(act, slot, x_struct)
                y, _ = stage_fn(sp, x_in)
                return act, cot, gacc, sacc, lacc, macc, y, x_zero

            def b_op(act, cot, gacc, sacc, lacc, macc):
                m = g["b_mb"]
                x_saved = backend.get(
                    act, jnp.where(g["b_slot"] >= 0, g["b_slot"], Wa), x_struct
                )
                cot_r = jnp.where(g["b_cot"] >= 0, g["b_cot"], Wc)
                if quant_cot:
                    cot_in = backend.get(cot, cot_r, x_struct)
                else:
                    cot_in = cot[cot_r]
                (y, loss), vjp_fn, metrics = jax.vjp(
                    lambda sp_, sh_, xs_: full_fn(sp_, sh_, xs_, m),
                    sp, shared, x_saved, has_aux=True,
                )
                y_cot = jnp.where(is_last, jnp.zeros_like(y), cot_in)
                d_sp, d_sh, dx = vjp_fn((y_cot, seed_))
                gacc = jax.tree.map(jnp.add, gacc, d_sp)
                sacc = jax.tree.map(jnp.add, sacc, d_sh)
                macc = jax.tree.map(jnp.add, macc, metrics)
                return act, cot, gacc, sacc, lacc + loss, macc, x_zero, dx

            act, cot, gacc, sacc, lacc, macc, y_send, dx_send = jax.lax.switch(
                opk, (idle_op, f_op, b_op), act, cot, gacc, sacc, lacc, macc
            )
            fwd_nxt = jax.lax.ppermute(y_send, axis, fwd_perm)
            bwd_nxt = jax.lax.ppermute(dx_send, axis, bwd_perm)
            return (act, cot, gacc, sacc, lacc, macc, fwd_nxt, bwd_nxt), None

        zeros_like_tree = lambda t: jax.tree.map(
            lambda a: jnp.zeros(a.shape, a.dtype), t
        )
        carry0 = (
            backend.init(Wa + 1, x_struct),
            backend.init(Wc + 1, x_struct) if quant_cot
            else jnp.zeros((Wc + 1,) + x_struct.shape, x_struct.dtype),
            zeros_like_tree(sp),
            zeros_like_tree(shared),
            jnp.zeros((), jnp.float32),
            zero_metrics,
            x_zero,
            x_zero,
        )
        carry, _ = jax.lax.scan(tick, carry0, rows)
        _, _, gacc, sacc, lacc, macc, _, _ = carry

        red = (axis,) + ((data_axis,) if data_axis else ())
        sacc = jax.tree.map(lambda a: jax.lax.psum(a, red), sacc)
        lacc = jax.lax.psum(lacc, red)
        macc = jax.tree.map(lambda a: jax.lax.psum(a, red), macc)
        if data_axis:
            gacc = jax.tree.map(lambda a: jax.lax.psum(a, data_axis), gacc)
        return lacc, macc, gacc, sacc

    repl = lambda t: jax.tree.map(lambda _: Pspec(), t)
    sid = jnp.arange(P_count, dtype=jnp.int32)
    fn = shard_map(
        inner,
        mesh=mesh,
        in_specs=(Pspec(axis), stage_specs, repl(shared_params), mb_specs,
                  Pspec()),
        out_specs=(Pspec(), repl(metrics_struct), stage_specs,
                   repl(shared_params)),
        check_vma=False,
    )
    return fn(sid, stage_params, shared_params, microbatches, seed)


def pipeline_grads_host(
    first_fn: Callable,
    stage_fn: Callable,
    last_fn: Callable,
    stage_params: Any,
    shared_params: Any,
    microbatches: Any,
    *,
    table: TickTable,
    x_struct,
    metrics_struct: Any,
    seed=None,
    stash=None,
    lookahead: int = 2,
):
    """Host-driven twin of :func:`pipeline_grads`: the same tick tables,
    executed as a Python loop on ONE device (dp = tp = 1), with all P
    stages' ops issued sequentially per tick and ppermute traffic emulated
    by per-stage wire buffers (a value sent at tick t arrives at t+1,
    exactly the table's ``avail`` contract).

    This is the execution mode where a stateful stash backend becomes
    legal: ``HostStash`` evicts activation slots to host RAM between a
    microbatch's forward and backward (vDNN applied to the 1F1B stash), so
    a pipeline whose min(P, M) raw slots exceed device memory still trains
    — slot indices are concrete ints here, and put/get may block on
    transfers. Math is identical to the in-scan runner per backend (same
    per-stage op order and grad accumulation), minus cross-device psum
    reduction order, so losses agree to float tolerance.

    Overlap: each tick first ``poll``s every stage's store (retiring
    completed async evictions), then reads the next ``lookahead`` ticks'
    B-entries from the table and ``prefetch``es their slots so host->device
    loads run under this tick's compute. A get neither windowed nor
    prefetched is a counted stall (``HostStash.stats``). ``lookahead=0``
    is the eager baseline. Prefetching is a pure residency hint — puts
    invalidate staged copies, so the result is bitwise-equal to the eager
    runner for every backend and lookahead.

    ``stage_params`` is the FULL stacked-layer tree (leading layer axis
    unsharded); returns (loss_sum, metrics_sums, stage_grads, shared_grads)
    with stage_grads matching ``stage_params``'s full shapes.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.stash import RawStash

    backend = stash if stash is not None else RawStash()
    quant_cot = bool(getattr(backend, "cotangents", False))
    P_count, M = table.n_stages, table.n_microbatches
    L = jax.tree.leaves(stage_params)[0].shape[0]
    assert L % P_count == 0, (L, P_count)
    k = L // P_count
    Wa, Wc = table.n_act_slots, table.n_cot_slots
    zero_metrics = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), metrics_struct
    )
    if seed is None:
        seed = jnp.ones((), jnp.float32)

    def sp_slice(s):
        return jax.tree.map(lambda a: a[s * k:(s + 1) * k], stage_params)

    def mb_slice(m):
        return jax.tree.map(lambda a: a[m], microbatches)

    def full_fn(s, m):
        is_first, is_last = s == 0, s == P_count - 1

        def fn(sp_, sh_, xs_):
            mb = mb_slice(m)
            if is_first:
                x = backend.roundtrip(first_fn(sh_, mb).astype(x_struct.dtype))
            else:
                x = xs_
            y, aux = stage_fn(sp_, x)
            if is_last:
                tail, metrics = last_fn(sh_, y, mb)
            else:
                tail, metrics = jnp.zeros((), jnp.float32), zero_metrics
            return (y, aux.astype(jnp.float32) + tail), metrics

        return fn

    acts = [backend.init(Wa, x_struct) for _ in range(P_count)]
    cots: List[List[Any]] = [[None] * max(Wc, 1) for _ in range(P_count)]
    gacc = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), stage_params)
    sacc = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), shared_params)
    lacc = jnp.zeros((), jnp.float32)
    macc = zero_metrics
    fwd_wire: List[Any] = [None] * P_count
    bwd_wire: List[Any] = [None] * P_count

    for t in range(table.n_ticks):
        # arrivals land before this tick's ops read the buffers
        for s in range(P_count):
            af = int(table.arr_f[t, s])
            if af >= 0:
                acts[s] = backend.put(acts[s], af, fwd_wire[s])
                fwd_wire[s] = None
            ab = int(table.arr_b[t, s])
            if ab >= 0:
                # quantized cotangent storage: the codec roundtrip value is
                # bitwise what the in-scan runner's put-then-get produces
                cots[s][ab] = (
                    backend.roundtrip(bwd_wire[s]) if quant_cot
                    else bwd_wire[s]
                )
                bwd_wire[s] = None
        # overlap pass: retire completed evictions, then start host->device
        # loads for the next ticks' backward reads (no-ops for RawStash &c)
        for s in range(P_count):
            backend.poll(acts[s])
        for dt in range(1, lookahead + 1):
            if t + dt >= table.n_ticks:
                break
            for s in range(P_count):
                if int(table.b_mb[t + dt, s]) >= 0:
                    backend.prefetch(acts[s], int(table.b_slot[t + dt, s]))
        next_fwd: List[Any] = [None] * P_count
        next_bwd: List[Any] = [None] * P_count
        for s in range(P_count):
            fm, bm = int(table.f_mb[t, s]), int(table.b_mb[t, s])
            if fm >= 0:
                slot = int(table.f_slot[t, s])
                if s == 0:
                    acts[0] = backend.put(
                        acts[0], slot,
                        first_fn(shared_params, mb_slice(fm)).astype(
                            x_struct.dtype
                        ),
                    )
                x_in = backend.get(acts[s], slot, x_struct)
                y, _ = stage_fn(sp_slice(s), x_in)
                if s + 1 < P_count:
                    next_fwd[s + 1] = y
            elif bm >= 0:
                slot = int(table.b_slot[t, s])
                x_saved = backend.get(acts[s], slot, x_struct)
                (y, loss), vjp_fn, metrics = jax.vjp(
                    full_fn(s, bm), sp_slice(s), shared_params, x_saved,
                    has_aux=True,
                )
                if s == P_count - 1:
                    y_cot = jnp.zeros_like(y)
                else:
                    y_cot = cots[s][int(table.b_cot[t, s])]
                d_sp, d_sh, dx = vjp_fn((y_cot, seed))
                lo, hi = s * k, (s + 1) * k
                gacc = jax.tree.map(
                    lambda g, d: g.at[lo:hi].add(d), gacc, d_sp
                )
                sacc = jax.tree.map(jnp.add, sacc, d_sh)
                macc = jax.tree.map(jnp.add, macc, metrics)
                lacc = lacc + loss
                if s > 0:
                    next_bwd[s - 1] = dx
        fwd_wire, bwd_wire = next_fwd, next_bwd
    return lacc, macc, gacc, sacc
