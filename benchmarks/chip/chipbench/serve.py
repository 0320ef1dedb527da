"""Open-loop wall-clock driver for the paged serving engine.

One process, one engine. Requests of the mix are submitted when due (one
thread: due requests go in at each tick boundary, and the driver sleeps
when the engine is idle); tokens are timed as ``on_token`` delivers them
to the host. TTFT counts from when a request was due, so a late submit or
a long tick counts against the engine.

Phases: warm-up (two small requests that run every engine program once),
ramp (the mix's own arrivals for ``ramp_s``, so the window starts at a
steady occupancy), window (``--seconds``; its requests are the ones
measured), tail (arrivals go on after the window until every window
request has its first token, at most ``drain_s``).

Work per device call is counted from what the driver sent and received:
deliveries give each decode step's position, and prefill chunks are
assigned in submission order, one ``prefill_chunks`` tick at a time (the
engine admits FIFO and chunks the oldest prefilling request first).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from chipbench import counts, traffic, weights
from chipbench.common import CellRun, Span


@dataclasses.dataclass
class Rec:
    rid: int
    phase: str
    prompt: np.ndarray
    max_new: int
    due: float = 0.0          # absolute perf_counter time
    times: List[float] = dataclasses.field(default_factory=list)
    sizes: List[int] = dataclasses.field(default_factory=list)
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


@dataclasses.dataclass
class Tick:
    start: float
    end: float = 0.0
    prefill: List[Any] = dataclasses.field(default_factory=list)
    decode: Dict[int, List[int]] = dataclasses.field(default_factory=dict)
    occupancy: Tuple[int, int, int] = (0, 0, 0)


def percentile(xs, q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    return float(np.percentile(np.asarray(xs, np.float64), q))


class Driver:
    def __init__(self, run: CellRun):
        self.run = run
        self.c = run.config
        self.mix = run.mix
        self.recs: Dict[int, Rec] = {}
        self.ticks: List[Tick] = []
        self.cur: Optional[Tick] = None
        self.chunk_queue: "collections.deque[List[int]]" = collections.deque()
        self.chunk_misses = 0
        self.firsts: List[int] = []

    # ------------------------------------------------------------ engine
    def build(self):
        import jax
        import jax.numpy as jnp

        from repro.models import Runtime, init_params
        from repro.serve import EngineConfig, ServeEngine

        c, s = self.c, self.c["serve"]
        self.cfg = self.run.program_config()
        struct = jax.eval_shape(lambda: init_params(self.cfg,
                                                    jax.random.PRNGKey(0)))
        self.p_struct = struct
        params = weights.generate(struct, self.run.seed, jnp.bfloat16)
        mx = self.mix
        cap = EngineConfig.capacity(
            mx["prompt"]["max"], mx["output"]["max"], slots=s["slots"],
            headroom=s["pool_headroom"], cfg=self.cfg,
            page_size=s["page_size"], kv_dtype=s["kv_dtype"],
            native_itemsize=2,
        )
        ecfg = cap.engine(
            use_kernel=s["use_kernel"], prefill_chunk=s["prefill_chunk"],
            inner_steps=s["inner_steps"], policy=s["policy"],
            prefix_cache=s["prefix_cache"], temperature=0.0,
        )
        self.eng = ServeEngine(self.cfg, params, Runtime(dtype=jnp.bfloat16),
                               ecfg)
        self.run.note(f"engine: {cap.slots} slots, pool "
                      f"{cap.pool_bytes / 2**30:.3f} GiB, horizon "
                      f"{cap.max_len} tokens, chunk {s['prefill_chunk']}, "
                      f"inner_steps {s['inner_steps']}")

    def submit(self, phase: str, prompt: np.ndarray, max_new: int, due: float):
        def on_token(rid, toks, done):
            rec = self.recs[rid]
            if toks:
                t = time.perf_counter()
                if not rec.out:
                    self.firsts.append(rid)
                elif self.cur is not None:
                    self.cur.decode[rid] = [len(rec.prompt) + len(rec.out) - 1 + i
                                            for i in range(len(toks))]
                rec.times.append(t)
                rec.sizes.append(len(toks))
                rec.out.extend(int(x) for x in toks)
            if done is not None:
                rec.done = True

        rid = self.eng.submit(prompt, max_new, on_token=on_token)
        self.recs[rid] = Rec(rid, phase, prompt, max_new, due=due)
        self.chunk_queue.append([rid, 0])


    def _assign_chunks(self, n: int) -> None:
        T = self.c["serve"]["prefill_chunk"]
        for _ in range(n):
            if not self.chunk_queue:
                self.chunk_misses += 1
                return
            head = self.chunk_queue[0]
            rid, start = head
            P = len(self.recs[rid].prompt)
            rows = min(T, P - start)
            last = start + rows >= P
            self.cur.prefill.append((start, rows, last))
            if last:
                self.chunk_queue.popleft()
            else:
                head[1] = start + rows

    def tick(self) -> None:
        self.cur = Tick(time.perf_counter())
        before = self.eng.stats.get("prefill_chunks", 0)
        with Span("bench.tick"):
            self.eng.step()
        self._assign_chunks(self.eng.stats.get("prefill_chunks", 0) - before)
        # a request's first token ends its prefill: all its chunks assigned
        queued = {r for r, _ in self.chunk_queue}
        self.chunk_misses += sum(1 for r in self.firsts if r in queued)
        self.firsts.clear()
        self.cur.end = time.perf_counter()
        self.cur.occupancy = self.occupancy()
        self.ticks.append(self.cur)
        self.cur = None

    def occupancy(self):
        """(seated slots, pages reserved, KV tokens written) after a tick."""
        live = sum(len(r.prompt) + len(r.out) - 1
                   for r in self.recs.values() if r.out and not r.done)
        if self.chunk_queue:
            live += self.chunk_queue[0][1]
        seated = sum(s is not None for s in self.eng._slots)
        return seated, self.eng.pool.pages_in_use, live

    def drive_until_idle(self) -> None:
        while self.eng.busy:
            self.tick()

    # ------------------------------------------------------------- phases
    def warm_up(self) -> None:
        """Run every engine program once: a prefill-only call, the fused
        prefill + decode call, a decode-only call, retirement."""
        s = self.c["serve"]
        rng = np.random.default_rng(0)
        V = self.c["vocab_size"]
        n_new = 2 * s["inner_steps"] + 1
        first = rng.integers(0, V, 2 * s["prefill_chunk"] + 3, dtype=np.int32)
        self.submit("warm", first, n_new, time.perf_counter())
        while not self.recs[max(self.recs)].out:
            self.tick()
        self.submit("warm", first[: s["prefill_chunk"] + 1], n_new,
                    time.perf_counter())
        self.drive_until_idle()

    def open_loop(self) -> Dict[str, Any]:
        run, mix = self.run, self.mix
        reqs = traffic.serve_requests(mix, run.seed, run.seconds,
                                      self.c["vocab_size"])
        ramp = collections.deque(r for r in reqs if r.phase == "ramp")
        rest = collections.deque(r for r in reqs if r.phase != "ramp")
        late: List[float] = []

        def feed(queue, origin, now):
            while queue and origin + queue[0].due <= now:
                r = queue.popleft()
                due = origin + r.due
                late.append(now - due)
                self.submit(r.phase, r.prompt, r.max_new, due)

        def wait_or_tick(queue, origin):
            if self.eng.busy:
                self.tick()
            else:
                nxt = origin + queue[0].due if queue else time.perf_counter()
                with Span("bench.wait"):
                    time.sleep(max(1e-3, nxt - time.perf_counter()))

        # ramp: due times lie in [-ramp_s, 0) before its end, r_end
        r_end = time.perf_counter() + mix["ramp_s"]
        while time.perf_counter() < r_end:
            feed(ramp, r_end, time.perf_counter())
            if self.eng.busy:
                self.tick()
            else:
                nxt = r_end + ramp[0].due if ramp else r_end
                with Span("bench.wait"):
                    time.sleep(max(0.0, min(nxt, r_end) - time.perf_counter()))
        run.start_window()
        t_open = time.perf_counter()
        t_end = t_open + run.seconds
        with Span("bench.window"):
            while time.perf_counter() < t_end:
                feed(rest, t_open, time.perf_counter())
                wait_or_tick(rest, t_open)
        t_close = time.perf_counter()
        run.close_window()
        window = [r for r in self.recs.values() if r.phase == "window"]
        deadline = t_close + mix["drain_s"]
        while (any(not r.out for r in window)
               and time.perf_counter() < deadline):
            feed(rest, t_open, time.perf_counter())
            wait_or_tick(rest, t_open)
        return {"t_open": t_open, "t_close": t_close, "late": late,
                "window": window}

    # ------------------------------------------------------------ metrics
    def end_to_end(self, w: Dict[str, Any]) -> Dict[str, Any]:
        t_open, t_close, window = w["t_open"], w["t_close"], w["window"]
        ttft = [r.times[0] - r.due for r in window if r.out]
        failed = sum(1 for r in window if not r.out)
        gaps: List[float] = []
        for r in self.recs.values():
            for k in range(1, len(r.times)):
                if t_open <= r.times[k] <= t_close:
                    gaps.append(r.times[k] - r.times[k - 1])
                    gaps.extend([0.0] * (r.sizes[k] - 1))
        late = w["late"]
        occ = [t.occupancy for t in self.ticks
               if t_open <= t.start and t.end <= t_close]
        if occ:
            pool = self.eng.pool
            s = self.c["serve"]
            self.run.note(
                f"occupancy over {len(occ)} ticks: mean seated slots "
                f"{np.mean([o[0] for o in occ]):.2f} of {s['slots']}, "
                f"reserved share {np.mean([o[1] for o in occ]) / pool.budget:.3f}"
                f" (max {max(o[1] for o in occ) / pool.budget:.3f}), live KV "
                f"share {np.mean([o[2] for o in occ]) / (pool.budget * s['page_size']):.3f}"
                f" of {pool.budget * s['page_size']} tokens")
        self.run.note(
            f"samples: {len(window)} window requests ({len(ttft)} with a "
            f"first token, {failed} without), {len(gaps)} inter-token gaps; "
            f"generator lateness p50 {percentile(late, 50) * 1e3:.3f} ms "
            f"max {max(late) * 1e3:.3f} ms over {len(late)} submits")
        if ttft:
            self.run.note(
                f"ttft_ms p50 {percentile(ttft, 50) * 1e3:.1f} p95 "
                f"{percentile(ttft, 95) * 1e3:.1f} max {max(ttft) * 1e3:.1f}; "
                f"itl_ms p50 {percentile(gaps, 50) * 1e3:.1f} p95 "
                f"{percentile(gaps, 95) * 1e3:.1f}; chunk assignment misses "
                f"{self.chunk_misses}")
        metrics = {}
        if ttft:
            metrics["ttft_p50_ms"] = percentile(ttft, 50) * 1e3
            metrics["ttft_p95_ms"] = percentile(ttft, 95) * 1e3
        if gaps:
            metrics["itl_p95_ms"] = percentile(gaps, 95) * 1e3
        return {"metrics": metrics, "attempted": len(window), "failed": failed}

    def work(self, t_open: float, t_close: float) -> Dict[str, Any]:
        """Per-call lower bounds of the ticks inside the window."""
        peaks, c = self.run.peaks, self.c
        attn_s = step_s = 0.0
        n = 0
        tick_ms = []
        for t in self.ticks:
            if t.start < t_open or t.end > t_close:
                continue
            n += 1
            tick_ms.append((t.end - t.start) * 1e3)
            steps = max((len(v) for v in t.decode.values()), default=0)
            dsteps = [[pos[i] for pos in t.decode.values() if i < len(pos)]
                      for i in range(steps)]
            w = counts.serve_call(
                c, [(s, r) for s, r, _ in t.prefill], dsteps,
                prefill_sampled=sum(1 for *_, last in t.prefill if last))
            attn_s += counts.bound_s(*w["attn"], peaks)
            step_s += counts.bound_s(*w["step"], peaks)
        return {"ticks": n, "tick_ms": tick_ms, "attn_bound_s": attn_s,
                "step_bound_s": step_s}

    # ------------------------------------------------------- correctness
    def sample(self, t_open: float, t_close: float) -> List[Rec]:
        """Requests the window finished (last token delivered inside it),
        drawn from the seed, the longest among them: at least
        ``min_requests`` and ``min_tokens`` served tokens, at most
        ``max_requests``."""
        chk = self.mix["check"]
        done = sorted((r for r in self.recs.values()
                       if r.done and r.phase != "warm"
                       and len(r.out) == r.max_new
                       and t_open <= r.times[-1] <= t_close),
                      key=lambda r: r.rid)
        if not done:
            return []
        longest = max(done, key=lambda r: (len(r.out), -r.rid))
        rng = np.random.default_rng([self.run.seed % (2**63), 4])
        picked = [longest]
        for i in rng.permutation(len(done)):
            if len(picked) >= chk["max_requests"] or (
                    len(picked) >= chk["min_requests"]
                    and sum(len(r.out) for r in picked) >= chk["min_tokens"]):
                break
            if done[i] is not longest:
                picked.append(done[i])
        return picked

    def release(self) -> None:
        del self.eng
        import gc

        gc.collect()


def served_gaps(c: Dict[str, Any], params, picked: List[Rec], modes=("f32",),
                bucket: int = 1024) -> Dict[str, float]:
    """Widest gap, over every served token of ``picked``, by which its f32
    reference logit lies below the reference's best at that position.
    With ``"fp8"`` in ``modes`` also the control's: the gap of the token the
    fp8 reference puts first. Tokens, and the rows read (the last one
    repeated), are padded to a multiple of ``bucket``, so that a few
    programs serve every request and the compile cache finds them in
    later runs."""
    import jax.numpy as jnp

    from references import dense_decoder as ref

    worst = {m: 0.0 for m in modes}
    for r in picked:
        seq = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        L = -(-len(seq) // bucket) * bucket
        toks = np.zeros(L, np.int32)
        toks[: len(seq)] = seq
        rows = np.arange(len(r.prompt) - 1, len(seq))
        padded = np.full(-(-len(rows) // bucket) * bucket, rows[-1])
        padded[: len(rows)] = rows
        lg = np.asarray(ref.logits_at(c, params, jnp.asarray(toks),
                                      jnp.asarray(padded), "f32"))[: len(rows)]
        best = lg.max(axis=1)
        served = np.asarray(r.out)
        worst["f32"] = max(worst["f32"], float(np.max(
            best - lg[np.arange(len(rows)), served])))
        if "altered" in modes:
            # a planted fault: each served token replaced by the next id
            alt = (served + 1) % lg.shape[1]
            worst["altered"] = max(worst["altered"], float(np.max(
                best - lg[np.arange(len(rows)), alt])))
        if "fp8" in modes:
            lq = np.asarray(ref.logits_at(c, params, jnp.asarray(toks),
                                          jnp.asarray(padded), "fp8"))[: len(rows)]
            pick = lq.argmax(axis=1)
            worst["fp8"] = max(worst["fp8"], float(np.max(
                best - lg[np.arange(len(rows)), pick])))
    return worst


def run_cell(run: CellRun) -> Dict[str, Any]:
    d = Driver(run)
    d.build()
    t0 = time.perf_counter()
    d.warm_up()
    run.note(f"set-up: weights and engine by {t0 - run.t_start:.1f} s, "
             f"warm-up {time.perf_counter() - t0:.1f} s, then the "
             f"{run.mix['ramp_s']} s ramp")
    w = d.open_loop()
    e2e = d.end_to_end(w)
    work = d.work(w["t_open"], w["t_close"])
    run.read_memory()
    picked = d.sample(w["t_open"], w["t_close"])
    d.release()
    run.per_layer_inputs(work=work)
    # the reference, after the program's state is gone
    import jax.numpy as jnp

    params = weights.generate(d.p_struct, run.seed, jnp.bfloat16)
    modes = ("f32", "fp8", "altered") if run.control else ("f32",)
    t0 = time.perf_counter()
    gaps = served_gaps(run.config, params, picked, modes)
    del params
    n_tok = sum(len(r.out) for r in picked)
    run.note(f"reference: {len(picked)} requests, {n_tok} served tokens, "
             f"{time.perf_counter() - t0:.1f} s")
    limit = run.config["limits"]["served_gap"]
    checks = [("served_gap", gaps["f32"], limit)]
    if run.control:
        checks.append(("control_fp8_gap", gaps["fp8"], limit))
        checks.append(("fault_altered_token_gap", gaps["altered"], limit))
    ok = bool(picked) and gaps["f32"] <= limit
    return dict(e2e, correct=ok, checks=checks)
