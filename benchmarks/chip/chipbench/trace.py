"""Device trace: capture with ``jax.profiler`` and reduce to numbers.

``load`` turns an ``.xplane.pb`` into plain lists:

* ``device``: {device id: [(op name, start_ns, dur_ns)]} from each TPU
  plane's op line (``XLA Ops``), which holds one event per executed HLO op
  or kernel (a Pallas call is one event, its grid steps are inside it);
* ``async``: the same from each TPU plane's ``Async XLA Ops`` line, where
  an asynchronous collective runs from its start to its done;
* ``kinds``: {device id: {op name: HLO opcode}} of the op line's
  collectives (a collective's name may be JAX's, ``psum.187``: its opcode,
  ``all-reduce``, says what it is);
* ``host``: [(span name, start_ns, dur_ns)] for the benchmark's own
  ``TraceAnnotation`` spans (names starting ``bench.``).

Device and host events share the profiler's clock. Everything after
``load`` is pure list arithmetic, tested on a recorded trace under
``tests/fixtures``.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, dur_ns
SPAN_PREFIX = "bench."
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
COLLECTIVES = ("all-reduce", "collective-permute", "all-gather",
               "reduce-scatter", "all-to-all")


def latest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str) -> Dict[str, object]:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    device: Dict[int, List[Event]] = {}
    asyn: Dict[int, List[Event]] = {}
    kinds: Dict[int, Dict[str, str]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            tail = plane.name[len("/device:TPU:"):]
            if not tail.isdigit():
                continue
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OP_LINE] or lines
            dev = int(tail)
            device[dev], longs = [], set()
            for ln in ops:
                for ev in ln.events:
                    longs.add(ev.name)
                    device[dev].append((short_name(ev.name),
                                        float(ev.start_ns),
                                        float(ev.duration_ns)))
            kinds[dev] = {short_name(n): op_kind(n) for n in longs
                          if is_collective(op_kind(n))}
            asyn[dev] = [(short_name(ev.name), float(ev.start_ns),
                          float(ev.duration_ns))
                         for ln in lines if ln.name == ASYNC_LINE
                         for ev in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
    return {"device": device, "async": asyn, "kinds": kinds, "host": host}


def short_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")


def op_kind(hlo: str) -> str:
    """``%psum.3 = bf16[8]{0:T(256)} all-reduce(...)`` -> ``all-reduce``:
    the first word after the result's type that opens an operand list (a
    type's layout has no space before its parentheses); a name with no
    instruction text is its own kind."""
    _, sep, rest = hlo.partition(" = ")
    m = _OPCODE.search(" " + rest) if sep else None
    return m.group(1) if m else short_name(hlo)


# ------------------------------------------------------------- arithmetic
def clip(events: Iterable[Event], w0: float, w1: float) -> List[Event]:
    """Events cut to the window [w0, w1]; those outside dropped."""
    out = []
    for name, s, d in events:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            out.append((name, a, b - a))
    return out


def union(events: Iterable[Event]) -> List[Tuple[float, float]]:
    """Merged [start, end) intervals covered by ``events``."""
    iv = sorted((s, s + d) for _, s, d in events if d > 0)
    out: List[List[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(events: Iterable[Event]) -> float:
    return sum(b - a for a, b in union(events))


def device_idle(ctx) -> float:
    """Share of the traced window (%) in which no operation ran on the
    device, averaged over the cell's devices; busy is the union of each
    device's op intervals. The reader of every ``device_idle.*`` metric."""
    busy = sum(ctx.busy_s.values()) / len(ctx.busy_s)
    return 100.0 * (1.0 - busy / ctx.window_s)


def is_collective(kind: str) -> bool:
    return any(c in kind.lower() for c in COLLECTIVES)


def subtract(a: Sequence[Tuple[float, float]], b: Sequence[Tuple[float, float]]
             ) -> float:
    """Length of the merged intervals ``a`` outside the merged intervals
    ``b`` (both sorted, as ``union`` gives them)."""
    total, j = 0.0, 0
    for s, e in a:
        t = s
        while j < len(b) and b[j][1] <= t:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > t:
                total += b[k][0] - t
            t = max(t, b[k][1])
            k += 1
        if e > t:
            total += e - t
    return total


def collective_spans(ops: Iterable[Event], asyn: Iterable[Event] = (),
                     kinds: Optional[Dict[str, str]] = None) -> List[Event]:
    """Where collectives run on one device: each innermost collective op of
    the op line (a synchronous one runs for its whole event), each
    ``<collective>-start``/``-done`` pair of it from the start's beginning
    to the done's end (paired in order per kind), and the collective
    events of the async line. An op's kind is its opcode in ``kinds``, or
    else its name."""
    kinds = kinds or {}
    out, open_ = [], defaultdict(list)
    for name, s, d in sorted(leaves(ops), key=lambda e: e[1]):
        base = kinds.get(name, name).split(".", 1)[0]
        if not is_collective(base):
            continue
        if base.endswith("-start"):
            open_[base[:-len("-start")]].append((name, s))
        elif base.endswith("-done"):
            kind = base[:-len("-done")]
            if open_[kind]:
                n0, s0 = open_[kind].pop(0)
                out.append((n0, s0, s + d - s0))
            else:
                out.append((name, s, d))
        else:
            out.append((name, s, d))
    out += [e for e in asyn if is_collective(e[0])]
    return out


def collective_exposed(ctx) -> Optional[float]:
    """Share of the traced window (%) in which a collective runs on a
    device while no compute op does, averaged over the cell's devices;
    compute is every innermost op of the op line that is no collective.
    None where no device ran a collective."""
    shares, seen = [], False
    for dev, ops in ctx.device.items():
        kinds = ctx.op_kinds.get(dev, {})
        coll = union(collective_spans(ops, ctx.async_ops.get(dev, ()), kinds))
        seen = seen or bool(coll)
        compute = union(e for e in leaves(ops)
                        if not is_collective(kinds.get(e[0], e[0])))
        shares.append(subtract(coll, compute) / (ctx.window_s * 1e9))
    return 100.0 * sum(shares) / len(shares) if seen else None


def window_of(host: Sequence[Event], name: str = "bench.window") -> Tuple[float, float]:
    for n, s, d in host:
        if n == name:
            return s, s + d
    raise ValueError(f"no {name!r} span in the trace")


def idle_gaps(events: Iterable[Event], host: Sequence[Event], w0: float,
              w1: float, top: int = 10) -> List[Tuple[str, float]]:
    """The ``top`` longest gaps in [w0, w1] with no device op, each named by
    the innermost benchmark span open at its midpoint; seconds."""
    busy = union(clip(events, w0, w1))
    gaps, t = [], w0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if w1 > t:
        gaps.append((t, w1))
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        open_ = [(d, n) for n, s, d in host
                 if s <= mid <= s + d and n != "bench.window"]
        label = min(open_)[1] if open_ else "no span"
        out.append((label, (b - a) / 1e9))
    out.sort(key=lambda x: -x[1])
    return out[:top]


def leaves(events: Iterable[Event]) -> List[Event]:
    """The events that contain no other event: an op line nests the ops of
    a loop's body inside the loop's own event."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    return [e for i, e in enumerate(ev)
            if i + 1 == len(ev) or ev[i + 1][1] >= e[1] + e[2]]


def top_ops(events: Iterable[Event], top: int = 10) -> List[Tuple[str, float]]:
    """Innermost device ops by total time, seconds."""
    tot: Dict[str, float] = defaultdict(float)
    for name, _, d in leaves(events):
        tot[name] += d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [(n, t / 1e9) for n, t in best]


def matching(events: Iterable[Event], patterns: Sequence[str]) -> List[Event]:
    pats = [p.lower() for p in patterns]
    return [e for e in events if any(p in e[0].lower() for p in pats)]


def summarize(tr: Dict[str, object], window: Optional[Tuple[float, float]] = None):
    """(per-device clipped events, host spans, w0, w1)."""
    host = tr["host"]
    w0, w1 = window or window_of(host)
    dev = {k: clip(v, w0, w1) for k, v in tr["device"].items()}
    return dev, host, w0, w1
