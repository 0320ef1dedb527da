"""Device trace: capture with ``jax.profiler`` and reduce to numbers.

``load`` turns an ``.xplane.pb`` into plain lists:

* ``device``: {device id: [(op name, start_ns, dur_ns)]} from each TPU
  plane's op line (``XLA Ops``), which holds one event per executed HLO op
  or kernel (a Pallas call is one event, its grid steps are inside it);
* ``host``: [(span name, start_ns, dur_ns)] for the benchmark's own
  ``TraceAnnotation`` spans (names starting ``bench.``).

Device and host events share the profiler's clock. Everything after
``load`` is pure list arithmetic, tested on a recorded trace under
``tests/fixtures``.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, dur_ns
SPAN_PREFIX = "bench."
OP_LINE = "XLA Ops"


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
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            tail = plane.name[len("/device:TPU:"):]
            if not tail.isdigit():
                continue
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OP_LINE] or lines
            device[int(tail)] = [
                (short_name(ev.name), float(ev.start_ns), float(ev.duration_ns))
                for ln in ops for ev in ln.events
            ]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
    return {"device": device, "host": host}


def short_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


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
