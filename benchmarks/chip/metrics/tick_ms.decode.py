"""Median wall time (ms) of one ``ServeEngine.step()`` inside the window,
from the benchmark's own span around each call; moves ``itl_p95_ms``."""

import statistics


def read(ctx):
    ticks = ctx.inputs.get("work", {}).get("tick_ms") or []
    return statistics.median(ticks) if ticks else None
