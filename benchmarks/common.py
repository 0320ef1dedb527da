"""Shared benchmark utilities: timing + CSV emission."""
from __future__ import annotations

import time
from typing import Callable, List

import jax

ROWS: List[str] = []


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    row = f"{name},{us_per_call:.1f},{derived}"
    ROWS.append(row)
    print(row)


def time_fn(fn: Callable, *args, iters: int = 5, warmup: int = 2) -> float:
    """Median wall-time in microseconds (jit-compiled fns; blocks on ready)."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


def header(title: str) -> None:
    print(f"\n# === {title} ===")
    print("name,us_per_call,derived")


def subprocess_env():
    """Inherit the environment (a bare env hangs jax backend probing on CPU
    containers), pinned to the CPU: the children fake host devices, and a
    child that reached for the TPU would fight its parent for the chip.
    Scripts set their own XLA_FLAGS."""
    import os

    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return env
