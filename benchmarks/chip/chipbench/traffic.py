"""The one traffic generator: reads a mix's parameters (``traffic/<mix>.json``)
and makes its requests or batches from the seed.

Serving mixes are open-loop: each request has a due time on the wall
clock. The sizes and the gaps between arrivals of each phase (ramp before
the window, the window, the tail after it) are drawn once from the mix's
``shape_seed``, in one order for every seed; ``--seed`` draws the tokens.
So every seed offers the same schedule of work. (Seeded-trace structure
after ``serve/frontend.py``'s ``poisson_trace``, on the wall clock instead
of engine ticks.)

Training mixes are steps of ``global_batch x seq_len`` uniform tokens,
``distinct_batches`` of them, each row different.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List

import numpy as np

PHASES = ("ramp", "window", "tail")


@dataclasses.dataclass
class Request:
    phase: str
    due: float                # seconds after the window opens (ramp < 0)
    prompt: np.ndarray
    max_new: int


def _rng(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (2**63), *salt])


def lengths(spec: Dict[str, Any], n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths from a clipped lognormal with the given median."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], size=n)
    return np.clip(np.round(x), spec["min"], spec["max"]).astype(np.int64)


def phase_counts(mix: Dict[str, Any], seconds: float) -> Dict[str, int]:
    rate = mix["rate_rps"]
    return {
        "ramp": int(round(rate * mix["ramp_s"])),
        "window": max(1, int(round(rate * seconds))),
        "tail": int(round(rate * mix["tail_s"])),
    }


def serve_requests(mix: Dict[str, Any], seed: int, seconds: float,
                   vocab: int) -> List[Request]:
    """All requests of one run, ordered by due time."""
    counts = phase_counts(mix, seconds)
    spans = {"ramp": (-mix["ramp_s"], mix["ramp_s"]),
             "window": (0.0, seconds), "tail": (seconds, mix["tail_s"])}
    out: List[Request] = []
    for pi, phase in enumerate(PHASES):
        n = counts[phase]
        if n == 0:
            continue
        shape = _rng(mix["shape_seed"], pi)
        plen = lengths(mix["prompt"], n, shape)
        olen = lengths(mix["output"], n, shape)
        gaps = shape.exponential(1.0, size=n)
        # n arrivals spread over the phase at the mix's mean rate
        start, span = spans[phase]
        due = start + span * (np.cumsum(gaps) - gaps[0]) / gaps.sum()
        toks = _rng(seed, 2, pi)
        for d, p, o in zip(due, plen, olen):
            out.append(Request(phase, float(d),
                               toks.integers(0, vocab, size=int(p),
                                             dtype=np.int32), int(o)))
    out.sort(key=lambda r: r.due)
    return out


def train_batches(mix: Dict[str, Any], seed: int, vocab: int) -> List[Dict[str, np.ndarray]]:
    """``distinct_batches`` batches of tokens/labels (next-token shift)."""
    B, S = mix["global_batch"], mix["seq_len"]
    rng = _rng(seed, 3)
    out = []
    for _ in range(mix["distinct_batches"]):
        t = rng.integers(0, vocab, size=(B, S + 1), dtype=np.int32)
        out.append({"tokens": t[:, :-1], "labels": t[:, 1:]})
    return out
