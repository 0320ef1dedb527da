#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout: ``BENCHMARK.json`` names the cell, its
configuration (``configs/<config>.json``) and its traffic mix
(``traffic/<mix>.json``, whose ``driver`` names the module that drives it);
``src/`` holds the system under test. One process, no children. It refuses
to run without a TPU, with fewer chips than the cell asks for, with Pallas
kernels in interpret mode, or on a device kind missing from ``peaks.py``.

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` the window runs under the profiler and they are its
per-layer metrics, with ``busy_s``/``window_s`` and a ``breakdown``. The
last line of standard output is the result, one JSON object; the numbers
compared with the reference stand beside their limits as the last lines of
standard error and as the result's last key, ``checks``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def fail(msg: str, code: int = 1) -> None:
    print(f"run.py: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="also read the fp8 control beside the reference "
                         "(for setting limits; never part of a measured run)")
    args = ap.parse_args()

    bench_path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.isfile(bench_path):
        fail(f"no BENCHMARK.json at {ROOT}")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"no system under test: {ROOT}/src/repro is missing")
    if os.environ.get("REPRO_PALLAS_INTERPRET"):
        fail("REPRO_PALLAS_INTERPRET is set: the benchmark runs compiled kernels")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, HERE)

    from chipbench.common import CellRun, load_json, log
    from peaks import peaks_for

    bench = load_json(bench_path)
    try:
        run = CellRun(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace), control=args.control, t_start=T_START)
    except (KeyError, ValueError) as e:
        fail(str(e))
    chips = int(run.cell["chips"])

    import jax

    from repro.kernels.runtime import default_interpret
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: jax found {dev.platform}")
    if len(devices) < chips:
        fail(f"cell {run.name} needs {chips} chips, jax found {len(devices)}")
    if default_interpret():
        fail("Pallas kernels would run in interpret mode")
    try:
        peaks = peaks_for(dev.device_kind)
    except KeyError as e:
        fail(str(e))
    cache = enable_compile_cache()
    # every program, however quick to compile, is cached: set-up stays steady
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    run.use_devices(devices[:chips], peaks)
    log(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
        f"compile cache {cache}, cell {run.name}, seed {run.seed}")

    driver = importlib.import_module("chipbench." + run.mix["driver"])
    res = driver.run_cell(run)

    if run.trace:
        tr = run.read_trace()
        metrics = tr["metrics"]
        extra = {"busy_s": tr["busy_s"], "window_s": tr["window_s"]}
    else:
        try:
            metrics = run.end_to_end_metrics(res["metrics"])
        except ValueError as e:
            fail(str(e))
        extra = {}
    checks = {name: {"value": float(v), "limit": float(lim)}
              for name, v, lim in res["checks"]}
    out = {
        "correct": bool(res["correct"]),
        "attempted": int(res["attempted"]),
        "failed": int(res["failed"]),
        "metrics": metrics,
        "device": dict({"platform": dev.platform, "kind": dev.device_kind,
                        "count": len(devices),
                        "memory_peak_bytes": run.memory_peak_bytes}, **extra),
    }
    if run.trace:
        out["breakdown"] = tr["breakdown"]
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
