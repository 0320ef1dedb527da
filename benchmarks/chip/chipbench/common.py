"""What every cell shares: the run's settings, the device checks, set-up
and window clocks, the compile counter, the trace and the per-layer
readers, and the result line.

Everything a cell is made of is found by name: ``BENCHMARK.json`` names the
cell's configuration and traffic mix, ``configs/<config>.json`` holds the
configuration, ``traffic/<mix>.json`` the mix (its ``driver`` names the
module of this package that drives it), ``metrics/<metric>.py`` each
per-layer metric's reader.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))   # benchmarks/chip


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def Span(name: str):
    """A host span in the profiler's trace (a no-op when none is running)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class CompileCounter:
    """Counts programs lowered and compiled (JAX's monitoring events)."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax._src import monitoring

        self.lowered = 0
        self.compiled = 0
        self.on = False
        monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name: str, _secs: float, **_kw) -> None:
        if not self.on:
            return
        if name == self.LOWER:
            self.lowered += 1
        elif name == self.COMPILE:
            self.compiled += 1


def load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class CellRun:
    """One run of one cell."""

    def __init__(self, bench: Dict[str, Any], workload: str, seed: int,
                 seconds: float, trace: bool, control: bool = False,
                 root: str = HERE, t_start: Optional[float] = None):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
        self.bench = bench
        self.cell = cells[workload]
        self.name = workload
        self.config = load_json(os.path.join(
            root, "configs", self.cell["config"] + ".json"))
        self.mix = load_json(os.path.join(
            root, "traffic", self.cell["traffic"] + ".json"))
        self.root = root
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.control = control
        self.t_start = t_start if t_start is not None else time.perf_counter()
        self.setup_s: Optional[float] = None
        self.memory_peak_bytes = 0
        self.inputs: Dict[str, Any] = {}
        self.peaks: Dict[str, float] = {}
        self.devices: List[Any] = []
        self.counter: Optional[CompileCounter] = None
        self.trace_dir = os.path.join(root, ".trace", workload)

    def note(self, msg: str) -> None:
        log(msg)

    # ----------------------------------------------------------- devices
    def use_devices(self, devices, peaks: Dict[str, float]) -> None:
        self.devices = list(devices)
        self.peaks = peaks
        self.counter = CompileCounter()

    def program_config(self):
        """The program's own config of this model, checked against the file
        (``program_size: reduced`` gives the program's smoke widths, for
        tests on the CPU)."""
        from repro.configs import launch_config

        c = self.config
        cfg = launch_config(c["program_arch"],
                            full=c.get("program_size", "full") == "full",
                            layers=c["num_hidden_layers"])
        self.check_arch(cfg)
        return cfg

    def check_arch(self, cfg) -> None:
        """The program runs the configuration as its file states it."""
        c = self.config
        want = {
            "d_model": c["hidden_size"], "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"], "head_dim": c["head_dim"],
            "d_ff": c["intermediate_size"], "vocab_size": c["vocab_size"],
            "n_layers": c["num_hidden_layers"], "mlp_gated": c["mlp_gated"],
            "norm": c["norm"], "rope_theta": c["rope_theta"],
            "tie_embeddings": c["tie_word_embeddings"],
        }
        got = {k: getattr(cfg, k) for k in want}
        bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
        if bad:
            raise ValueError(f"program config departs from the file: {bad}")

    # ------------------------------------------------------------ window
    def start_window(self) -> None:
        """Set-up ends here; compiles from here on are counted."""
        if self.trace:
            import shutil

            import jax

            shutil.rmtree(self.trace_dir, ignore_errors=True)
            os.makedirs(self.trace_dir, exist_ok=True)
            jax.profiler.start_trace(self.trace_dir)
        self.setup_s = time.perf_counter() - self.t_start
        if self.counter is not None:
            self.counter.on = True

    def close_window(self) -> None:
        if self.counter is not None:
            self.counter.on = False
            self.note(f"compiles inside the window: "
                      f"{self.counter.lowered} lowered, "
                      f"{self.counter.compiled} compiled")
        if self.trace:
            import jax

            jax.profiler.stop_trace()

    def read_memory(self) -> None:
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        self.memory_peak_bytes = peak

    def per_layer_inputs(self, **kw) -> None:
        self.inputs.update(kw)

    # ------------------------------------------------------------ results
    def end_to_end_names(self) -> List[Tuple[str, str]]:
        out = []
        for m in self.bench["end_to_end"]:
            if "workloads" in m and self.name not in m["workloads"]:
                continue
            out.append((m["name"], m["unit"]))
        return out

    def end_to_end_metrics(self, readings: Dict[str, float]) -> Dict[str, Any]:
        """The result's metrics of an untraced run: the driver's readings of
        this cell's end-to-end metrics and the set-up clock, each with its
        unit; readings of other metrics are dropped. A metric of the cell
        with no reading is an error."""
        want = dict(self.end_to_end_names())
        out = {k: {"value": float(v), "unit": want[k]}
               for k, v in readings.items() if k in want}
        out["setup_s"] = {"value": float(self.setup_s), "unit": "s"}
        missing = sorted(set(want) - set(out))
        if missing:
            raise ValueError(f"no reading of {missing}")
        return out

    def per_layer_specs(self) -> List[Dict[str, Any]]:
        """The per-layer metrics of this cell: each names its cells."""
        for m in self.bench["per_layer"]:
            if "workloads" not in m:
                raise ValueError(f"per-layer metric {m['name']} names no "
                                 f"workloads")
        return [m for m in self.bench["per_layer"]
                if self.name in m["workloads"]]

    def read_trace(self) -> Dict[str, Any]:
        """Device numbers, breakdown and per-layer metrics of a traced run."""
        from chipbench import trace as tr

        path = tr.latest_xplane(self.trace_dir)
        data = tr.load(path)
        dev, host, w0, w1 = tr.summarize(data)
        if not dev:
            raise RuntimeError("the trace holds no TPU device plane")
        window_s = (w1 - w0) / 1e9
        busy = {k: tr.busy_ns(v) / 1e9 for k, v in dev.items()}
        ctx = TraceContext(self, dev, host, window_s, busy)
        metrics = {}
        for spec in self.per_layer_specs():
            mod = load_module(os.path.join(self.root, "metrics",
                                           spec["name"] + ".py"),
                              "metric_" + spec["name"].replace(".", "_"))
            value = mod.read(ctx)
            if value is not None:
                metrics[spec["name"]] = {"value": float(value),
                                         "unit": spec["unit"]}
        all_ops = [e for v in dev.values() for e in v]
        first = dev[min(dev)]
        breakdown = {"device_ops": [list(x) for x in tr.top_ops(all_ops)],
                     "idle_gaps": [list(x) for x in
                                   tr.idle_gaps(first, host, w0, w1)]}
        self.note(f"trace: window {window_s:.3f} s, busy per device "
                  f"{ {k: round(v, 4) for k, v in busy.items()} }, "
                  f"{len(all_ops)} device ops")
        return {"metrics": metrics, "breakdown": breakdown,
                "busy_s": sum(busy.values()) / len(busy),
                "window_s": window_s}


class TraceContext:
    """What a per-layer reader gets: the window's device events per device,
    the benchmark's host spans, the window's and each device's busy
    seconds, the peaks, the configuration and mix, and the load
    generator's work counts."""

    def __init__(self, run: CellRun, dev, host, window_s, busy):
        self.device = dev
        self.host = host
        self.window_s = window_s
        self.busy_s = busy
        self.peaks = run.peaks
        self.config = run.config
        self.mix = run.mix
        self.inputs = run.inputs
        self.n_devices = len(dev)
