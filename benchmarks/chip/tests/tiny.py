"""Tiny cells for the CPU tests: the program's smoke widths (``reduced()``),
short requests and small batches, driven by the same harness as the chip
runs with the chip check skipped."""
from __future__ import annotations

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
REPO = os.path.dirname(os.path.dirname(CHIP))
for p in (os.path.join(REPO, "src"), CHIP):
    if p not in sys.path:
        sys.path.insert(0, p)

# Limits at this size, set from CPU readings at seed 11 (program / fp8
# control / half batch): served_gap 0.0169 / 0.302 (a token altered: 5.58);
# loss_gap 1.26e-4 / 1.62e-3 / 1.51e-2, grad_gap 5.5e-4 / 5.7e-3 / 1.6e-2,
# update_gap 2.7e-4 / 1.6e-3 / 0.22.
# The four-chip cell's (1, 2, 2) 1F1B program at seed 11 on four CPU
# devices reads 2.12e-4 / 7.1e-4 / 1.9e-4 (fp8 control 1.0e-3 / 6.7e-3 /
# 4.9e-4, half batch 4.6e-3 / 1.6e-2 / 0.19), above its chip-set loss_gap
# limit at this width, so it is held to the same tiny limits.
TINY_LIMITS = {"serve": {"served_gap": 0.08},
               "train": {"loss_gap": 4e-4, "grad_gap": 2e-3,
                         "update_gap": 8e-4}}
TINY_LIMITS["train8"] = TINY_LIMITS["train"]
REAL_CELLS = {"serve": "granite-8b.serve16.chat",
              "train": "granite-8b.train2.seq2k",
              "train8": "granite-8b.train8.pp2tp2"}
TINY = {"hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 1,
        "head_dim": 64, "intermediate_size": 512, "vocab_size": 1024,
        "num_hidden_layers": 2, "program_size": "reduced"}
# the four-chip cell's (1, 2, 2) plan at smoke widths: TP=2 splits the kv
# heads, which the program's own smoke granite-8b has one of
# (``register_tp_arch``); two layers a stage
TINY_PP = dict(TINY, num_key_value_heads=2, num_hidden_layers=4,
               program_arch="granite-8b-tp2", program_size="full")


def register_tp_arch():
    """granite-8b at the program's smoke widths with 2 kv heads, in the
    program's registry under ``TINY_PP["program_arch"]``."""
    from repro.configs import registry
    from repro.configs.base import reduced

    cfg = reduced(registry.get_config("granite-8b"),
                  name=TINY_PP["program_arch"], n_kv_heads=2)
    registry.ARCHITECTURES[cfg.name] = cfg


def _load(path):
    with open(path) as f:
        return json.load(f)


def make_root(tmp, kind: str, real: bool = False):
    """A benchmark root under ``tmp`` holding one tiny cell of ``kind``
    (``serve`` or ``train``); returns (root, bench dict, cell name). With
    ``real`` the cell is ``REAL_CELLS[kind]`` of the repository's own
    ``BENCHMARK.json``, its metric lists included, and the tiny files
    stand under its configuration's and mix's names."""
    root = str(tmp)
    os.makedirs(os.path.join(root, "configs"), exist_ok=True)
    os.makedirs(os.path.join(root, "traffic"), exist_ok=True)
    shutil.copytree(os.path.join(CHIP, "metrics"),
                    os.path.join(root, "metrics"), dirs_exist_ok=True)
    if kind == "serve":
        c = _load(os.path.join(CHIP, "configs", "granite-8b.serve16.json"))
        c.update(TINY)
        c["serve"].update(slots=4, pool_headroom=0.5, prefill_chunk=32,
                          use_kernel=False)
        mix = _load(os.path.join(CHIP, "traffic", "chat.json"))
        mix.update(rate_rps=8.0, ramp_s=0.5, tail_s=5.0, drain_s=5.0)
        mix["prompt"].update(median=24, min=4, max=80)
        mix["output"].update(median=8, min=2, max=24)
        mix["check"].update(min_requests=2, max_requests=8, min_tokens=120)
    elif kind == "train":
        c = _load(os.path.join(CHIP, "configs", "granite-8b.train2.json"))
        c.update(TINY)
        c["train"]["fused_backward"] = False
        mix = _load(os.path.join(CHIP, "traffic", "seq2k.json"))
        mix.update(global_batch=4, seq_len=64, distinct_batches=4)
    else:
        register_tp_arch()
        c = _load(os.path.join(CHIP, "configs", "granite-8b.train8.json"))
        c.update(TINY_PP)
        mix = _load(os.path.join(CHIP, "traffic", "seq2k.b8.json"))
        mix.update(seq_len=64, distinct_batches=4)
    if real:
        bench = _load(os.path.join(REPO, "BENCHMARK.json"))
        cell = REAL_CELLS[kind]
        w = {w["name"]: w for w in bench["workloads"]}[cell]
        c["name"], mix_name = w["config"], w["traffic"]
    else:
        cell, c["name"], mix_name = "tiny." + kind, "tiny-" + kind, "tiny-" + kind
        bench = {"configs": [{"name": c["name"]}],
                 "workloads": [{"name": cell, "config": c["name"],
                                "traffic": mix_name,
                                "chips": 4 if kind == "train8" else 1}],
                 "end_to_end": [], "per_layer": []}
    c["limits"] = TINY_LIMITS[kind]
    with open(os.path.join(root, "configs", c["name"] + ".json"), "w") as f:
        json.dump(c, f)
    with open(os.path.join(root, "traffic", mix_name + ".json"), "w") as f:
        json.dump(mix, f)
    return root, bench, cell


def run_tiny(tmp, kind: str, seed: int = 7, seconds: float = 2.0,
             control: bool = False, real: bool = False):
    """Drive one tiny cell on the CPU; returns (driver result, CellRun)."""
    import importlib

    import jax

    from chipbench.common import CellRun
    from peaks import PEAKS

    root, bench, cell = make_root(tmp, kind, real)
    run = CellRun(bench, cell, seed, seconds, False, control=control,
                  root=root)
    run.use_devices(jax.devices()[: run.cell["chips"]], PEAKS["TPU v5 lite"])
    driver = importlib.import_module("chipbench." + run.mix["driver"])
    return driver.run_cell(run), run
