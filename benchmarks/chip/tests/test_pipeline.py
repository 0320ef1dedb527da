"""The four-chip training cell's path on four forced CPU devices, at the
program's smoke widths (``tiny.TINY_PP``), each case in a subprocess of its
own (the device count is fixed when JAX starts):

* the reference with its state spread over four devices equals the
  one-device reference to f32 round-off;
* a sound run of the (1, 2, 2) 1F1B program through the harness passes
  the cell's comparison (at this width held to ``tiny.TINY_LIMITS``) and
  reports the cell's end-to-end metrics;
* with the timed path broken underneath (half of the batch left out, the
  mean taken over the rest; the state returned unchanged; the TP
  all-reduce left out) the run comes out not correct."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

import tiny

SCRIPT = textwrap.dedent("""
    import json, os, sys, tempfile
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, {tests!r})
    import tiny
    import jax
    import numpy as np

    case, seed = sys.argv[1], 11
    tmp = tempfile.mkdtemp()
    if case == "sharded_reference":
        from chipbench import train, traffic
        from chipbench.common import CellRun
        from repro.models import init_params

        root, bench, cell = tiny.make_root(tmp, "train8")
        run = CellRun(bench, cell, seed, 1.0, False, root=root)
        cfg = run.program_config()
        p_struct = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
        host = traffic.train_batches(run.mix, seed, run.config["vocab_size"])[:2]
        args = (run.config, run.config["train"], p_struct, seed, host)
        out = {{"one": train.reference_steps(*args),
               "four": train.reference_steps(*args, devices=jax.devices()[:4])}}
        print("RESULT " + json.dumps(out))
        sys.exit(0)
    if case == "half_batch":
        from chipbench import train

        build = train.Trainer.build

        def half(self):
            build(self)
            h = self.mix["global_batch"] // 2
            self.batches = [jax.tree.map(lambda a: jax.device_put(
                np.concatenate([np.asarray(a)[:h]] * 2), a.sharding), b)
                for b in self.batches]

        train.Trainer.build = half
    elif case == "unchanged_state":
        from repro.train import loop

        finish = loop.finish_step

        def keep(state, grads, metrics, *a, **k):
            return state, finish(state, grads, metrics, *a, **k)[1]

        loop.finish_step = keep
    elif case == "no_tp_all_reduce":
        from repro.models import stack

        stack.tp_region_end = lambda x, axis_name="model": x
    res, run = tiny.run_tiny(tmp, "train8", seed=seed, real=True)
    print("RESULT " + json.dumps({{
        "correct": res["correct"], "checks": res["checks"],
        "end_to_end": sorted(run.end_to_end_metrics(res["metrics"]))}}))
""").format(tests=os.path.dirname(os.path.abspath(__file__)))


def _run(case):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(tiny.REPO, "src"))
    r = subprocess.run([sys.executable, "-c", SCRIPT, case],
                       capture_output=True, text=True, timeout=600, env=env,
                       cwd=tiny.REPO)
    assert r.returncode == 0, r.stderr[-4000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_sharded_reference_equals_one_device():
    """By the cell's own measures (``chipbench.train.compare``), the two
    differ by under a hundredth of the program's readings at this size."""
    from chipbench import train

    out = _run("sharded_reference")
    gaps = train.compare(out["four"], out["one"])
    assert all(v < 1e-5 for v in gaps.values()), gaps
    assert len(out["four"]["update_norms"]) == len(out["one"]["update_norms"])


def test_sound_pipeline_run_is_correct():
    """Named as the real cell, with the repository's metric lists: it
    reports its own end-to-end metrics."""
    out = _run("sound")
    assert out["correct"], out["checks"]
    assert all(v <= lim for _, v, lim in out["checks"]), out["checks"]
    assert out["end_to_end"] == ["setup_s", "train_peak_hbm_gib",
                                 "train_tokens_per_s"]


@pytest.mark.parametrize("case", ["half_batch", "unchanged_state",
                                  "no_tp_all_reduce"])
def test_broken_pipeline_run_is_not_correct(case):
    out = _run(case)
    assert not out["correct"], out["checks"]
