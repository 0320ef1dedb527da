"""Each configuration names its plain reference, and that reference says
which keys of the file the program's config is held to: the repository's
configurations resolve to ``dense_decoder`` and its eleven keys; a stub
reference that a tiny configuration names is imported and its own keys are
checked; a missing one is an error that names it."""
import dataclasses
import json
import os

import pytest

import tiny

from chipbench.common import CellRun  # noqa: E402

ELEVEN = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "num_hidden_layers": "n_layers", "mlp_gated": "mlp_gated",
          "norm": "norm", "rope_theta": "rope_theta",
          "tie_word_embeddings": "tie_embeddings"}


def _bench():
    with open(os.path.join(tiny.REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _program_config(run):
    from repro.configs import launch_config

    return launch_config(run.config["program_arch"], full=True,
                         layers=run.config["num_hidden_layers"])


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_repository_configs_use_dense_decoder(cell):
    run = CellRun(_bench(), cell, 1, 1.0, False)
    assert run.reference.__name__ == "references.dense_decoder"
    assert dict(run.reference.ARCH_KEYS) == ELEVEN
    cfg = _program_config(run)
    run.check_arch(cfg)
    for key, field in ELEVEN.items():
        with pytest.raises(ValueError, match=key):
            run.check_arch(dataclasses.replace(
                cfg, **{field: _departed(getattr(cfg, field))}))


def _departed(value):
    if isinstance(value, bool):
        return not value
    if isinstance(value, str):
        return value + "-other"
    return value + 1


def _stub_root(tmp_path, monkeypatch, reference):
    root, bench, cell = tiny.make_root(tmp_path / "chip", "train")
    path = os.path.join(root, "configs", bench["workloads"][0]["config"] + ".json")
    with open(path) as f:
        c = json.load(f)
    c["reference"] = reference
    with open(path, "w") as f:
        json.dump(c, f)
    monkeypatch.syspath_prepend(str(tmp_path / "stubs"))
    return root, bench, cell


def test_stub_reference_is_imported_and_its_keys_checked(tmp_path, monkeypatch):
    (tmp_path / "stubs" / "references").mkdir(parents=True)
    (tmp_path / "stubs" / "references" / "stub_two_keys.py").write_text(
        'ARCH_KEYS = (("hidden_size", "d_model"), ("vocab_size", "vocab_size"))\n')
    root, bench, cell = _stub_root(tmp_path, monkeypatch, "stub_two_keys")
    run = CellRun(bench, cell, 1, 1.0, False, root=root)
    assert run.reference.__name__ == "references.stub_two_keys"
    cfg = run.program_config()
    # a key the stub does not read may depart; one it reads may not
    run.check_arch(dataclasses.replace(cfg, d_ff=cfg.d_ff + 1))
    with pytest.raises(ValueError, match="vocab_size"):
        run.check_arch(dataclasses.replace(cfg, vocab_size=cfg.vocab_size * 2))


def test_missing_reference_is_named(tmp_path, monkeypatch):
    root, bench, cell = _stub_root(tmp_path, monkeypatch, "no_such_reference")
    with pytest.raises(ValueError, match="no_such_reference"):
        CellRun(bench, cell, 1, 1.0, False, root=root)
