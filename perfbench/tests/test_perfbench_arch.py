"""Architectures found by the ``model_type`` a configuration states: the
Llama module gives the program's configuration as the harness always did,
an unknown type fails where the cell is loaded, and a second architecture
runs through the harness with new files only."""
import dataclasses
import filecmp
import json
import shutil
import time
from pathlib import Path

import numpy as np
import pytest

from perfbench import harness
from perfbench.tests.conftest import (TINY_ENGINE, TINY_LIMITS, TINY_MIX,
                                      TINY_TRAINER)

# the program's ModelConfig for each configuration file, as the harness
# built it before architectures had modules of their own
WANT = {
    "deepseek7b-pp3": dict(
        name="deepseek7b-pp3", arch_type="dense", n_layers=10, d_model=4096,
        n_heads=32, n_kv_heads=32, d_ff=11008, vocab=102400, head_dim=128,
        rope_theta=10000.0, rms_eps=1e-06, tie_embeddings=False,
        dtype="bfloat16"),
    "phi3medium-pp4": dict(
        name="phi3medium-pp4", arch_type="dense", n_layers=10, d_model=5120,
        n_heads=40, n_kv_heads=10, d_ff=17920, vocab=32064, head_dim=128,
        rope_theta=10000.0, rms_eps=1e-05, tie_embeddings=False,
        dtype="bfloat16"),
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_model_config_as_before(name):
    from repro.models.configs import ModelConfig
    spec = json.loads((harness.HERE / "configs" / f"{name}.json")
                      .read_text())
    got = harness.architecture(spec["model_type"]).model_config(spec)
    want = ModelConfig(**WANT[name])
    for f in dataclasses.fields(ModelConfig):
        assert getattr(got, f.name) == getattr(want, f.name), f.name


@pytest.fixture
def copy_root(tmp_path):
    """A copy of BENCHMARK.json and perfbench/, to add files to."""
    shutil.copy(harness.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.HERE, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    return tmp_path


def add_cell(root: Path, cell: str, config: dict, traffic: dict,
             setup: dict):
    """New files for one cell, and its entries in the copy's manifest."""
    pb = root / "perfbench"
    (pb / "configs" / f"{config['name']}.json").write_text(json.dumps(config))
    (pb / "traffic" / f"{cell}.json").write_text(json.dumps(traffic))
    (pb / "cells" / f"{cell}.json").write_text(json.dumps(setup))
    man = json.loads((root / "BENCHMARK.json").read_text())
    man["configs"].append(dict(
        name=config["name"], source="https://example.org/config.json",
        file=f"perfbench/configs/{config['name']}.json", reduced=[],
        why="a test architecture"))
    man["workloads"].append(dict(name=cell, config=config["name"],
                                 traffic=cell, chips=1, why="a test cell"))
    (root / "BENCHMARK.json").write_text(json.dumps(man))


def test_unknown_model_type_fails_in_load_cell(copy_root):
    spec = json.loads((harness.HERE / "configs" / "deepseek7b-pp3.json")
                      .read_text())
    mix = json.loads((harness.HERE / "traffic" / "chat-saturate.json")
                     .read_text())
    setup = json.loads((harness.HERE / "cells" /
                        "deepseek7b.chat-saturate.json").read_text())
    add_cell(copy_root, "unknown.chat",
             dict(spec, name="unknown-arch", model_type="no_such_arch"),
             mix, setup)
    with pytest.raises(FileNotFoundError,
                       match="perfbench/arch/no_such_arch.py"):
        harness.load_cell("unknown.chat", root=copy_root)


# A Llama block whose configuration file names its sizes otherwise, as some
# published configurations do: the harness can reach them only through this
# module.  ``USED`` records which of its entry points the harness called.
KEYS = dict(hidden_size="d_model", num_attention_heads="n_heads",
            num_key_value_heads="n_kv_heads", intermediate_size="d_ff",
            num_hidden_layers="n_layers")
RENAMED = f'''
from perfbench.arch import llama

NORMS = llama.NORMS
KEYS = {KEYS!r}
USED = set()


def _llama(spec):
    return dict(spec, **{{k: spec[v] for k, v in KEYS.items()}})


def model_config(spec):
    USED.add("model_config")
    return llama.model_config(_llama(spec))


def Reference(spec, *, fp8=False):
    USED.add("Reference")
    return llama.Reference(_llama(spec), fp8=fp8)


def step_flops(spec, *args):
    USED.add("step_flops")
    return llama.step_flops(_llama(spec), *args)
'''


def test_second_architecture_with_new_files_only(copy_root):
    pb = copy_root / "perfbench"
    (pb / "arch" / "renamed_llama.py").write_text(RENAMED)
    spec = json.loads((harness.HERE / "configs" / "deepseek7b-pp3.json")
                      .read_text())
    for key in KEYS:
        del spec[key]
    targets = ["wq", "wv", "wo", "wd"]
    spec.update(name="renamed-tiny", model_type="renamed_llama", d_model=256,
                n_heads=8, n_kv_heads=4, d_ff=512, n_layers=2, vocab_size=512,
                lora=dict(spec["lora"], targets=targets))
    mix = json.loads((harness.HERE / "traffic" / "chat-coserve.json")
                     .read_text())
    mix.update(TINY_MIX)
    mix["trainer"].update(TINY_TRAINER)
    setup = dict(rate_rps=12.0, sample_requests=4, engine=TINY_ENGINE,
                 limits=TINY_LIMITS)
    add_cell(copy_root, "renamed.chat-tiny", spec, mix, setup)
    # what the copy had is unchanged: only files were added
    for old in harness.HERE.rglob("*.py"):
        assert filecmp.cmp(old, pb / old.relative_to(harness.HERE),
                           shallow=False), old

    cell = harness.load_cell("renamed.chat-tiny", root=copy_root)
    assert Path(cell.arch.__file__).parent == pb / "arch"
    seed = 2 ** 32 + 5
    built = harness.build(cell, seed)
    assert (np.asarray(built.model.base["final_norm"]) == 1).all()
    win = harness.serve(cell, built, seed, 2.0, cell.setup["rate_rps"],
                        False, time.perf_counter(), harness.compile_seconds(),
                        log=lambda *_: None)
    stepped = [x for x in win.run.ticks if x.kind]
    assert stepped and all(x.flops > 0 for x in stepped)
    assert set(win.training["grad"]) == {f"{t}.{f}" for t in targets
                                         for f in "ab"}
    got = harness.compare(cell, built.make_params, built.make_adapters, seed,
                          win.picked, win.training,
                          built.names.index(harness.TRAINED))
    assert all(got[k] <= v for k, v in TINY_LIMITS.items()), got
    assert cell.arch.USED == {"model_config", "Reference", "step_flops"}
