"""A configuration names its plain reference (`spec.reference`): a
file without the key is judged by `bench/reference.py` as before, and
a configuration added as new files only (its configuration file, its
reference, a traffic mix and a cell, with their entries in
BENCHMARK.json) runs end to end through `run.main` and is judged by
its own reference."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import spec  # noqa: E402

TINY = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=4, head_dim=16, intermediate_size=128,
            vocab_size=256)


def _json(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["stablelm-3b", "minitron-4b"])
def test_a_file_without_reference_gets_the_plain_reference(name):
    cfg = _json(os.path.join(BENCH, "configs", f"{name}.json"))
    assert "reference" not in cfg
    mod = spec.reference(cfg)
    assert os.path.samefile(mod.__file__, os.path.join(BENCH, "reference.py"))
    assert spec.reference(cfg) is mod


def test_the_plain_reference_gives_the_same_gaps_to_the_bit():
    """Through `spec` and through the file loaded on its own, at a tiny
    size, the program's gaps and the control's agree to the bit."""
    cfg = dict(_json(os.path.join(BENCH, "configs", "stablelm-3b.json")),
               **TINY)
    own = importlib.util.spec_from_file_location(
        "plain_reference_alone", os.path.join(BENCH, "reference.py"))
    alone = importlib.util.module_from_spec(own)
    own.loader.exec_module(alone)
    rng = np.random.default_rng(3)
    seqs = [{"prompt": rng.integers(0, 256, n, dtype=np.int32),
             "served": rng.integers(0, 256, m, dtype=np.int32)}
            for n, m in ((40, 12), (23, 30))]
    for control in (False, True):
        a = spec.reference(cfg).logit_gaps(cfg, 2**31 + 7, seqs, (2, 96),
                                           control=control)
        b = alone.logit_gaps(cfg, 2**31 + 7, seqs, (2, 96), control=control)
        assert a == b
        assert a["tokens"] == 42 and len(a["gap_per_seq"]) == 2
        assert ("control_gap" in a) is control


def test_a_bad_reference_name_is_an_error():
    with pytest.raises(ValueError, match="reference"):
        spec.reference({"reference": "../reference"})


TOY_REFERENCE = '''

_plain_logit_gaps = logit_gaps


def logit_gaps(cfg, seed, seqs, shape, control=False):
    """The plain reference's gaps, signed by this file."""
    return dict(_plain_logit_gaps(cfg, seed, seqs, shape, control=control),
                judged_by=__file__)
'''


def test_a_configuration_of_new_files_only_runs_under_its_own_reference(
        tmp_path):
    """A copy of the benchmark gains a toy configuration as new files
    (no file of the copy is edited; BENCHMARK.json gains entries), and
    `run.main` serves its cell on the CPU and is judged by
    `references/toy.py`."""
    tree = tmp_path / "checkout"
    shutil.copytree(BENCH, tree / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "src"), tree / "src")
    before = {p: p.read_bytes() for p in (tree / "bench").rglob("*")
              if p.is_file()}

    b = tree / "bench"
    cfg = dict(_json(b / "configs" / "stablelm-3b.json"), **TINY,
               name="toy", reference="toy")
    mix = _json(b / "traffic" / "chat-poisson.json")
    mix.update(prompt={"median": 128, "sigma": 0.6, "min": 16},
               output={"median": 32, "sigma": 0.5, "min": 8})
    mix["cuts"] = {k: dict(v, tokens=v["tokens"] // 8)
                   for k, v in mix["cuts"].items()}
    cell = {"max_batch": 8, "rate_per_s": 20.0,
            "check": {"tokens": 60, "shape": [6, 2560], "gap_limit": 0.22}}
    new = {b / "configs" / "toy.json": json.dumps(cfg),
           b / "traffic" / "toy-chat.json": json.dumps(mix),
           b / "cells" / "toy.toy-chat.json": json.dumps(cell),
           b / "references" / "toy.py":
               (b / "reference.py").read_text() + TOY_REFERENCE}
    for path, text in new.items():
        assert not path.exists()
        path.parent.mkdir(exist_ok=True)
        path.write_text(text)
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"].append({
        "name": "toy", "source": "a toy", "file": "bench/configs/toy.json",
        "reduced": [], "why": "a toy"})
    bench["workloads"].append({
        "name": "toy.toy-chat", "config": "toy", "traffic": "toy-chat",
        "chips": 1, "why": "a toy"})
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))
    assert all(p.read_bytes() == data for p, data in before.items())

    code = ("import sys; sys.path.insert(0, 'bench'); import run; "
            "run.GRACE_CAP_S = 3.0; run.main(sys.argv[1:], require_tpu=False)")
    p = subprocess.run(
        [sys.executable, "-c", code, "--workload", "toy.toy-chat",
         "--seed", str(2**31 + 3), "--seconds", "1.5", "--trace", "0"],
        cwd=tree, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"] is True, p.stderr[-4000:]
    assert res["checks"]["served_tokens_short"]["value"] == 0
    signed = json.dumps(str(b / "references" / "toy.py"))
    assert f'"judged_by": {signed}' in p.stderr
