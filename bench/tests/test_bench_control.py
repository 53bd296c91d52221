"""The control of the check, at a size a test run can hold: put in the
program's place in the harness's own comparison (`run.compare`), the
reference computed with float8 weights makes `correct` false on the
tokens the engine served, while the engine itself (bf16) makes it
true. On the chip the same verdicts come from `bench/control.py` at
the cells' own sizes (PERF.md gives them)."""

import dataclasses
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import control  # noqa: E402
import run  # noqa: E402
import spec  # noqa: E402


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    run.init_jax(1, False, str(tmp_path_factory.mktemp("cache")))
    base = spec.load_cell("stablelm-3b.chat-poisson")
    cfg = dict(base.config, num_hidden_layers=2, hidden_size=64,
               num_attention_heads=4, num_key_value_heads=4, head_dim=16,
               intermediate_size=128, vocab_size=1024)
    mix = dict(base.traffic,
               prompt={"median": 128, "sigma": 0.6, "min": 16},
               output={"median": 96, "sigma": 0.5, "min": 8})
    mix["cuts"] = {k: dict(v, tokens=v["tokens"] // 4)
                   for k, v in mix["cuts"].items()}
    return dataclasses.replace(base, config=cfg, traffic=mix,
                               cell=dict(base.cell, rate_per_s=10.0,
                                         check=dict(base.cell["check"],
                                                    tokens=400)))


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 5])
def test_control_fails_the_limit_the_program_passes(cell, seed):
    limit = cell.cell["check"]["gap_limit"]
    r = control.readings(cell, seed, 1.5)
    assert r["tokens"] >= cell.cell["check"]["tokens"]
    assert r["gap"] <= limit < r["control_gap"]
    assert r["correct"] is True and r["control_correct"] is False
