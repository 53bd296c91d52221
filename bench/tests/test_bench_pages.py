"""Page accounting asked of the engine (`bench/pages.py`): the
leaf-page engine's answers are a page for every `page_tokens` tokens
of prompt and output, and its own lane tables; an engine that answers
for itself is asked."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import pages  # noqa: E402
import traffic  # noqa: E402


@pytest.fixture(scope="module")
def eng():
    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve.jit_engine import JitServeEngine

    cfg = get_config("stablelm-3b").reduced()
    return JitServeEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(0)), num_pages=64,
        page_tokens=16, max_batch=4, max_lane_pages=32, max_out=16,
        dtype=jnp.float32)


def test_request_pages_counts_the_whole_output(eng):
    assert pages.request_pages(eng, 33, 15) == 3
    assert pages.request_pages(eng, 33, 16) == 4
    with open(os.path.join(BENCH, "traffic", "chat-poisson.json")) as f:
        lens = traffic.length_set(json.load(f))
    for p in lens["prompt"].tolist():
        for m in lens["output"].tolist():
            assert pages.request_pages(eng, p, m) == -(-(p + m) // 16)


def test_lane_pages_are_the_engine_state_tables(eng):
    held = pages.lane_pages(eng)
    assert held["page_shard"] is eng.state.page_shard
    assert held["page_off"] is eng.state.page_off
    assert held["n_pages"] is eng.state.n_pages


def test_an_engine_that_answers_is_asked(eng, monkeypatch):
    tables = {"page_shard": None, "page_off": None, "n_pages": None}
    monkeypatch.setattr(eng, "request_pages", lambda p, m: 64 + p + m,
                        raising=False)
    monkeypatch.setattr(eng, "lane_pages", lambda: tables, raising=False)
    assert pages.request_pages(eng, 3, 4) == 71
    assert pages.lane_pages(eng) is tables
