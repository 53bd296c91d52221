"""The harness end to end on the CPU at a tiny size, with the chip
check skipped: a sound run is correct, and each fault the served path
can have, planted under the timed path, makes `correct` false. Also:
no TPU, no program beside the benchmark, and an unknown device kind
are errors."""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import run  # noqa: E402
import spec  # noqa: E402

CELL = "stablelm-3b.chat-poisson"
LOAD_CELL = spec.load_cell


def _tiny(name=CELL, **over):
    """The cell at a size the CPU runs in seconds: every width cut, the
    lengths cut to an eighth so that they finish inside the grace, the
    lanes, pool and check as the cell has them. `over` replaces entries
    of the configuration (`num_pages`)."""
    base = LOAD_CELL(name)
    cfg = dict(base.config, num_hidden_layers=2, hidden_size=64,
               num_attention_heads=4, num_key_value_heads=4, head_dim=16,
               intermediate_size=128, vocab_size=256)
    mix = dict(base.traffic,
               prompt={"median": 128, "sigma": 0.6, "min": 16},
               output={"median": 32, "sigma": 0.5, "min": 8})
    mix["cuts"] = {k: dict(v, tokens=v["tokens"] // 8)
                   for k, v in mix["cuts"].items()}
    cellf = dict(base.cell, rate_per_s=20.0,
                 check=dict(base.cell["check"], tokens=60))
    cfg.update(over)
    return dataclasses.replace(base, config=cfg, cell=cellf, traffic=mix)


@pytest.fixture
def tiny_run(monkeypatch, tmp_path):
    monkeypatch.setattr(run, "GRACE_CAP_S", 3.0)

    def go(seed=7, name=CELL, **over):
        monkeypatch.setattr(spec, "load_cell", lambda n: _tiny(n, **over))
        return run.main(["--workload", name, "--seed", str(seed),
                         "--seconds", "1.5", "--trace", "0"],
                        require_tpu=False, cache_dir=str(tmp_path / "cache"))
    return go


def _patch_engine_run(monkeypatch, wrap):
    """Break the fused decode step from the window on (warm-up runs the
    sound one, as a run's set-up would)."""
    import serve_loop
    from repro.serve import jit_engine

    real_step, real_serve = jit_engine.engine_run, serve_loop.serve

    def serve(*a, **kw):
        monkeypatch.setattr(
            jit_engine, "engine_run",
            lambda ecfg, params, state, n: wrap(real_step, ecfg, params, state, n))
        return real_serve(*a, **kw)

    monkeypatch.setattr(serve_loop, "serve", serve)


def test_sound_run_is_correct(tiny_run):
    res = tiny_run()
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"tokens_per_s", "tpot_p90_ms", "setup_s"}
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


def test_pool_that_binds_holds_requests_back_and_none_overflows(
        tiny_run, monkeypatch):
    """With a pool of under two of the longest requests for eight
    lanes, the harness hands a request over only while every live
    request's whole output fits: the pool fills and no lane runs out of
    pages."""
    import serve_loop

    held = []
    real_sync = serve_loop._sync

    def sync(eng, win, now, admitted):
        real_sync(eng, win, now, admitted)
        assert win.reserved <= win.pool_pages
        held.append(win.chunks[-1]["pages"])

    monkeypatch.setattr(serve_loop, "_sync", sync)
    # two or three requests at a time work off the window's queue
    monkeypatch.setattr(run, "GRACE_CAP_S", 30.0)
    cuts = _tiny().traffic["cuts"]
    longest = -(-(cuts["prompt_max"]["tokens"]
                  + cuts["output_max"]["tokens"]) // 16)
    pages = 1 << (2 * longest).bit_length() - 1  # a power of two
    assert longest <= pages < 2 * longest
    res = tiny_run(num_pages=pages)
    assert res["correct"] is True
    assert res["checks"]["overflowed"]["value"] == 0
    assert max(held) > pages // 2


def test_gate_reserves_a_page_for_every_16_tokens_of_prompt_and_output(
        tiny_run, monkeypatch):
    """With the pool binding, the gate asks the engine for each request's
    pages, and every answer, and so every hold and release, is
    ceil((prompt + max_new) / 16): the gate admits the requests that a
    fixed formula of a page per 16 tokens would, in the same chunks."""
    import pages
    import serve_loop

    asked = []
    real_ask, real_sync = pages.request_pages, serve_loop._sync

    def ask(eng, prompt_len, max_new):
        need = real_ask(eng, prompt_len, max_new)
        asked.append((need, -(-(prompt_len + max_new) // 16)))
        return need

    def sync(eng, win, now, admitted):
        real_sync(eng, win, now, admitted)
        live = [win.by_id[rid] for rid in win.holds]
        assert win.reserved == sum(-(-(len(r.prompt) + r.max_new) // 16)
                                   for r in live)
        assert all(r.n_seen < r.max_new for r in live)

    monkeypatch.setattr(pages, "request_pages", ask)
    monkeypatch.setattr(serve_loop, "_sync", sync)
    monkeypatch.setattr(run, "GRACE_CAP_S", 30.0)
    res = tiny_run(num_pages=64)
    assert res["correct"] is True
    assert asked and all(new == old for new, old in asked)


def _under_reported_pages(eng):
    """The engine's accessor under-reports one lane's pages: the lane
    that holds most loses its newest page from its table and count."""
    st = eng.state
    lane = jnp.argmax(st.n_pages)
    last = jnp.maximum(st.n_pages[lane] - 1, 0)
    holds = st.n_pages[lane] > 0
    shard = st.page_shard.at[lane, last].set(
        jnp.where(holds, -1, st.page_shard[lane, last]))
    return {"page_shard": shard, "page_off": st.page_off,
            "n_pages": st.n_pages.at[lane].add(-holds.astype(jnp.int32))}


def test_under_reported_lane_pages_are_unaccounted(tiny_run, monkeypatch):
    import serve_loop
    from repro.serve.jit_engine import JitServeEngine

    real_serve = serve_loop.serve

    def serve(*a, **kw):
        monkeypatch.setattr(JitServeEngine, "lane_pages",
                            _under_reported_pages, raising=False)
        return real_serve(*a, **kw)

    monkeypatch.setattr(serve_loop, "serve", serve)
    res = tiny_run()
    assert res["checks"]["pages_unaccounted"]["value"] >= 1
    assert res["correct"] is False


def _altered_token(real, ecfg, params, state, n):
    """A token altered where it is produced: every lane's newest."""
    state, traj = real(ecfg, params, state, n)
    pos = jnp.clip(state.n_out - 1, 0, ecfg.max_out - 1)
    lanes = jnp.arange(ecfg.max_batch)
    bad = (state.out_toks[lanes, pos] + 1) % ecfg.arch.vocab_size
    return state._replace(out_toks=state.out_toks.at[lanes, pos].set(bad)), traj


def _state_unchanged(real, ecfg, params, state, n):
    """A step that returns its state unchanged."""
    _, traj = real(ecfg, params, jax.tree.map(jnp.copy, state), n)
    return state, traj


def _half_batch(real, ecfg, params, state, n):
    """Half of the batch left out: lanes of the upper half never decode."""
    upper = jnp.arange(ecfg.max_batch) >= ecfg.max_batch // 2
    out, traj = real(ecfg, params,
                     state._replace(active=state.active & ~upper), n)
    return out._replace(active=out.active | (state.active & upper)), traj


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged, _half_batch],
                         ids=["altered_token", "state_unchanged", "half_batch"])
def test_fault_under_the_timed_path_is_not_correct(tiny_run, monkeypatch, fault):
    _patch_engine_run(monkeypatch, fault)
    res = tiny_run()
    assert res["correct"] is False
    assert any(c["value"] is None or c["value"] > c["limit"]
               for c in res["checks"].values())


def _bare_run(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _bare_run(ROOT, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_exits_nonzero_beside_no_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _bare_run(tmp_path, dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        spec.peaks("TPU v9 imaginary")


def test_every_cell_finds_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    for w in b["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.end_to_end and cell.per_layer
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        for m in cell.per_layer:
            assert callable(spec.load_reader(m["name"]))
            assert m["moves"] in {e["name"] for e in cell.end_to_end}
        # every request of the mix fits one lane, and the pool
        longest = -(-(cell.traffic["cuts"]["prompt_max"]["tokens"]
                      + cell.traffic["cuts"]["output_max"]["tokens"]) // 16)
        assert longest <= cell.traffic["max_lane_pages"]
        assert cell.traffic["max_lane_pages"] <= cell.config["num_pages"]
        assert cell.traffic["cuts"]["output_max"]["tokens"] <= cell.traffic["max_out"]


def test_sample_for_check_takes_the_longest_first():
    from traffic import Request

    reqs = []
    for i, n in enumerate([10, 40, 25, 8, 30]):
        r = Request(rid=i, due=0.0, prompt=np.zeros(20, np.int32), max_new=n)
        r.out, r.done = list(range(n)), True
        reqs.append(r)

    class W:
        pass

    w = W()
    w.reqs = reqs
    picked = run.sample_for_check(w, 3, {"tokens": 60, "shape": [3, 128]})
    assert picked[0].rid == 1 and len(picked) <= 3
    assert sum(len(r.out) for r in picked) >= 60 or len(picked) == 3
