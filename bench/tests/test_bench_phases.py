"""The engine's phases on the profiler's clock (`phases.py`): the split
of device idle time by engine span on a synthetic trace with known
answers, the clock's alignment on a profiler trace of the CPU, the
reduction of the recorded trace pinned to its values, and the
`admit_sync_ms` reader on a synthetic run."""

import glob
import gzip
import json
import lzma
import os
import sys
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data")
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402
import phases  # noqa: E402
import spec  # noqa: E402
from test_bench_trace import _ev, _synthetic  # noqa: E402


def _with_engine_spans():
    """`_synthetic()` with the engine's annotations inside the chunk:

    engine.drain [1,2] (engine.sync [1,2]), engine.admit [2,8]
    (engine.alloc [2,6] (engine.sync [5,6])), engine.decode_steps [8,11]
    idle  [0,3] [5,8] [28,30]
    """
    pd, scopes = _synthetic()
    host = pd.planes[0].lines[0]
    host.events += [
        _ev("engine.drain", 1, 2), _ev("engine.sync", 1, 2),
        _ev("engine.admit", 2, 8), _ev("engine.alloc", 2, 6),
        _ev("engine.sync", 5, 6), _ev("engine.decode_steps", 8, 11)]
    return pd, scopes


def test_innermost_labels_each_part_with_its_path():
    evs = [(0, 10, "a"), (2, 5, "b"), (3, 4, "c"), (6, 8, "d"), (12, 13, "e")]
    assert phases.innermost(evs) == [
        (0, 2, "a"), (2, 3, "a > b"), (3, 4, "a > b > c"), (4, 5, "a > b"),
        (5, 6, "a"), (6, 8, "a > d"), (8, 10, "a"), (12, 13, "e")]


def test_engine_idle_on_synthetic_trace():
    pd, _ = _with_engine_spans()
    r = phases.engine_idle(pd)
    # [0,1] harness, [1,2] drain's sync, [2,3] alloc; [5,6] alloc's
    # sync, [6,8] admit; [28,30] harness
    assert r["engine_idle_s"] == pytest.approx({
        "-": 0.003,
        "engine.drain > engine.sync": 0.001,
        "engine.admit > engine.alloc": 0.001,
        "engine.admit > engine.alloc > engine.sync": 0.001,
        "engine.admit": 0.002,
    })
    assert r["idle_s"] == pytest.approx(0.008)
    assert r["window_s"] == pytest.approx(0.030)
    assert phases.engine_idle_share(r) == pytest.approx(100 * 5 / 30)


def test_engine_spans_move_no_key_of_the_reduction_but_the_gap_labels():
    pd, scopes = _synthetic()
    before = devtrace.reduce(pd, scopes)
    pd, scopes = _with_engine_spans()
    after = devtrace.reduce(pd, scopes)
    for r in (before, after):
        del r["breakdown"]["idle_gaps"]
    assert after == before


def test_align_recovers_the_offset_and_its_spread():
    offset = -5_425_042_217  # the profiler counts from its own origin
    jitter = [0, 3_000, -2_000, 1_000]  # ns
    records, events = [], []
    for i, j in enumerate(jitter):
        t0, t1 = 0.5 + i * 0.01, 0.505 + i * 0.01
        records.append({"phase": "engine.sync", "t0": t0, "t1": t1})
        events.append((round(t0 * 1e9) + offset + j,
                       round(t1 * 1e9) + offset + j, "engine.sync"))
    records.append({"phase": "decode", "t0": 0.0, "t1": 1.0})
    r = phases.align(records, events)
    assert r["matched"] == 4
    assert r["offset_ns"] == pytest.approx(offset + 500, abs=1)
    assert r["max_us"] == pytest.approx(2.5, abs=1e-3)
    assert 0 < r["spread_us"] <= 5.0
    assert phases.align(records[-1:], events) is None


def _serve(eng, rid0):
    from repro.serve.engine import Request

    rng = np.random.default_rng(3)
    for i in range(8):
        n = int(rng.integers(1, 14))
        prompt = rng.integers(0, eng.cfg.vocab_size, size=n).astype(np.int32)
        eng.submit(Request(rid0 + i, prompt, int(rng.integers(1, 8))))
    eng.run_to_completion(chunk=4)


def test_journal_and_cpu_profiler_trace_line_up(tmp_path):
    """Every `engine.*` record of a traced stretch has its annotation in
    the profiler's trace, and after the offset the two agree within
    100 us."""
    from jax.profiler import ProfileData

    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve.jit_engine import JitServeEngine

    cfg = get_config("stablelm-3b").reduced()
    eng = JitServeEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(0)), num_pages=8,
        page_tokens=4, max_batch=4, max_lane_pages=8, max_out=16,
        dtype=jnp.float32)
    _serve(eng, 0)  # compiles outside the trace
    n0 = len(eng.spans)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(eng, 100)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[-1]
    events = phases.engine_events(ProfileData.from_file(path))
    journal = eng.spans[n0:]
    names = sorted(r["phase"] for r in journal
                   if r["phase"].startswith("engine."))
    assert sorted(n for _, _, n in events) == names
    clock = phases.align(journal, events)
    assert clock["matched"] == len(names)
    assert clock["spread_us"] < 100


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    with lzma.open(os.path.join(DATA, "stablelm-3b.chat.xplane.pb.xz")) as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    with gzip.open(os.path.join(DATA, "stablelm-3b.chat.scopes.json.gz")) as f:
        scopes = json.load(f)
    return pd, scopes


def test_recorded_trace_reduces_to_its_pinned_values(recorded):
    """Every key of the reduction of the trace recorded on the chip, to
    the last bit: the per-layer metrics read these."""
    assert devtrace.reduce(*recorded) == {
        "busy_s": 0.264027032,
        "window_s": 0.272255544,
        "program_s": {
            "convert_element_type": 9.717e-06,
            "admit_pages": 0.000212561,
            "serve_prefill": 0.018784724000000003,
            "dynamic_slice": 0.00046317700000000005,
            "prefill_insert": 0.044841848000000004,
            "engine_run": 0.199735392,
            "reduce_trajectory": 1.0168e-05,
            "merge": 2.5363e-05},
        "scope_s": {
            "telemetry": 0.0001787989999999999,
            "paged_decode": 0.1719413099999965,
            "nbbs_alloc": 0.0003459410000000001,
            "retire_free": 0.0007021269999999989},
        "kernel_s": 0.03195952000000002,
        "breakdown": {
            "device_ops": [
                ["engine_run:paged_decode:dynamic-slice_bitcast_fusion.5",
                 0.05231847699999999],
                ["engine_run:paged_decode:paged_attention.5",
                 0.03195952000000002],
                ["engine_run:paged_decode:dynamic-slice_bitcast_fusion.4",
                 0.023033137000000016],
                ["engine_run:paged_decode:fusion.671", 0.012392160999999992],
                ["engine_run:paged_decode:fusion.672", 0.012365691000000002],
                ["engine_run:paged_decode:bitcast_add_fusion.4",
                 0.012359885999999999],
                ["prefill_insert:-:copy.10", 0.011094348],
                ["prefill_insert:-:copy.8", 0.011063464],
                ["prefill_insert:-:copy.11", 0.0055650230000000005],
                ["engine_run:-:copy.44", 0.005564551]],
            "idle_gaps": [
                ["bench.chunk > np.asarray(jax.Array)", 0.004063608999999986],
                ["bench.chunk > DevicePut", 0.002532415],
                ["bench.sync > np.asarray(jax.Array)", 0.001604965],
                ["bench.chunk > PjitFunction(merge)", 2.3381999999999994e-05],
                ["bench.chunk > PjitFunction(admit_pages)",
                 4.119000000000001e-06],
                ["bench.chunk > PjitFunction(serve_prefill)",
                 1.7000000000000003e-08],
                ["bench.chunk > PjitFunction(reduce_trajectory)",
                 3.0000000000000004e-09],
                ["bench.chunk > engine.decode_steps", 2e-09]]},
    }


def test_recorded_trace_engine_idle_splits_the_idle_time(recorded):
    pd, scopes = recorded
    r = phases.engine_idle(pd)
    red = devtrace.reduce(pd, scopes)
    assert r["window_s"] == red["window_s"]
    assert r["idle_s"] == pytest.approx(red["window_s"] - red["busy_s"])
    # the recording's program annotated only its decode dispatches
    assert set(r["engine_idle_s"]) == {"-", "engine.decode_steps"}


def _run_view(engine_spans, admitted=(2,)):
    trace = {"span": {"step0": 0, "step1": 8, "chunks0": 0,
                      "chunks1": len(admitted), "engine_spans": engine_spans},
             "kernel_s": 0.0}
    win = NS(chunks=[{"admitted": n} for n in admitted], reqs=[])
    return devtrace.RunView(trace, win, {}, {})


def _span(phase, t0, t1, parent=-1, **kw):
    return {"phase": phase, "t0": t0, "t1": t1, "step0": 0, "step1": 0,
            "parent": parent, **kw}


# one chunk's journal: a drain, an admission of two requests (the
# free-lane read 2 ms, the reads of the two allocations 2 and 1 ms),
# the decode dispatch, and an admission that admitted nothing
JOURNAL = [
    _span("engine.drain", 0.000, 0.004),
    _span("engine.sync", 0.000, 0.003, 0),
    {"phase": "drain", "t0": 0.000, "t1": 0.0039, "step0": 0, "step1": 0,
     "drained": 1},
    _span("engine.admit", 0.004, 0.020),
    _span("engine.sync", 0.004, 0.006, 3),
    _span("engine.alloc", 0.006, 0.009, 3, rid=7),
    _span("engine.sync", 0.007, 0.009, 5),
    _span("engine.prefill", 0.009, 0.012, 3, rid=7),
    _span("engine.insert", 0.012, 0.013, 3, rid=7),
    _span("engine.alloc", 0.013, 0.015, 3, rid=8),
    _span("engine.sync", 0.014, 0.015, 9),
    _span("engine.prefill", 0.015, 0.018, 3, rid=8),
    _span("engine.insert", 0.018, 0.0195, 3, rid=8),
    {"phase": "admit", "t0": 0.004, "t1": 0.0198, "step0": 0, "step1": 0,
     "admitted": 2},
    _span("engine.decode_steps", 0.020, 0.021),
    {"phase": "decode", "t0": 0.020, "t1": 0.0209, "step0": 0, "step1": 8,
     "n": 8, "fused": 1},
    _span("engine.admit", 0.021, 0.024),
    _span("engine.sync", 0.021, 0.024, 16),
]


def test_admit_sync_ms_reads_the_syncs_inside_admissions():
    read = spec.load_reader("admit_sync_ms")
    assert read(_run_view(JOURNAL)) == pytest.approx((2 + 2 + 1) / 2)
    # the parent program journals no engine spans: nothing to read
    legacy = [s for s in JOURNAL if not s["phase"].startswith("engine.")]
    assert read(_run_view(legacy)) is None
    no_admission = [s for s in JOURNAL if s["phase"] != "admit"]
    assert read(_run_view(no_admission, admitted=(0,))) is None


def test_admit_host_ms_reads_only_the_admit_records():
    read = spec.load_reader("admit_host_ms")
    assert read(_run_view(JOURNAL)) == pytest.approx(15.8 / 2)


@pytest.fixture(scope="module")
def recorded_engine():
    """0.87 s of a trace recorded on one TPU v5e serving stablelm-3b
    with the engine's spans (a traced run of `stablelm-3b.chat-poisson`
    whose trace was kept, cut to the stretch around one admission), the
    scope map of its compiled `engine_run`, and the journal's records
    inside the same stretch."""
    from jax.profiler import ProfileData

    stem = os.path.join(DATA, "stablelm-3b.chat.engine")
    with lzma.open(f"{stem}.xplane.pb.xz") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    with gzip.open(f"{stem}.scopes.json.gz") as f:
        scopes = json.load(f)
    with gzip.open(f"{stem}.spans.json.gz") as f:
        journal = json.load(f)
    return pd, scopes, journal


def test_recorded_engine_trace_splits_idle_by_engine_phase(recorded_engine):
    pd, scopes, _ = recorded_engine
    red = devtrace.reduce(pd, scopes)
    r = phases.engine_idle(pd)
    assert r["window_s"] == red["window_s"]
    assert r["idle_s"] == pytest.approx(red["window_s"] - red["busy_s"])
    device_idle_share = 100 * (1 - red["busy_s"] / red["window_s"])
    assert 0 < phases.engine_idle_share(r) <= device_idle_share
    assert {"-", "engine.drain > engine.sync", "engine.decode_steps",
            "engine.admit > engine.alloc > engine.sync",
            "engine.admit > engine.prefill"} <= set(r["engine_idle_s"])
    # the kernel is found by the name the program gives it
    assert red["kernel_s"] > 0


def test_recorded_engine_trace_lines_up_with_the_journal(recorded_engine):
    pd, _, journal = recorded_engine
    events = phases.engine_events(pd)
    records = [r for r in journal if r["phase"].startswith("engine.")]
    assert len(events) == len(records)
    clock = phases.align(journal, events)
    assert clock["matched"] == len(records)
    assert clock["spread_us"] < 100 and clock["max_us"] < 100
