"""The trace reduction: on a synthetic trace with known answers, and on
a short trace recorded on one TPU v5e serving stablelm-3b
(`data/`, with the scope map of the compiled `engine_run` it ran)."""

import gzip
import json
import lzma
import os
import sys
from types import SimpleNamespace as NS

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(BENCH, "tests", "data")
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402

MS = 1_000_000  # ns


def _ev(name, s, e):
    return NS(name=name, start_ns=s * MS, end_ns=e * MS)


def _line(name, events):
    return NS(name=name, events=events)


def _synthetic():
    """Two chunks of engine_run and one admission, on one chip.

    host  bench.submit [0,1]  bench.chunk [1,12]  bench.sync [12,30]
          (inside the chunk: PjitFunction(admit_pages) [2,7])
    device  admit_pages [3,5]; engine_run [8,28] holding a while
          [8,27] whose body runs fusion.1 (paged_decode) [8,18] and
          paged_attention.3 (paged_decode) [18,22] and fusion.9
          (nbbs_alloc) [22,24]; copy.4 (no scope) [27,28]
    idle  [0,3] [5,8] [28,30]
    """
    host = NS(name="/host:CPU", lines=[_line("python3", [
        _ev("bench.submit", 0, 1), _ev("bench.chunk", 1, 12),
        _ev("PjitFunction(admit_pages)", 2, 7), _ev("bench.sync", 12, 30)])])
    dev = NS(name="/device:TPU:0", lines=[
        _line("XLA Modules", [_ev("jit_admit_pages(1)", 3, 5),
                              _ev("jit_engine_run(2)", 8, 28)]),
        _line("XLA Ops", [
            _ev("%fusion.7 = s32[] fusion()", 3, 5),
            _ev("%while.1 = (s32[]) while()", 8, 27),
            _ev("%fusion.1 = bf16[] fusion()", 8, 18),
            _ev("%paged_attention.3 = bf16[] custom-call()", 18, 22),
            _ev("%fusion.9 = s32[] fusion()", 22, 24),
            _ev("%copy.4 = bf16[] copy()", 27, 28)]),
        _line("Async XLA Ops", [_ev("%copy-start.1 = ()", 24, 26)]),
    ])
    scopes = {"while.1": "", "fusion.1": "paged_decode",
              "paged_attention.3": "paged_decode", "fusion.9": "nbbs_alloc",
              "copy.4": "", "fusion.7": ""}
    return NS(planes=[host, dev]), scopes


def test_synthetic_busy_programs_scopes_kernel():
    pd, scopes = _synthetic()
    r = devtrace.reduce(pd, scopes)
    assert r["window_s"] == pytest.approx(0.030)
    # busy: [3,5] [8,28] (the async copy lies inside the while)
    assert r["busy_s"] == pytest.approx(0.022)
    assert r["program_s"] == pytest.approx({"admit_pages": 0.002,
                                            "engine_run": 0.020})
    # the while's own time is 19 - (10 + 4 + 2) = 3 ms, outside the scopes
    assert r["scope_s"] == pytest.approx({"paged_decode": 0.014,
                                          "nbbs_alloc": 0.002})
    assert r["kernel_s"] == pytest.approx(0.004)
    ops = dict((k, v) for k, v in r["breakdown"]["device_ops"])
    assert ops["engine_run:-:while.1"] == pytest.approx(0.003)
    assert ops["engine_run:paged_decode:fusion.1"] == pytest.approx(0.010)


def test_synthetic_gaps_are_attributed_to_the_host():
    pd, scopes = _synthetic()
    gaps = dict((k, v) for k, v in
                devtrace.reduce(pd, scopes)["breakdown"]["idle_gaps"])
    # [0,3] (middle 1.5), [5,8] (6.5) and [28,30] (29)
    assert gaps == pytest.approx({
        "bench.chunk": 0.003,
        "bench.chunk > PjitFunction(admit_pages)": 0.003,
        "bench.sync": 0.002,
    })
    assert sum(gaps.values()) == pytest.approx(0.030 - 0.022)


def test_union_merges_overlaps():
    assert devtrace._union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]


def test_hlo_scopes_reads_op_name_metadata():
    text = "\n".join([
        '  %fusion.3 = f32[2]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(engine_run)/while/body/paged_decode/dot_general" stack_frame_id=1}',
        '  ROOT %copy.1 = f32[2]{0} copy(%x), metadata={op_name="jit(engine_run)/while/body/retire_free/select_n"}',
        '  %param.0 = f32[2]{0} parameter(0)',
        '  %add.2 = f32[2]{0} add(%a, %b), metadata={op_name="jit(engine_run)/while"}',
    ])
    assert devtrace.hlo_scopes(text) == {
        "fusion.3": "paged_decode", "copy.1": "retire_free", "add.2": ""}


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    with lzma.open(os.path.join(DATA, "stablelm-3b.chat.xplane.pb.xz")) as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    with gzip.open(os.path.join(DATA, "stablelm-3b.chat.scopes.json.gz")) as f:
        scopes = json.load(f)
    return devtrace.reduce(pd, scopes)


def test_recorded_trace_busy_and_idle(recorded):
    assert 0 < recorded["busy_s"] <= recorded["window_s"]
    idle = sum(v for _, v in recorded["breakdown"]["idle_gaps"])
    # the ten longest idle kinds cover at most the idle time
    assert idle <= recorded["window_s"] - recorded["busy_s"] + 1e-9


def test_recorded_trace_scopes_and_kernel(recorded):
    prog = recorded["program_s"]
    scopes = recorded["scope_s"]
    assert set(scopes) == set(devtrace.SCOPES)
    # every scope's self time lies inside engine_run's device time, and
    # the kernel runs inside the model step's scope
    assert 0 < sum(scopes.values()) <= prog["engine_run"]
    assert 0 < recorded["kernel_s"] <= scopes["paged_decode"]
    assert {"admit_pages", "serve_prefill", "prefill_insert"} <= set(prog)
    assert sum(prog.values()) <= recorded["window_s"]
    names = [k for k, _ in recorded["breakdown"]["device_ops"]]
    assert any(devtrace.KERNEL in n for n in names)
