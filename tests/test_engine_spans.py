"""The host-phase journal of `JitServeEngine` (`spans`): the `admit` /
`decode` / `drain` records that the trace exporter and the benchmark
read, and the nested `engine.*` records that `_span` writes beside its
profiler annotations."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_params
from repro.serve.engine import Request
from repro.serve.jit_engine import JitServeEngine, SpanJournal

# what the engine journalled for `_serve` before it had `_span`: the
# records' fields but their times (an 8-page pool, so one admission
# finds it full and three lanes overflow)
LEGACY = [
    {"phase": "admit", "step0": 0, "step1": 0, "admitted": 3},
    {"phase": "decode", "step0": 0, "step1": 4, "n": 4, "fused": 1},
    {"phase": "drain", "step0": 4, "step1": 4, "drained": 2},
    {"phase": "admit", "step0": 4, "step1": 4, "admitted": 3},
    {"phase": "decode", "step0": 4, "step1": 8, "n": 4, "fused": 1},
    {"phase": "drain", "step0": 8, "step1": 8, "drained": 4},
    {"phase": "admit", "step0": 8, "step1": 8, "admitted": 2},
    {"phase": "decode", "step0": 8, "step1": 12, "n": 4, "fused": 1},
    {"phase": "decode", "step0": 12, "step1": 16, "n": 4, "fused": 1},
    {"phase": "drain", "step0": 16, "step1": 16, "drained": 2},
    {"phase": "admit", "step0": 16, "step1": 16, "admitted": 1},
    {"phase": "decode", "step0": 16, "step1": 17, "n": 1, "fused": 0},
    {"phase": "decode", "step0": 17, "step1": 18, "n": 1, "fused": 0},
    {"phase": "decode", "step0": 18, "step1": 19, "n": 1, "fused": 0},
    {"phase": "drain", "step0": 19, "step1": 19, "drained": 1},
]
# the `engine.*` span each legacy record shares its start with
HOST_OF = {"admit": "engine.admit", "decode": "engine.decode_steps",
           "drain": "engine.drain"}


def _engine():
    cfg = get_config("stablelm-3b").reduced()
    return cfg, JitServeEngine(
        cfg, init_params(cfg, jax.random.PRNGKey(0)), num_pages=8,
        page_tokens=4, max_batch=4, max_lane_pages=8, max_out=16,
        dtype=jnp.float32)


@pytest.fixture(scope="module")
def served():
    cfg, eng = _engine()
    rng = np.random.default_rng(3)
    for i in range(8):
        n = int(rng.integers(1, 14))
        prompt = rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
        eng.submit(Request(i, prompt, int(rng.integers(1, 8))))
    eng.run_to_completion(chunk=4)
    eng.submit(Request(8, np.arange(5, dtype=np.int32), 3))
    while eng.step():
        pass
    eng.run_to_completion()
    assert eng.stats["queued_full"] == 1 and eng.stats["overflow_retired"] == 3
    return eng


def test_legacy_records_are_unchanged(served):
    legacy = [s for s in served.spans if not s["phase"].startswith("engine.")]
    assert [{k: v for k, v in s.items() if k not in ("t0", "t1")}
            for s in legacy] == LEGACY
    for s in legacy:
        host = served.spans[max(i for i, h in enumerate(served.spans)
                                if h["phase"] == HOST_OF[s["phase"]]
                                and h["t0"] <= s["t0"])]
        assert host["t0"] == s["t0"] <= s["t1"] <= host["t1"]


def test_engine_spans_nest_and_name_their_request(served):
    spans = served.spans
    engine = [(i, s) for i, s in enumerate(spans)
              if s["phase"].startswith("engine.")]
    for i, s in engine:
        assert s["t0"] <= s["t1"] and s["step0"] <= s["step1"]
        if s["parent"] >= 0:
            up = spans[s["parent"]]
            assert s["parent"] < i
            assert up["t0"] <= s["t0"] and s["t1"] <= up["t1"]
    by_phase = {}
    for _, s in engine:
        parent = spans[s["parent"]]["phase"] if s["parent"] >= 0 else None
        by_phase.setdefault(s["phase"], set()).add(parent)
    assert by_phase["engine.admit"] == by_phase["engine.drain"] == {None}
    assert by_phase["engine.decode_steps"] == {None}
    assert by_phase["engine.clear"] == {"engine.drain"}
    for phase in ("engine.alloc", "engine.prefill", "engine.insert"):
        assert by_phase[phase] == {"engine.admit"}
    # each blocking read: the drain's and the admission's lane reads,
    # each allocation's verdict, `step`'s count of live lanes
    assert by_phase["engine.sync"] == {
        "engine.drain", "engine.admit", "engine.alloc", None}
    # every admitted request was allocated, prefilled and inserted once
    # under its own id; the one that found the pool full was allocated
    # again when it fitted
    rids = {p: [s["rid"] for _, s in engine if s["phase"] == p]
            for p in ("engine.alloc", "engine.prefill", "engine.insert")}
    assert rids["engine.prefill"] == rids["engine.insert"]
    assert sorted(rids["engine.insert"]) == list(range(9))
    assert len(rids["engine.alloc"]) == 9 + served.stats["queued_full"]


def test_span_writes_journal_record_and_annotation(served, tmp_path):
    import glob

    from jax.profiler import ProfileData

    n0 = len(served.spans)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with served._span("outer", rid=5):
            with served._span("sync"):
                pass
    finally:
        jax.profiler.stop_trace()
    outer, inner = served.spans[n0:]
    assert outer["phase"] == "engine.outer" and outer["rid"] == 5
    assert outer["parent"] == -1 and inner["parent"] == n0
    assert inner["phase"] == "engine.sync" and "rid" not in inner
    assert outer["t0"] <= inner["t0"] <= inner["t1"] <= outer["t1"]
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[-1]
    events = {e.name: (e.start_ns, e.end_ns)
              for pl in ProfileData.from_file(path).planes
              for ln in pl.lines for e in ln.events
              if e.name.startswith("engine.")}
    assert set(events) == {"engine.outer", "engine.sync"}
    (o0, o1), (i0, i1) = events["engine.outer"], events["engine.sync"]
    assert o0 <= i0 <= i1 <= o1


def test_span_journal_holds_the_newest_and_indexes_them_all():
    j = SpanJournal(cap=4)
    for i in range(10):
        j.append({"i": i})
    assert len(j) == 10 and j.dropped == 6
    assert [r["i"] for r in j] == [6, 7, 8, 9]
    assert j[6]["i"] == 6 and j[9]["i"] == 9 and j[-1]["i"] == 9
    assert [r["i"] for r in j[7:9]] == [7, 8]
    assert [r["i"] for r in j[3:8]] == [6, 7]  # the dropped part is gone
    assert [r["i"] for r in j[9:]] == [9]
    assert j[2:5] == [] and j[8:8] == []
    for i in (5, 10, -5):
        with pytest.raises(IndexError):
            j[i]


def test_long_run_keeps_the_journal_bounded():
    cfg, eng = _engine()
    eng.spans = SpanJournal(cap=32)
    rng = np.random.default_rng(5)
    for i in range(12):
        prompt = rng.integers(0, cfg.vocab_size, size=6).astype(np.int32)
        eng.submit(Request(i, prompt, 6))
    n0 = len(eng.spans)
    while eng.step():
        pass
    eng.run_to_completion()
    n1 = len(eng.spans)
    assert len(eng.completed) == 12
    assert n1 > 4 * 32 and len(list(eng.spans)) == 32
    assert eng.spans.dropped == n1 - 32
    # a slice by earlier counts, as the benchmark's tracer takes one,
    # reads the records written since, as far as they are held
    assert eng.spans[n1 - 10:n1] == list(eng.spans)[-10:]
    assert eng.spans[n0:n1] == list(eng.spans)
    # each held record's parent is held, or was dropped before it
    for i in range(eng.spans.dropped, n1):
        rec = eng.spans[i]
        if rec["phase"].startswith("engine.") and rec["parent"] >= 0:
            assert rec["parent"] < i
            if rec["parent"] >= eng.spans.dropped:
                up = eng.spans[rec["parent"]]
                assert up["t0"] <= rec["t0"] and rec["t1"] <= up["t1"]
    assert len(eng.snapshot()["spans"]) == 32
