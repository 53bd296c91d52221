"""Telemetry-plane tests: the metric registry/slot orders (drift
guards), functional metric accumulation, the in-graph event ring,
the Chrome-trace exporter, the bench-artifact schema checker, and the
engine-level acceptance gates (telemetry on: still trace-once, still
transfer-free; stat totals exactly equal to the host oracle's)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import metrics as om
from repro.obs import ring as oring
from repro.obs import schema as osch
from repro.obs.trace_export import (
    SNAPSHOT_VERSION,
    chrome_trace,
    validate_snapshot,
    validate_trace,
)

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# schema: the single catalogue + positional slot orders
# ---------------------------------------------------------------------------


class TestSchema:
    def test_unregistered_name_raises_with_guidance(self):
        with pytest.raises(KeyError, match="obs/schema.py"):
            osch.spec("definitely_not_a_metric")

    def test_registry_entries_are_well_formed(self):
        for name, s in osch.REGISTRY.items():
            assert s.name == name
            assert s.kind in ("counter", "gauge", "histogram", "derived")
            if s.kind == "histogram":
                assert s.buckets, name
                assert list(s.buckets) == sorted(s.buckets), name
                assert s.n_slots == len(s.buckets) + 1  # overflow slot

    def test_kernel_slot_orders_are_locked(self):
        """The positional stat rows the Pallas kernels emit: width and
        order are load-bearing (producers pack, consumers unpack by
        these tuples).  Reordering or renaming must fail loudly here,
        not silently misattribute counters."""
        assert osch.WAVEFRONT_ALLOC_SLOTS == (
            "rounds", "merged_writes", "logical_rmws",
        )
        assert osch.WAVEFRONT_STEP_SLOTS == (
            "rounds", "merged_writes", "logical_rmws",
            "free_merged_writes", "free_logical_rmws", "freed",
        )
        assert osch.POOL_STEP_SLOTS == osch.WAVEFRONT_STEP_SLOTS + (
            "fastpath_hits", "magazine_hits", "magazine_spills",
            "magazine_refills",
        )
        for slots in (osch.WAVEFRONT_ALLOC_SLOTS,
                      osch.WAVEFRONT_STEP_SLOTS, osch.POOL_STEP_SLOTS):
            for name in slots:
                osch.spec(name)  # every slot is a registered metric

    def test_pack_unpack_roundtrip(self):
        slots = osch.POOL_STEP_SLOTS
        vals = {n: jnp.int32(10 + i) for i, n in enumerate(slots)}
        rowv = osch.pack_slots(slots, vals)
        assert rowv.shape == (len(slots),)
        back = osch.unpack_slots(slots, rowv)
        for i, n in enumerate(slots):
            assert int(back[n]) == 10 + i

    def test_unpack_rejects_wrong_width(self):
        with pytest.raises(ValueError):
            osch.unpack_slots(
                osch.WAVEFRONT_STEP_SLOTS, jnp.zeros(3, jnp.int32)
            )


# ---------------------------------------------------------------------------
# metrics: functional accumulation by registered kind
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_zeros_shapes(self):
        m = om.zeros(
            ("merged_writes", "free_pages_shard", "alloc_rounds_hist"),
            vector_lens={"free_pages_shard": 4},
        )
        assert m["merged_writes"].shape == ()
        assert m["free_pages_shard"].shape == (4,)
        n = osch.spec("alloc_rounds_hist").n_slots
        assert m["alloc_rounds_hist"].shape == (n,)

    def test_inc_counter_sums_gauge_overwrites(self):
        m = om.zeros(("merged_writes", "free_pages"))
        m = om.inc(m, "merged_writes", 3)
        m = om.inc(m, "merged_writes", 4)
        m = om.inc(m, "free_pages", 9)
        m = om.inc(m, "free_pages", 5)  # gauge: latest wins
        assert int(m["merged_writes"]) == 7
        assert int(m["free_pages"]) == 5

    def test_observe_buckets_and_overflow(self):
        # alloc_rounds_hist buckets (0, 1, 2, 4, 8, 16, 32): bucket i
        # counts value <= edge[i]; beyond the last edge -> overflow slot
        m = om.zeros(("alloc_rounds_hist",))
        for v in (0, 1, 3, 100):
            m = om.observe(m, "alloc_rounds_hist", v)
        counts = [int(x) for x in m["alloc_rounds_hist"]]
        edges = osch.spec("alloc_rounds_hist").buckets
        assert counts[edges.index(0)] == 1
        assert counts[edges.index(1)] == 1
        assert counts[edges.index(4)] == 1  # 3 -> first edge >= 3
        assert counts[-1] == 1              # 100 -> overflow
        assert sum(counts) == 4

    def test_observe_on_counter_raises(self):
        m = om.zeros(("merged_writes",))
        with pytest.raises(ValueError, match="not a histogram"):
            om.observe(m, "merged_writes", 1)

    def test_observe_many_masks_out_lanes(self):
        m = om.zeros(("probe_distance_hist",))
        vals = jnp.asarray([0, 1, 2, 7], jnp.int32)
        mask = jnp.asarray([True, False, True, True])
        m = om.observe_many(m, "probe_distance_hist", vals, mask)
        assert int(m["probe_distance_hist"].sum()) == 3  # masked lane dropped

    def test_merge_by_kind_and_drift_guard(self):
        a = om.zeros(("merged_writes", "free_pages"))
        a = om.inc(a, "merged_writes", 2)
        a = om.inc(a, "free_pages", 10)
        b = om.zeros(("merged_writes", "free_pages"))
        b = om.inc(b, "merged_writes", 5)
        b = om.inc(b, "free_pages", 6)
        out = om.merge(a, b)
        assert int(out["merged_writes"]) == 7  # counter: sum
        assert int(out["free_pages"]) == 6     # gauge: new wins
        with pytest.raises(ValueError, match="metric key drift"):
            om.merge(a, om.zeros(("merged_writes",)))

    def test_reduce_trajectory(self):
        traj = {
            "merged_writes": jnp.asarray([1, 2, 3], jnp.int32),
            "free_pages": jnp.asarray([9, 7, 5], jnp.int32),
            "alloc_rounds_hist": jnp.ones(
                (3, osch.spec("alloc_rounds_hist").n_slots), jnp.int32
            ),
        }
        tot = om.reduce_trajectory(traj)
        assert int(tot["merged_writes"]) == 6
        assert int(tot["free_pages"]) == 5  # gauge: final step
        assert int(tot["alloc_rounds_hist"].sum()) == 3 * osch.spec(
            "alloc_rounds_hist"
        ).n_slots

    def test_accumulates_inside_scan(self):
        """The point of the functional design: metrics are a scan carry."""
        def body(m, x):
            m = om.inc(m, "merged_writes", x)
            m = om.observe(m, "alloc_rounds_hist", x)
            return m, ()

        @jax.jit
        def run(xs):
            m0 = om.zeros(("merged_writes", "alloc_rounds_hist"))
            m, _ = jax.lax.scan(body, m0, xs)
            return m

        m = run(jnp.asarray([1, 2, 3, 4], jnp.int32))
        assert int(m["merged_writes"]) == 10
        assert int(m["alloc_rounds_hist"].sum()) == 4

    def test_to_host_and_host_counters(self):
        m = om.zeros(("merged_writes", "free_pages_shard"),
                     vector_lens={"free_pages_shard": 2})
        h = om.to_host(m)
        assert h == {"merged_writes": 0, "free_pages_shard": [0, 0]}
        hc = om.host_counters({"admitted": 3})
        assert int(hc["admitted"]) == 3
        with pytest.raises(KeyError):
            om.host_counters({"not_a_metric": 1})

    def test_hist_summary_labels(self):
        s = osch.spec("probe_distance_hist")
        lab = om.hist_summary(
            "probe_distance_hist", list(range(s.n_slots))
        )
        assert list(lab)[0] == f"<={s.buckets[0]}"
        assert list(lab)[-1] == "inf"
        assert lab["inf"] == s.n_slots - 1


# ---------------------------------------------------------------------------
# event ring
# ---------------------------------------------------------------------------


class TestEventRing:
    def test_push_drain_order(self):
        r = oring.make_ring(8)
        for i in range(3):
            r = oring.push(r, oring.event(oring.EV_STEP, step=i, rounds=i))
        evs = oring.drain(r)
        assert [e["step"] for e in evs] == [0, 1, 2]
        assert all(e["kind_name"] == "step" for e in evs)
        assert int(oring.dropped(r)) == 0

    def test_masked_push_is_a_noop(self):
        r = oring.make_ring(4)
        r = oring.push(r, oring.event(oring.EV_STEP, step=7), mask=False)
        assert int(r.count) == 0
        assert oring.drain(r) == []

    def test_overflow_drops_oldest(self):
        r = oring.make_ring(4)
        for i in range(6):
            r = oring.push(r, oring.event(oring.EV_STEP, step=i))
        assert int(oring.dropped(r)) == 2
        evs = oring.drain(r)
        assert [e["step"] for e in evs] == [2, 3, 4, 5]  # survivors, oldest first

    def test_push_many_exclusive_slots(self):
        r = oring.make_ring(8)
        rows = jnp.stack([
            oring.event(oring.EV_RETIRE, step=s) for s in range(4)
        ])
        mask = jnp.asarray([True, False, True, True])
        r = oring.push_many(r, rows, mask)
        evs = oring.drain(r)
        assert [e["step"] for e in evs] == [0, 2, 3]
        assert int(r.count) == 3

    def test_zero_capacity_counts_but_stores_nothing(self):
        r = oring.make_ring(0)
        r = oring.push(r, oring.event(oring.EV_STEP, step=1))
        rows = jnp.stack([oring.event(oring.EV_STEP, step=2)] * 2)
        r = oring.push_many(r, rows, jnp.asarray([True, True]))
        assert int(r.count) == 3
        assert oring.drain(r) == []
        assert int(oring.dropped(r)) == 3

    def test_event_rejects_unknown_field(self):
        with pytest.raises(KeyError, match="unknown event fields"):
            oring.event(oring.EV_STEP, bogus=1)

    def test_pushes_compile_inside_scan(self):
        def body(r, i):
            row = oring.event(oring.EV_STEP, step=i, lanes_won=i % 2)
            return oring.push(r, row, mask=i % 2 == 0), ()

        run = jax.jit(
            lambda r, xs: jax.lax.scan(body, r, xs)[0]
        )
        r2 = run(oring.make_ring(4), jnp.arange(6, dtype=jnp.int32))
        assert int(r2.count) == 3  # even steps only
        assert [e["step"] for e in oring.drain(r2)] == [0, 2, 4]


# ---------------------------------------------------------------------------
# trace exporter
# ---------------------------------------------------------------------------


def _synth_snapshot(n_steps=4):
    ring = oring.make_ring(16)
    for i in range(n_steps):
        ring = oring.push(ring, oring.event(
            oring.EV_STEP, step=i, lanes_won=1, rounds=2,
            free_pages=10 - i,
        ))
    return {
        "obs_schema": SNAPSHOT_VERSION,
        "source": "test",
        "config": {"num_pages": 16},
        "metrics": {"alloc_pages": n_steps, "free_pages": 10 - n_steps},
        "events": oring.drain(ring),
        "spans": [
            {"phase": "admit", "t0": 0.0, "t1": 0.01,
             "step0": 0, "step1": 0},
            {"phase": "decode", "t0": 0.01, "t1": 0.05,
             "step0": 0, "step1": n_steps},
        ],
    }


class TestTraceExport:
    def test_validate_snapshot_rejects_malformed(self):
        snap = _synth_snapshot()
        for key in ("obs_schema", "metrics", "events", "spans"):
            bad = {k: v for k, v in snap.items() if k != key}
            with pytest.raises(ValueError, match=key):
                validate_snapshot(bad)
        bad = dict(snap, metrics={"nope": 1})
        with pytest.raises(KeyError):
            validate_snapshot(bad)
        bad = dict(snap, spans=[{"phase": "x", "t0": 1.0, "t1": 0.5}])
        with pytest.raises(ValueError, match="ends before"):
            validate_snapshot(bad)

    def test_chrome_trace_renders_steps_and_counters(self):
        snap = _synth_snapshot(n_steps=4)
        trace = chrome_trace(snap)
        validate_trace(trace)
        evs = trace["traceEvents"]
        steps = [e for e in evs
                 if e["ph"] == "X" and e["name"].startswith("step ")]
        assert len(steps) == 4
        # the device-steps thread (tid 2; the host loop is tid 1) holds
        # the interpolated steps alone: no sub-spans of invented width
        assert [e for e in evs if e["ph"] == "X" and e["tid"] == 2
                and not e["name"].startswith("step ")] == []
        # a step's counts ride in its args
        assert steps[0]["args"]["lanes_won"] == 1
        counters = [e for e in evs if e["ph"] == "C"]
        assert {e["name"] for e in counters} == {"free_pages", "lanes_won"}
        # occupancy counter replays the ring's free_pages series
        fp = [e["args"]["free_pages"] for e in counters
              if e["name"] == "free_pages"]
        assert fp == [10, 9, 8, 7]

    def test_steps_outside_decode_windows_are_skipped(self):
        snap = _synth_snapshot(n_steps=4)
        snap["spans"] = [s for s in snap["spans"]
                         if s["phase"] != "decode"]
        trace = chrome_trace(snap)  # no wall-clock window: no step spans
        assert not [e for e in trace["traceEvents"]
                    if e["ph"] == "X" and e["name"].startswith("step ")]


# ---------------------------------------------------------------------------
# bench-artifact schema
# ---------------------------------------------------------------------------


class TestBenchSchema:
    def _checker(self):
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        sys.path.insert(0, os.path.join(root, "tools"))
        try:
            import check_bench_schema
        finally:
            sys.path.pop(0)
        return check_bench_schema

    def test_bench_record_rejects_unregistered_metric(self):
        from benchmarks.common import bench_envelope, bench_record

        with pytest.raises(KeyError, match="obs/schema.py"):
            bench_record(dims={}, metrics={"made_up": 1})
        env = bench_envelope(
            "t", {"w": 1},
            [bench_record(dims={"n_shards": 1},
                          metrics={"merged_writes": 3})],
            extra_summary={"anything": True},
        )
        assert env["schema_version"] == 1
        assert env["extra_summary"] == {"anything": True}

    def test_checker_accepts_envelope_rejects_drift(self, tmp_path):
        cbs = self._checker()
        good = {
            "schema_version": 1, "benchmark": "t", "config": {},
            "records": [{"dims": {"s": 1},
                         "metrics": {"merged_writes": 2.0,
                                     "free_pages_shard": [1, 2]}}],
        }
        p = tmp_path / "BENCH_T.json"
        p.write_text(json.dumps(good))
        assert cbs.check_file(str(p)) == []
        for mutate in (
            lambda d: d.update(schema_version=2),
            lambda d: d.pop("benchmark"),
            lambda d: d["records"][0]["metrics"].update(bogus=1),
            lambda d: d["records"][0]["metrics"].update(
                merged_writes="three"
            ),
            lambda d: d["records"][0]["dims"].update(t=[1, 2]),
            lambda d: d.update(records=[]),
        ):
            bad = json.loads(json.dumps(good))
            mutate(bad)
            p.write_text(json.dumps(bad))
            assert cbs.check_file(str(p)), mutate

    def test_checker_validates_snapshots_too(self, tmp_path):
        cbs = self._checker()
        p = tmp_path / "BENCH_SNAP.json"
        p.write_text(json.dumps(_synth_snapshot()))
        assert cbs.check_file(str(p)) == []
        bad = _synth_snapshot()
        bad["metrics"] = {"invented": 1}
        p.write_text(json.dumps(bad))
        assert cbs.check_file(str(p))


# ---------------------------------------------------------------------------
# engine-level acceptance gates (telemetry plane on)
# ---------------------------------------------------------------------------


def _engine(cfg, params, **kw):
    from repro.serve.jit_engine import JitServeEngine

    base = dict(
        num_pages=16, page_tokens=4, max_batch=4, max_lane_pages=8,
        max_out=16, dtype=jnp.float32,
    )
    base.update(kw)
    return JitServeEngine(cfg, params, **base)


class TestEngineTelemetry:
    @pytest.fixture(scope="class")
    def setup(self):
        from repro.configs import get_config
        from repro.models import init_params

        cfg = get_config("stablelm-3b").reduced()
        return cfg, init_params(cfg, KEY)

    def _submit_trace(self, eng, vocab, n=6, seed=3):
        from repro.serve.engine import Request

        rng = np.random.default_rng(seed)
        for i in range(n):
            p = rng.integers(
                0, vocab, size=int(rng.integers(1, 10))
            ).astype(np.int32)
            eng.submit(Request(i, p, int(rng.integers(1, 6))))

    def test_telemetry_on_is_trace_once_and_transfer_free(self, setup):
        """The acceptance gate with the full plane enabled: metrics
        dict + event ring + histograms add zero re-traces and zero
        host<->device transfers to the steady decode loop."""
        from repro.serve import jit_engine as je

        cfg, params = setup
        eng = _engine(cfg, params, ring_capacity=32)
        self._submit_trace(eng, cfg.vocab_size)
        eng._admit()
        eng.decode_steps(1)          # warmup: compile engine_step
        eng.decode_steps(2, fused=True)  # warmup: compile fused chunk
        traced = je.TRACE_COUNTS[eng.ecfg]
        with jax.transfer_guard("disallow"):
            eng.decode_steps(4)
            eng.decode_steps(2, fused=True)
        assert je.TRACE_COUNTS[eng.ecfg] == traced  # zero re-traces

    def test_ring_records_engine_steps(self, setup):
        cfg, params = setup
        eng = _engine(cfg, params, ring_capacity=64)
        self._submit_trace(eng, cfg.vocab_size)
        eng.run_to_completion(max_steps=40)
        evs = oring.drain(eng.state.ring)
        assert evs, "active steps must be recorded"
        steps = [e["step"] for e in evs]
        assert steps == sorted(steps)
        assert all(e["kind_name"] == "step" for e in evs)
        tot = eng.stat_totals()
        assert tot["ring_events"] == int(eng.state.ring.count)
        assert tot["ring_dropped"] == 0
        # ring free_pages gauge agrees with the drained occupancy tail
        assert evs[-1]["free_pages"] == tot["free_pages"]

    def test_ring_overflow_reports_drops(self, setup):
        cfg, params = setup
        eng = _engine(cfg, params, ring_capacity=4)
        self._submit_trace(eng, cfg.vocab_size)
        eng.run_to_completion(max_steps=40)
        tot = eng.stat_totals()
        assert tot["ring_events"] > 4
        assert tot["ring_dropped"] == tot["ring_events"] - 4
        assert len(oring.drain(eng.state.ring)) == 4

    def test_negative_ring_capacity_rejected(self, setup):
        cfg, params = setup
        with pytest.raises(ValueError, match="ring_capacity"):
            _engine(cfg, params, ring_capacity=-1)

    def test_stat_totals_exactly_match_host_oracle(self, setup):
        """Satellite #2: host admission counters and device step metrics
        route through ONE schema-checked merge, so the engine's totals
        equal the oracle's — including the slab fastpath split across
        admission (host) and in-step (device) traffic."""
        from repro.serve.engine import Request
        from repro.serve.oracle import HostOracleEngine

        cfg, params = setup
        kw = dict(num_pages=16, page_tokens=4, max_batch=4,
                  max_lane_pages=8, max_out=16)
        eng = _engine(cfg, params, fastpath=True, ring_capacity=16, **kw)
        orc = HostOracleEngine(fastpath=True, **kw)
        rng = np.random.default_rng(11)
        for i in range(8):
            p = rng.integers(
                0, cfg.vocab_size, size=int(rng.integers(1, 12))
            ).astype(np.int32)
            mn = int(rng.integers(1, 7))
            eng.submit(Request(i, p, mn))
            orc.submit(Request(i, p.copy(), mn))
        for _ in range(60):
            eng._drain(), eng._admit()
            orc._drain(), orc._admit()
            if not eng.running and not eng.waiting:
                break
            eng.decode_steps(2, fused=True)
            orc.decode_steps(2)
        etot, otot = eng.stat_totals(), orc.stat_totals()
        for key in otot:
            assert etot[key] == otot[key], (key, etot[key], otot[key])

    def test_snapshot_exports_a_valid_trace(self, setup):
        """Tentpole exit path: a real engine run's snapshot validates
        and renders as a loadable Chrome/Perfetto trace with per-step
        device spans inside the measured decode windows."""
        cfg, params = setup
        eng = _engine(cfg, params, ring_capacity=64)
        self._submit_trace(eng, cfg.vocab_size)
        eng.run_to_completion(max_steps=40)
        snap = eng.snapshot()
        validate_snapshot(snap)
        assert json.loads(json.dumps(snap)) == snap  # JSON-serializable
        assert snap["config"]["ring_capacity"] == 64
        trace = chrome_trace(snap)
        validate_trace(trace)
        names = [e["name"] for e in trace["traceEvents"]]
        assert any(n.startswith("step ") for n in names)
        assert "decode" in names  # host decode-chunk span
