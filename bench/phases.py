"""The engine's host phases on the profiler's clock.

`JitServeEngine` writes each host phase twice (`JitServeEngine._span`):
as a profiler annotation `engine.<name>`, which a trace shows beside
the device's ops, and as a record of its journal (`eng.spans`), timed
on `time.perf_counter()` from the engine's construction. This module
reads both:

  align        the offset between the journal's clock and the trace's,
               from the records and annotations of the same names, in
               order; its spread is the alignment error
  engine_idle  each idle stretch of the device, cut at the edges of the
               `engine.*` annotations and credited to the innermost one
               covering each part ("-" for the harness, outside them)

Run as a script it is `run.py` with a tracer that adds both to the
traced run's reduction and logs them on an earlier line:

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s> \\
        --trace 1
"""

from __future__ import annotations

import glob
import json
import statistics
import sys
from collections import defaultdict

import devtrace

PREFIX = "engine."


def engine_events(pd) -> list:
    """(start_ns, end_ns, name) of every `engine.*` annotation on the
    host, sorted by start."""
    return sorted((e.start_ns, e.end_ns, e.name)
                  for pl in pd.planes if pl.name == "/host:CPU"
                  for ln in pl.lines for e in ln.events
                  if e.name.startswith(PREFIX))


def align(records: list, events: list):
    """Offset (ns) to add to a journal time (s, times 1e9) to place it
    on the trace's clock: the median of the differences between each
    `engine.*` record's start and end and those of the annotation of
    the same name and rank. `spread_us` is the distance between the
    differences' quartiles, `max_us` the largest distance of one from
    the offset. None when nothing matches."""
    recs, evs = defaultdict(list), defaultdict(list)
    for r in records:
        if r["phase"].startswith(PREFIX) and r["t1"] is not None:
            recs[r["phase"]].append((r["t0"], r["t1"]))
    for s, e, name in events:
        evs[name].append((s, e))
    diffs = []
    for name, rs in recs.items():
        for (t0, t1), (s, e) in zip(sorted(rs), evs.get(name, [])):
            diffs += [s - t0 * 1e9, e - t1 * 1e9]
    if len(diffs) < 2:
        return None
    offset = statistics.median(diffs)
    q1, _, q3 = statistics.quantiles(diffs, n=4)
    return {"offset_ns": offset, "spread_us": (q3 - q1) * 1e-3,
            "max_us": max(abs(d - offset) for d in diffs) * 1e-3,
            "matched": len(diffs) // 2}


def innermost(events: list) -> list:
    """Sorted, disjoint (start, end, path) segments covering the union
    of nested events, each labelled with the path of names from the
    outermost event covering it to the innermost (" > " between)."""
    out, stack, t = [], [], None  # stack: (end, path), innermost last

    def close_to(limit):
        nonlocal t
        while stack and stack[-1][0] <= limit:
            end, path = stack.pop()
            if end > t:
                out.append((t, end, path))
                t = end

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close_to(s)
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        if stack:  # a child ends with its parent at the latest
            e, name = min(e, stack[-1][0]), f"{stack[-1][1]} > {name}"
        stack.append((e, name))
        t = s
    close_to(float("inf"))
    return out


def _credit(idle: list, segments: list, into: dict) -> None:
    """Add each idle stretch's overlap with each labelled segment to
    `into` (seconds), and the rest under "-"."""
    j = 0
    for a, b in idle:
        while j < len(segments) and segments[j][1] <= a:
            j += 1
        covered, k = 0, j
        while k < len(segments) and segments[k][0] < b:
            s, e, path = segments[k]
            part = min(b, e) - max(a, s)
            if part > 0:
                into[path] += part * 1e-9
                covered += part
            k += 1
        into["-"] += (b - a - covered) * 1e-9


def engine_idle(pd) -> dict:
    """Device idle time in the traced span (the span and the device's
    busy intervals as `devtrace.reduce` takes them), split by the
    innermost `engine.*` annotation on the host, averaged over the
    chips: {"engine_idle_s": {path: s}, "idle_s", "window_s"}.

    The span (first to last `bench.*` annotation) and the busy intervals
    (the union of "XLA Ops" and "Async XLA Ops", clipped to the span)
    repeat `devtrace.reduce`'s rules and must change with them, until
    `reduce` credits its own idle stretches (PERF.md §7)."""
    bench = [(e.start_ns, e.end_ns) for pl in pd.planes
             if pl.name == "/host:CPU" for ln in pl.lines
             for e in ln.events if e.name.startswith("bench.")]
    if not bench:
        raise RuntimeError("no bench.* annotation in the trace")
    w0, w1 = min(s for s, _ in bench), max(e for _, e in bench)
    segments = innermost(engine_events(pd))
    split = defaultdict(float)
    chips = [pl for pl in pd.planes if pl.name.startswith("/device:TPU:")]
    for pl in chips:
        lines = {ln.name: ln for ln in pl.lines}
        busy = []
        for name in ("XLA Ops", "Async XLA Ops"):
            for e in lines.get(name, lines["XLA Ops"]).events:
                s, t = devtrace._clip(e.start_ns, e.end_ns, w0, w1)
                if t > s:
                    busy.append((s, t))
        edges = [w0] + [x for iv in devtrace._union(busy) for x in iv] + [w1]
        _credit([(s, t) for s, t in zip(edges[::2], edges[1::2]) if t > s],
                segments, split)
    n = max(len(chips), 1)
    return {"engine_idle_s": {k: v / n for k, v in split.items()},
            "idle_s": sum(split.values()) / n,
            "window_s": (w1 - w0) * 1e-9}


def engine_idle_share(result: dict) -> float:
    """Idle time inside an engine span over the traced span, in %."""
    inside = sum(v for k, v in result["engine_idle_s"].items() if k != "-")
    return 100.0 * inside / result["window_s"]


class PhaseTracer(devtrace.Tracer):
    """`devtrace.Tracer` that also reads the engine's phases from the
    trace before its files go."""

    def finish(self, eng, win) -> dict:
        if self.span is None or "t1" not in self.span:
            return super().finish(eng, win)
        from jax.profiler import ProfileData

        path = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)[-1]
        pd = ProfileData.from_file(path)
        journal = eng.spans[self.span["spans0"]:self.span["spans1"]]
        phases = engine_idle(pd)
        phases["clock"] = align(journal, engine_events(pd))
        phases["engine_idle_share"] = engine_idle_share(phases)
        result = super().finish(eng, win)
        phases["device_idle_share"] = 100.0 * (
            1.0 - result["busy_s"] / result["window_s"])
        print("phases " + json.dumps(phases), file=sys.stderr, flush=True)
        result["phases"] = phases
        return result


def main(argv=None) -> dict:
    import run

    devtrace.Tracer = PhaseTracer
    return run.main(argv)


if __name__ == "__main__":
    main()
