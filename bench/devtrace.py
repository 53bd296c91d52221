"""Profiler trace of part of the window, and its reduction.

`Tracer` starts JAX's profiler at the first chunk boundary after a
third of the window and stops it at the first boundary after
`min(3 s, a third of the window)` more, so the traced span holds whole
chunks. After the window it reduces the trace (`reduce`):

  span      from the start of the first `bench.*` host annotation in
            the trace to the end of the last one
  busy      the union of the device's op intervals (XLA Ops and Async
            XLA Ops lines of each `/device:TPU:n` plane) in the span,
            averaged over the chips
  programs  device time of each jitted program (XLA Modules line)
  scopes    self time of each op of `engine_run` (its time minus that
            of the ops nested in it, such as a layer scan's body),
            attributed to the engine's named scopes (`nbbs_alloc`,
            `paged_decode`, `retire_free`, `telemetry`) through the
            `op_name` metadata of the compiled program's HLO
  kernel    device time of the ops whose name starts with the
            paged-attention kernel's custom-call name
  gaps      each idle stretch of the device, attributed to the host
            annotation that covered its middle: the outermost
            `bench.*` phase and the innermost event inside it
"""

from __future__ import annotations

import bisect
import glob
import re
import shutil
import tempfile
from collections import defaultdict

import jax

KERNEL = "paged_attention"  # custom-call name of the Pallas kernel
SCOPES = ("nbbs_alloc", "paged_decode", "retire_free", "telemetry")
_HLO_LINE = re.compile(
    r'^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?metadata=\{[^}]*op_name="([^"]*)"')


def hlo_scopes(hlo_text: str) -> dict:
    """Instruction name -> the named scope of the engine step it belongs
    to ("" outside them), from a compiled program's HLO text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            parts = m.group(2).split("/")
            out[m.group(1)] = next((p for p in parts if p in SCOPES), "")
    return out


class Tracer:
    def __init__(self, seconds: float):
        self.start_at = seconds / 3
        self.length = min(3.0, seconds / 3)
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.on = False
        self.span = None

    def at_boundary(self, t: float, eng, win) -> None:
        if self.span is None and t >= self.start_at:
            self.span = {"t0": t, "step0": eng.stats["steps"] - win.steps0,
                         "spans0": len(eng.spans), "chunks0": len(win.chunks)}
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host spans from annotations only
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.on = True
        elif self.on and t >= self.span["t0"] + self.length:
            self.stop(t, eng, win)

    def stop(self, t: float, eng, win) -> None:
        if not self.on:
            return
        jax.profiler.stop_trace()
        self.on = False
        self.span.update(t1=t, step1=eng.stats["steps"] - win.steps0,
                         spans1=len(eng.spans), chunks1=len(win.chunks))

    def finish(self, eng, win) -> dict:
        """Reduce the trace (after the window) and remove its files."""
        if self.span is None or "t1" not in self.span:
            raise RuntimeError("the window closed before the trace ended")
        from jax.profiler import ProfileData

        from repro.launch.serve import CHUNK
        from repro.serve.jit_engine import engine_run

        scopes = hlo_scopes(engine_run.lower(
            eng.ecfg, eng.params, eng.state, CHUNK).compile().as_text())
        span = dict(self.span)
        span["engine_spans"] = list(eng.spans[span["spans0"]:span["spans1"]])
        try:
            path = glob.glob(f"{self.dir}/**/*.xplane.pb", recursive=True)[-1]
            result = reduce(ProfileData.from_file(path), scopes)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        result["span"] = span
        return result


def _union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def _host_path(host_events, starts, t):
    """Outermost bench.* phase and innermost event covering time t."""
    i = bisect.bisect_right(starts, t)
    cover = [ev for ev in host_events[max(0, i - 400):i] if ev[1] >= t]
    if not cover:
        return "host: none"
    outer = next((ev[2] for ev in cover if ev[2].startswith("bench.")), None)
    inner = max(cover, key=lambda ev: ev[0])[2]
    if outer is None or outer == inner:
        return inner
    return f"{outer} > {inner}"


def reduce(pd, scopes: dict) -> dict:
    """Busy and idle time, program, scope and kernel time, and the
    breakdown, from a parsed profile."""
    host_lines = [ln for pl in pd.planes if pl.name == "/host:CPU"
                  for ln in pl.lines]
    host_events = []
    for ln in host_lines:
        evs = [(e.start_ns, e.end_ns, e.name) for e in ln.events]
        if any(n.startswith("bench.") for _, _, n in evs):
            host_events += evs
    host_events.sort()
    bench = [(s, e) for s, e, n in host_events if n.startswith("bench.")]
    if not bench:
        raise RuntimeError("no bench.* annotation in the trace")
    w0, w1 = min(s for s, _ in bench), max(e for _, e in bench)
    starts = [ev[0] for ev in host_events]

    program_s = defaultdict(float)
    scope_s = defaultdict(float)
    op_self = defaultdict(float)
    kernel_s = 0.0
    busy_total = 0.0
    gaps = defaultdict(float)
    chips = [pl for pl in pd.planes if pl.name.startswith("/device:TPU:")]
    for pl in chips:
        lines = {ln.name: ln for ln in pl.lines}
        mods = []
        for e in lines["XLA Modules"].events:
            s, t = _clip(e.start_ns, e.end_ns, w0, w1)
            if t > s:
                prog = e.name.split("(")[0]
                prog = prog[4:] if prog.startswith("jit_") else prog
                mods.append((e.start_ns, e.end_ns, prog))
                program_s[prog] += (t - s) * 1e-9
        mods.sort()
        mod_starts = [m[0] for m in mods]
        ops = []
        for e in lines["XLA Ops"].events:
            s, t = _clip(e.start_ns, e.end_ns, w0, w1)
            if t > s:
                ops.append((s, t, e.name[1:].split(" ", 1)[0]))
        ops.sort(key=lambda o: (o[0], -o[1]))
        busy = [(s, t) for s, t, _ in ops]
        for e in lines.get("Async XLA Ops", lines["XLA Ops"]).events:
            s, t = _clip(e.start_ns, e.end_ns, w0, w1)
            if t > s:
                busy.append((s, t))
        merged = _union(busy)
        busy_total += sum(t - s for s, t in merged) * 1e-9
        # self time: an op's duration less that of the ops nested in it
        self_ns = [t - s for s, t, _ in ops]
        stack = []
        for i, (s, t, _) in enumerate(ops):
            while stack and ops[stack[-1]][1] <= s:
                stack.pop()
            if stack:
                self_ns[stack[-1]] -= t - s
            stack.append(i)
        for (s, t, name), own in zip(ops, self_ns):
            j = bisect.bisect_right(mod_starts, s) - 1
            prog = mods[j][2] if j >= 0 and mods[j][1] >= s else "?"
            scope = scopes.get(name, "") if prog == "engine_run" else ""
            if scope:
                scope_s[scope] += own * 1e-9
            if name.startswith(KERNEL):
                kernel_s += (t - s) * 1e-9
            op_self[f"{prog}:{scope or '-'}:{name}"] += own * 1e-9
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for s, t in zip(edges[::2], edges[1::2]):
            if t > s:
                gaps[_host_path(host_events, starts, (s + t) / 2)] += (t - s) * 1e-9
    n = max(len(chips), 1)
    top = sorted(op_self.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_total / n,
        "window_s": (w1 - w0) * 1e-9,
        "program_s": {k: v / n for k, v in program_s.items()},
        "scope_s": {k: v / n for k, v in scope_s.items()},
        "kernel_s": kernel_s / n,
        "breakdown": {"device_ops": [[k, v / n] for k, v in top],
                      "idle_gaps": [[k, v / n] for k, v in idle]},
    }


class RunView:
    """What a per-layer reader may look at: the trace's reduction, the
    window's timeline, the configuration file and the chip's peaks."""

    def __init__(self, trace: dict, win, cfg_file: dict, peaks: dict):
        self.trace = trace
        self.win = win
        self.cfg = cfg_file
        self.peaks = peaks
        span = trace["span"]
        self.steps = span["step1"] - span["step0"]
        self.admitted = sum(
            c["admitted"] for c in win.chunks[span["chunks0"]:span["chunks1"]])
        self.engine_spans = span["engine_spans"]
        self.kernel_s = trace["kernel_s"]

    def program_s(self, name: str) -> float:
        """Device seconds of one jitted program in the traced span."""
        return self.trace["program_s"].get(name, 0.0)

    def scope_s(self, name: str) -> float:
        """Device seconds under one named scope of `engine_run`."""
        return self.trace["scope_s"].get(name, 0.0)

    def attended_per_step(self):
        """For each traced decode step, the positions each decoding
        lane's token attends over (prompt + tokens before it + itself).
        Token i of a request is decoded i steps after its first, so the
        timeline gives every lane's live context exactly."""
        s0, s1 = self.trace["span"]["step0"], self.trace["span"]["step1"]
        steps = [[] for _ in range(s1 - s0)]
        for r in self.win.reqs:
            if r.steps_first < 0:
                continue
            first = r.steps_first - r.n_first  # step index of token 0
            n = len(r.out) if r.done else r.n_seen
            for i in range(max(0, s0 - first), min(n, s1 - first)):
                steps[first + i - s0].append(len(r.prompt) + i)
        return steps
