"""On-chip serving benchmark: one cell of BENCHMARK.json per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the served engine (`repro.launch.serve.build_engine`: bf16
weights made on the device from the seed, JAX's persistent compile
cache in `.jax_cache/` at the checkout root) at the cell's
configuration, warms up every shape the cell's traffic uses, offers
the traffic for `--seconds`, serves on until every admitted request
has finished, and checks what was served against the configuration's
plain reference (`spec.reference`: `bench/reference.py`, or the
`bench/references/<name>.py` that the configuration file names). With
`--trace 1` a profiler trace of part of the window gives the per-layer
metrics instead of the end-to-end ones.

Earlier lines (standard error) give the set-up breakdown, compiles
inside the window, the generator's lateness, the engine's counters
and what the check compared. The last line of standard output is one
JSON object: correct, attempted, failed, metrics, device, (breakdown),
and last `checks`, each compared number beside its limit.

Exits non-zero, and prints no result, without a TPU, with fewer chips
than the cell asks for, or outside a checkout of the repository.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import defaultdict  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import spec  # noqa: E402
import timeline  # noqa: E402
import traffic  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
WARM_RID = 1 << 30  # request ids of the warm-up, apart from the traffic's
GRACE_CAP_S = 90.0  # longest wait after the close for admitted requests


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def fail(msg: str, code: int = 2) -> None:
    log(f"bench: {msg}")
    sys.exit(code)


class CompileLog:
    """Counts compiles, cache hits and their seconds by phase."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    HIT = "/jax/compilation_cache/cache_hits"
    LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self, jax):
        self.phase = "setup"
        self.n = defaultdict(int)
        self.s = defaultdict(float)
        self.names = defaultdict(int)  # what compiled inside the window
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        if event in (self.COMPILE, self.TRACE, self.LOAD):
            if self.phase == "window":
                self.names[kw.get("fun_name", "?")] += 1
            self.n[(self.phase, event)] += 1
            self.s[(self.phase, event)] += duration

    def _event(self, event, **kw):
        if event == self.HIT:
            self.n[(self.phase, event)] += 1

    def count(self, phase: str, event: str) -> int:
        return self.n[(phase, event)]

    def secs(self, phase: str, event: str) -> float:
        return self.s[(phase, event)]


def arch_config(cfg_file: dict):
    """The program's ArchConfig at the file's sizes."""
    import dataclasses

    from repro.configs import get_config

    base = get_config(cfg_file["registry"])
    return dataclasses.replace(
        base,
        n_layers=cfg_file["num_hidden_layers"],
        d_model=cfg_file["hidden_size"],
        n_heads=cfg_file["num_attention_heads"],
        n_kv_heads=cfg_file["num_key_value_heads"],
        head_dim=cfg_file["head_dim"],
        d_ff=cfg_file["intermediate_size"],
        vocab_size=cfg_file["vocab_size"],
        rope_theta=float(cfg_file["rope_theta"]),
        norm_eps=float(cfg_file["rms_norm_eps"]),
        tie_embeddings=bool(cfg_file["tie_word_embeddings"]),
    )


def warm_up(eng, mix: dict, chunk: int) -> None:
    """One request for each prefill bucket the mix's prompts can hit,
    served to completion: compiles (or loads) every program and eager
    op the window runs, at the window's shapes."""
    from repro.serve.engine import Request as EngineRequest

    lens = traffic.length_set(mix)["prompt"]
    by_bucket = {}
    for n in lens.tolist():
        by_bucket[1 << max(n - 1, 0).bit_length()] = n
    vocab = eng.cfg.vocab_size
    for i, n in enumerate(sorted(by_bucket.values())):
        prompt = (np.arange(n, dtype=np.int32) * 7919) % vocab
        eng.submit(EngineRequest(WARM_RID + i, prompt, max_new_tokens=chunk))
    eng.run_to_completion(chunk=chunk)
    import serve_loop

    serve_loop.lane_tables(eng)  # the eager ops of the check at the close


def sample_for_check(win, seed: int, check: dict) -> list:
    """Finished requests for the reference: the longest served one,
    then others in an order drawn from the seed, until `tokens` served
    tokens or the check's batch is full."""
    K, S = check["shape"]
    fits = [r for r in win.reqs
            if r.done and r.out and len(r.prompt) + len(r.out) - 1 <= S]
    if not fits:
        return []
    longest = max(fits, key=lambda r: (len(r.out), len(r.prompt)))
    rest = [r for r in fits if r is not longest]
    rng = np.random.default_rng(seed)
    picked, served = [longest], len(longest.out)
    for i in rng.permutation(len(rest)):
        if served >= check["tokens"] or len(picked) == K:
            break
        picked.append(rest[i])
        served += len(rest[i].out)
    return picked


def due_in_window(win, seconds: float) -> list:
    """Every request due inside the window, handed to the engine or
    not."""
    return [r for r in win.reqs if r.due < seconds]


def window_counts(eng, win, num_pages: int, seconds: float) -> dict:
    """The exact checks of a window, read while the engine still
    lives: requests due (or admitted) in the window that did not get
    their whole output, lanes cut short by an empty pool, requests
    refused, and the page bookkeeping."""
    due = due_in_window(win, seconds)
    return {
        "unfinished": sum(1 for r in due if not r.done),
        "truncated": sum(1 for r in due if r.done and len(r.out) != r.max_new),
        "overflowed": int(eng.stats["overflow_retired"]),
        "rejected": int(eng.stats["rejected"]),
        **bookkeeping(win, num_pages, eng.device_free_pages()),
    }


def reference_gaps(win, cfg_file: dict, seed: int, check: dict,
                   control: bool = False) -> dict:
    """The configuration's reference's logit gaps over the sample of
    finished requests (with `control`, the control's beside them)."""
    sample = sample_for_check(win, seed, check)
    if not sample:
        return {"requests": 0, "tokens": 0, "gap": math.inf,
                "control_gap": math.inf}
    gaps = spec.reference(cfg_file).logit_gaps(
        cfg_file, seed,
        [{"prompt": r.prompt, "served": np.asarray(r.out)} for r in sample],
        tuple(check["shape"]), control=control)
    return {"requests": len(sample), **gaps}


def compare(gap: float, tokens: int, check: dict, counts: dict) -> dict:
    """Every number the check compares, beside its limit: the widest
    logit gap of the served tokens (the program's, or the control's in
    its place), the served tokens it covered, and the exact counts."""
    return {
        "logit_gap": (gap, float(check["gap_limit"])),
        "served_tokens_short": (max(0, check["tokens"] - tokens), 0),
        **{k: (v, 0) for k, v in counts.items()},
    }


def is_correct(checks: dict) -> bool:
    return all(v <= lim for v, lim in checks.values())


def bookkeeping(win, num_pages: int, free_after: int) -> dict:
    """Distinct pages among live lanes at the close; conservation at
    the close; every page back after the final drain."""
    st = win.close_state
    live = st["page_shard"] >= 0
    gid = (st["page_shard"] * st["pages_per_shard"] + st["page_off"])[live]
    held = int(live.sum())
    return {
        "dup_pages": int(held - len(np.unique(gid))),
        "pages_unaccounted": int(abs(num_pages - st["free_pages"] - held)
                                 + abs(held - int(st["n_pages"].sum()))),
        "pages_leaked": int(num_pages - free_after),
    }


def init_jax(cell_chips: int, require_tpu: bool, cache_dir: str):
    """Import JAX with the compile cache in `cache_dir`, and check the
    devices. Returns (jax, compile log, first device)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    # libtpu logs to /tmp/tpu_logs unless told otherwise; keep its logs
    # under the run's TMPDIR, like every other file the run writes
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every program, the shim's eager ops too, so that only the
    # first run of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clog = CompileLog(jax)
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell_chips):
        fail(f"needs {cell_chips} TPU chip(s); JAX found "
             f"{len(devs)} {devs[0].platform} device(s)")
    return jax, clog, devs[0]


def build(cell, seed: int):
    """The served engine at the cell's configuration, weights from the
    seed, every shape of the cell's traffic warmed up. Returns
    (engine, arch, seconds for weights+engine, seconds for warm-up)."""
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import CHUNK, build_engine

    cfg_file, mix = cell.config, cell.traffic
    arch = arch_config(cfg_file)
    lanes = int(cell.cell["max_batch"])
    t0 = time.perf_counter()
    eng = build_engine(
        arch, seed=seed, dtype=jnp.dtype(cfg_file["torch_dtype"]),
        num_pages=cfg_file["num_pages"], page_tokens=cfg_file["page_tokens"],
        max_batch=lanes, max_lane_pages=mix["max_lane_pages"],
        max_out=mix["max_out"],
    )
    jax.block_until_ready((eng.params, eng.state))
    t1 = time.perf_counter()
    warm_up(eng, mix, CHUNK)
    return eng, arch, t1 - t0, time.perf_counter() - t1


class GcLog:
    """Pauses of Python's garbage collector, summed and the longest."""

    def __init__(self):
        self.total = 0.0
        self.longest = 0.0
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            d = time.perf_counter() - self._t
            self.total += d
            self.longest = max(self.longest, d)


def main(argv=None, require_tpu: bool = True, cache_dir: str = CACHE_DIR) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        fail("--seed must be >= 0")
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"no program beside the benchmark: {ROOT}/src/repro is missing")
    cell = spec.load_cell(args.workload)
    jax, clog, dev = init_jax(cell.chips, require_tpu, cache_dir)
    peaks = spec.peaks(dev.device_kind) if args.trace else None
    t_jax = time.perf_counter()

    from repro.launch.serve import CHUNK

    cfg_file, mix, cellf = cell.config, cell.traffic, cell.cell
    eng, arch, t_weights, t_warm = build(cell, args.seed)
    t_built = time.perf_counter()
    reqs = traffic.requests_for(mix, args.seed, args.seconds, arch.vocab_size,
                                cellf["rate_per_s"])
    import serve_loop

    tracer = None
    if args.trace:
        import devtrace

        tracer = devtrace.Tracer(args.seconds)
    # what set-up left behind is never collected again: a full
    # collection over it inside the window would stall the loop
    gc.collect()
    gc.freeze()
    gcl = GcLog()
    t_open = time.perf_counter()
    setup_s = t_open - T_START
    log(f"setup_s {setup_s!r}: python+jax init {t_jax - T_START!r}, "
        f"weights+engine {t_weights!r}, warm-up {t_warm!r} "
        f"(compiles {clog.count('setup', CompileLog.COMPILE)} in "
        f"{clog.secs('setup', CompileLog.COMPILE)!r} s, cache hits "
        f"{clog.count('setup', CompileLog.HIT)} loaded in "
        f"{clog.secs('setup', CompileLog.LOAD)!r} s, traces "
        f"{clog.count('setup', CompileLog.TRACE)} in "
        f"{clog.secs('setup', CompileLog.TRACE)!r} s), "
        f"traffic+gc {t_open - t_built!r}")

    clog.phase = "window"
    win = serve_loop.serve(
        eng, reqs, args.seconds, CHUNK, tracer=tracer,
        on_close=lambda: setattr(clog, "phase", "after"),
        grace_cap_s=GRACE_CAP_S)
    log(f"inside the window: compiles {clog.count('window', CompileLog.COMPILE)}"
        f", cache hits {clog.count('window', CompileLog.HIT)}, traces "
        f"{clog.count('window', CompileLog.TRACE)} (all should be 0)"
        + (f": {dict(clog.names)}" if clog.names else ""))
    log(f"gc inside the window and after: {gcl.total!r} s, longest pause "
        f"{gcl.longest!r} s")
    stalls = sorted(zip(
        [b["t_end"] - a["t_end"] for a, b in zip(win.chunks, win.chunks[1:])],
        [b["t_end"] for b in win.chunks[1:]]), reverse=True)[:3]
    log(f"longest gaps between chunk ends (s, at s): {stalls}")
    late = serve_loop.lateness(win)
    if len(late):
        log(f"handed to the engine after due, s (the pool gate's holds "
            f"included): median {float(np.median(late))!r}, max "
            f"{float(late.max())!r} over {len(late)} submissions")
    log(f"grace after the close: {win.grace_s!r} s; engine stats "
        f"{json.dumps(eng.stats)}")
    held = [c["pages"] for c in win.chunks if c["t_end"] <= args.seconds]
    if held:
        log(f"pool pages held at chunk ends in the window: mean "
            f"{float(np.mean(held))!r}, max {max(held)} of "
            f"{cfg_file['num_pages']}")
    totals = eng.stat_totals()
    log("engine counters: " + json.dumps({
        k: totals[k] for k in ("alloc_pages", "freed_pages", "alloc_rounds",
                               "overflow_lanes", "free_pages") if k in totals
    }, default=int))

    counts = window_counts(eng, win, cfg_file["num_pages"], args.seconds)
    mem = dev.memory_stats() or {}
    peak = int(mem.get("peak_bytes_in_use", 0))
    trace_result = tracer.finish(eng, win) if tracer is not None else None
    # free the program's state before the reference runs on the chip
    del eng, tracer
    gc.collect()

    t_ref = time.perf_counter()
    gaps = reference_gaps(win, cfg_file, args.seed, cellf["check"])
    log(f"reference over {gaps['requests']} requests, {gaps['tokens']} "
        f"served tokens, in {time.perf_counter() - t_ref!r} s: "
        f"{json.dumps(gaps)}")
    checks = compare(gaps["gap"], gaps["tokens"], cellf["check"], counts)
    correct = is_correct(checks)

    metrics = {}
    if args.trace:
        import devtrace

        run = devtrace.RunView(trace_result, win, cfg_file, peaks)
        for m in cell.per_layer:
            v = spec.load_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = timeline.end_to_end(win.reqs, win.chunks, args.seconds)
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            v = e2e[m["name"]]
            metrics[m["name"]] = {
                "value": v if math.isfinite(v) else None, "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell.chips, "memory_peak_bytes": peak}
    result = {"correct": correct,
              "attempted": len(due_in_window(win, args.seconds)),
              "failed": counts["unfinished"] + counts["truncated"],
              "metrics": metrics,
              "device": device}
    if args.trace:
        device["busy_s"] = trace_result["busy_s"]
        device["window_s"] = trace_result["window_s"]
        result["breakdown"] = trace_result["breakdown"]
    result["checks"] = {
        k: {"value": v if math.isfinite(v) else None, "limit": lim}
        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v!r} (limit {lim!r})")
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
