"""The measured window: offered requests through the served engine.

Each turn of the loop submits the requests that are due, calls the
engine's public `run_to_completion(max_steps=CHUNK, chunk=CHUNK)` --
which drains retired lanes, admits waiting requests (`admit_pages`,
`serve_prefill`, `prefill_insert`) and dispatches one fused
`engine_run` of CHUNK decode steps -- and then reads each lane's
sequence id and token count back from the device. That read waits for
the chunk, so its time is the chunk's end, and it says which requests
got which tokens. The engine would wait at the next drain anyway.

A request is handed to the engine only while the pool can hold the
whole output of every request handed over and not yet finished (the
most pages each can hold, `pages.request_pages`, against the pool's
pages): the engine reserves only a prompt's pages at admission and
cannot preempt, so a lane that found the pool empty in decode would be
cut short. Requests that do not fit yet wait in order, on the
harness's side, and their wait counts in their time to first token.
The read at each chunk end also takes the pages the lanes hold
(`pages.lane_pages`), for the pool's occupancy.

When nothing runs and nothing waits, the loop sleeps until the next
request is due. Every phase is wrapped in a `TraceAnnotation`
(`bench.submit`, `bench.chunk`, `bench.sync`, `bench.idle`), so that a
profiler trace can say what the host was doing while the device idled.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

import pages
from traffic import Request


class Window:
    """What one window recorded, in seconds after it opened."""

    def __init__(self, reqs: List[Request], seconds: float, pool_pages: int):
        self.reqs = reqs
        self.by_id: Dict[int, Request] = {r.rid: r for r in reqs}
        self.seconds = seconds
        self.pool_pages = pool_pages
        self.holds: Dict[int, int] = {}  # rid -> pages reserved for it
        self.chunks: List[dict] = []    # t_end, steps, tokens, admitted, pages
        self.t0 = 0.0                   # perf_counter when the window opened
        self.steps0 = 0                 # engine steps before the window
        self.close_state: Optional[dict] = None  # lane tables at the close
        self.grace_s = 0.0

    @property
    def reserved(self) -> int:
        """Pages held for the requests handed over and not finished."""
        return sum(self.holds.values())


def _sync(eng, win: Window, now: Callable[[], float], admitted: int) -> None:
    """Wait for the chunk and attribute its tokens."""
    with jax.profiler.TraceAnnotation("bench.sync"):
        seq, n_out, n_pages = jax.device_get(
            (eng.state.seq_id, eng.state.n_out,
             pages.lane_pages(eng)["n_pages"]))
    t = now()
    steps = eng.stats["steps"] - win.steps0
    tokens = 0
    for sid, n in zip(seq.tolist(), n_out.tolist()):
        r = win.by_id.get(sid)
        if r is None or n <= r.n_seen:
            continue
        tokens += n - r.n_seen
        if r.steps_first < 0:
            r.t_first, r.steps_first, r.n_first = t, steps, n
        r.n_seen = n
        if n >= r.max_new:
            del win.holds[r.rid]
        if t <= win.seconds:
            r.t_last_w, r.steps_last_w = t, steps
    win.chunks.append(
        {"t_end": t, "steps": steps, "tokens": tokens, "admitted": admitted,
         "pages": int(n_pages.sum())}
    )


def lane_tables(eng) -> dict:
    """The lanes' page tables and counts, read from the device."""
    held = pages.lane_pages(eng)
    shard, off, seq, n_pages = jax.device_get(
        (held["page_shard"], held["page_off"], eng.state.seq_id,
         held["n_pages"])
    )
    return {"page_shard": shard, "page_off": off, "seq_id": seq,
            "n_pages": n_pages, "free_pages": eng.device_free_pages(),
            "pages_per_shard": eng.ecfg.pages_per_shard}


def serve(eng, reqs: List[Request], seconds: float, chunk: int,
          tracer=None, on_close=None, grace_cap_s: float = 90.0) -> Window:
    """Offer `reqs` for `seconds`, then serve on without new arrivals
    until every admitted or due request has finished (at most
    `grace_cap_s` more), so that each answer can be checked and every
    page must be back in the pool."""
    win = Window(reqs, seconds, eng.ecfg.num_pages)
    pending = list(reqs)  # in due order
    nxt = 0
    win.steps0 = eng.stats["steps"]
    win.t0 = time.perf_counter()

    def now() -> float:
        return time.perf_counter() - win.t0

    def submit_due(limit: float) -> None:
        """Every request due by now, in order, while the pool can hold
        its whole output."""
        nonlocal nxt
        with jax.profiler.TraceAnnotation("bench.submit"):
            t = now()
            while nxt < len(pending):
                r = pending[nxt]
                if r.due > min(t, limit):
                    break
                need = pages.request_pages(eng, len(r.prompt), r.max_new)
                if win.reserved + need > win.pool_pages:
                    break
                r.submitted = t
                eng.submit(_engine_request(r))
                win.holds[r.rid] = need
                nxt += 1

    def one_chunk() -> None:
        before = eng.stats["admitted"]
        steps = eng.stats["steps"]
        with jax.profiler.TraceAnnotation("bench.chunk"):
            eng.run_to_completion(max_steps=chunk, chunk=chunk)
        if eng.stats["steps"] != steps:
            _sync(eng, win, now, eng.stats["admitted"] - before)

    while True:
        t = now()
        if tracer is not None:
            tracer.at_boundary(t, eng, win)
        if t >= seconds:
            break
        submit_due(seconds)
        if not eng.running and not eng.waiting:
            nxt_due = pending[nxt].due if nxt < len(pending) else seconds
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(max(0.0, min(nxt_due, seconds) - now()))
            continue
        one_chunk()
    if tracer is not None:
        tracer.stop(now(), eng, win)
    if on_close is not None:
        on_close()
    win.close_state = lane_tables(eng)
    g0 = now()
    while now() - g0 < grace_cap_s:
        submit_due(seconds)  # due inside the window, not yet submitted
        if not (eng.running or eng.waiting):
            break
        one_chunk()
    win.grace_s = now() - g0
    for sid, er in eng.completed.items():
        r = win.by_id.get(sid)
        if r is not None:
            r.out = list(er.out_tokens)
            r.done = True
    return win


def _engine_request(r: Request):
    from repro.serve.engine import Request as EngineRequest

    return EngineRequest(r.rid, r.prompt, max_new_tokens=r.max_new)


def lateness(win: Window) -> np.ndarray:
    """How late each request was handed to the engine after it was due,
    in s: the loop's own lateness and the pool gate's holds."""
    return np.array([r.submitted - r.due for r in win.reqs
                     if r.submitted == r.submitted])
