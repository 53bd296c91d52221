"""Host time the engine's admission waits on the device, per admitted
request: the `engine.sync` records of `JitServeEngine.spans` (each
blocking read the engine makes) that lie inside its `admit` records in
the traced span -- the read of the free lanes, and for each request
the read of whether its prompt's pages fitted -- over the requests
those records admitted. The part of `admit_host_ms` that batching the
admissions of one chunk could save. Moves ttft_p50_ms. None for a
program that journals no `engine.sync` spans."""


def read(run):
    spans = run.engine_spans
    syncs = [s for s in spans if s["phase"] == "engine.sync"]
    admits = [s for s in spans if s["phase"] == "admit"]
    n = sum(s.get("admitted", 0) for s in admits)
    if not syncs or not n:
        return None
    waited = sum(s["t1"] - s["t0"] for s in syncs
                 if any(a["t0"] <= s["t0"] and s["t1"] <= a["t1"]
                        for a in admits))
    return 1e3 * waited / n
