"""Host time the engine's admission phase takes per admitted request:
the `admit` spans of `JitServeEngine.spans` (the program's own
wall-clock journal) inside the traced span, over the requests they
admitted. Moves ttft_p50_ms."""


def read(run):
    spans = [s for s in run.engine_spans if s["phase"] == "admit"]
    n = sum(s.get("admitted", 0) for s in spans)
    if not n:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in spans) / n
