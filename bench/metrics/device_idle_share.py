"""Share of the traced span in which no operation ran on the device:
1 - (union of device op intervals) / span. Moves tokens_per_s."""


def read(run):
    if not run.trace["window_s"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
