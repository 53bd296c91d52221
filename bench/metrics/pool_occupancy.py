"""Share of the KV pool that live lanes hold: the pages of every lane
(the engine state's `n_pages`, read at each chunk end) over the pool's
pages, averaged over the chunk ends inside the window. Moves
tokens_per_s: a fuller pool serves more requests at once."""


def read(run):
    held = [c["pages"] for c in run.win.chunks if c["t_end"] <= run.win.seconds]
    if not held:
        return None
    return 100.0 * sum(held) / (len(held) * run.cfg["num_pages"])
