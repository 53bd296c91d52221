"""Page accounting, asked of the engine.

The pool gate (`serve_loop.serve`), the pool's occupancy at each chunk
end and the page bookkeeping of the check (`run.bookkeeping`) need two
answers that depend on how the engine lays a request out in the pool:

  request_pages(prompt_len, max_new) -> int
      the most pages one request of that prompt length and output can
      hold at once: what the gate reserves while it is in flight
  lane_pages() -> {"page_shard", "page_off", "n_pages"}
      device arrays: each lane's table of page handles (int32 [B, MP],
      -1 where the slot holds no page; the global page id is
      shard * pages_per_shard + off) and the count of pages it holds
      (int32 [B])

An engine that provides these methods gives the answers. One that does
not is the leaf-page engine, where a lane holds one page for every
`page_tokens` tokens of its context and its tables are the state's
own: the answers below are its.
"""

from __future__ import annotations


def request_pages(eng, prompt_len: int, max_new: int) -> int:
    """Pages a request holds once its whole output is written."""
    ask = getattr(eng, "request_pages", None)
    if ask is not None:
        return int(ask(prompt_len, max_new))
    return -(-(prompt_len + max_new) // eng.ecfg.page_tokens)


def lane_pages(eng) -> dict:
    """The lanes' page tables and page counts, as device arrays: no
    read from the device here."""
    ask = getattr(eng, "lane_pages", None)
    if ask is not None:
        return ask()
    st = eng.state
    return {"page_shard": st.page_shard, "page_off": st.page_off,
            "n_pages": st.n_pages}
