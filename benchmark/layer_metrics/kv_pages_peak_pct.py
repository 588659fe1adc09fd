"""Peak share of the KV pool's pages in use during the window (allocator
counts sampled five times a second)."""

NAME = "kv_pages_peak_pct"
UNIT = "%"
BETTER = "lower"
LAYER = "kv pool"
MOVES = "out_tok_s"
SOURCE = "program_counter"


def read(r):
    inside = r.samples_in(r.t0, r.t1)
    if not inside or not r.engine.get("num_pages"):
        return None
    return (max(s["pages_active"] for s in inside)
            / r.engine["num_pages"] * 100.0)
