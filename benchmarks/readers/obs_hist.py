"""A histogram of the program's obs registry, as it stood when the window
closed. `stat` is `p50`/`p95` (of the ring's newest 512 observations) or
`sum_pct_of_window` (the lifetime sum against the window's length)."""


def read(rec, *, histogram: str, stat: str):
    h = rec.run.stats.get("obs", {}).get(histogram)
    if not h or not h.get("count"):
        return None
    if stat == "sum_pct_of_window":
        return 100.0 * (h["sum"] / 1e3) / rec.run.window_s
    return float(h[stat])
