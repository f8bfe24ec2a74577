"""megastep_ms: the window's host-clock length over the mega-steps the
engine executed in it (its `megasteps_executed` counter)."""


def read(ctx):
    n = ctx.counters.get("megasteps", 0)
    if n <= 0:
        return None
    return 1e3 * ctx.counters["window_s"] / n
