"""``client_make_ms_per_round`` (ms): host time in the program's
``cohort.make`` spans (``data/shard_source.py``: a shard source
generating a client's arrays on a cache miss) per round of the traced
window, summed on the window's thread and clipped to the window.  A
trace without the span (a stacked plan, or a program that has no such
span) reads nothing."""


def span_ms_per_round(ctx, names):
    """Summed time of the host spans ``names`` on the window's thread,
    clipped to the window, in ms per window round; None where the trace
    is absent or holds none of them."""
    if ctx.trace is None or ctx.rounds <= 0:
        return None
    red = ctx.trace
    spans = [(start, start + dur) for _, name, start, dur in red.host
             if name in names]
    if not spans:
        return None
    ns = sum(min(b, red.t1) - max(a, red.t0) for a, b in spans)
    return ns / 1e6 / ctx.rounds


def read(ctx):
    return span_ms_per_round(ctx, ("cohort.make",))
