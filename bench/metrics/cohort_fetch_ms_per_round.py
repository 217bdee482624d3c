"""``cohort_fetch_ms_per_round`` (ms): host time inside the shard
source's ``device_batches`` (the streaming data plan's cohort fetch) per
round of the traced window.  The benchmark wraps that one instance's
method in a timer and a ``cohort_fetch`` trace span; a dataset that is
not a streaming source is never fetched from, and this reads nothing."""


def read(ctx):
    if not ctx.fetch_calls or ctx.rounds <= 0:
        return None
    return 1000.0 * ctx.fetch_s / ctx.rounds
