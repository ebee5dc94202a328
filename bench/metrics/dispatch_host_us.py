"""Median host time of one overlay decode call, entry to return, in us,
over the window (the engine's ``_decode``, timed by the harness's wrapper
in traced runs)."""

import statistics


def read(ctx):
    if not ctx.obs.decode_us:
        return None
    return statistics.median(ctx.obs.decode_us)
