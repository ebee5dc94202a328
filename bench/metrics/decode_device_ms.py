"""Device busy time of a tick that only decodes (no prefill chunk), mean
over the traced window, in ms.  Each tick ends in one ``device_get``, so
its device work lies inside its host span."""

from bench.metrics._ticks import decode_only


def read(ctx):
    ticks = decode_only(ctx)
    if not ticks:
        return None
    return 1e3 * sum(s for _, s, _ in ticks) / len(ticks)
