"""Share of the window's ticks that ran a prefill chunk, in %."""


def read(ctx):
    ticks = ctx.obs.ticks[:ctx.obs.window_ticks]
    if not ticks:
        return None
    return 100.0 * sum(1 for t in ticks if t.prefill) / len(ticks)
