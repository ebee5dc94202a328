"""Helpers shared by the readers that split traced ticks by their work."""


def decode_only(ctx):
    """(tick, device seconds, ops by name) of traced ticks that decoded and
    ran no prefill chunk."""
    if ctx.trace is None:
        return []
    return [(t, s, ops) for t, s, ops in zip(ctx.traced_ticks,
                                             ctx.trace.tick_busy_s,
                                             ctx.trace.tick_ops)
            if t.decode_ctx and not t.prefill]


def with_prefill(ctx):
    """(tick, device seconds) of traced ticks that ran a prefill chunk."""
    if ctx.trace is None:
        return []
    return [(t, s) for t, s in zip(ctx.traced_ticks, ctx.trace.tick_busy_s)
            if t.prefill]
