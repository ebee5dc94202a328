"""Model FLOP/s utilization of the whole step, in %: the operations that
every token processed in the traced window needs (prompt tokens and
decoded tokens, attention at each one's actual context, the output head
only where logits are needed), over the traced window times the chip's
bf16 peak, from the family's ``prefill_chunk`` and ``token_flops``.
Bounds every kernel's share: a kernel taken off the path leaves this
standing."""


def read(ctx):
    prefill_chunk = getattr(ctx.family, "prefill_chunk", None)
    token_flops = getattr(ctx.family, "token_flops", None)
    if prefill_chunk is None or token_flops is None or ctx.trace is None \
            or ctx.trace.window_s <= 0:
        return None
    total = 0.0
    for tick in ctx.traced_ticks:
        for off, n, last in tick.prefill:
            total += prefill_chunk(ctx.dims, off, n, last)
        total += sum(token_flops(ctx.dims, c, True)
                     for c in tick.decode_ctx)
    if total <= 0:
        return None
    return 100.0 * total / (ctx.trace.window_s
                            * ctx.peaks["bf16_flops_per_s"])
