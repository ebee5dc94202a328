"""Share of its roofline that the decode step reaches, in %: the least
time the chip could take for the decode-only ticks of the traced window
(the larger of needed bytes over peak bandwidth and needed operations over
peak FLOP/s, from the family's ``decode_step``), over their device busy
time."""

from bench.metrics._ticks import decode_only


def read(ctx):
    decode_step = getattr(ctx.family, "decode_step", None)
    ticks = decode_only(ctx)
    busy = sum(s for _, s, _ in ticks)
    if decode_step is None or not ticks or busy <= 0:
        return None
    least = 0.0
    for tick, _, _ in ticks:
        f, b = decode_step(ctx.dims, tick.decode_ctx)
        least += max(f / ctx.peaks["bf16_flops_per_s"],
                     b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / busy
