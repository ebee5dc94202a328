"""Share of its roofline that the SSD chunk Pallas kernel reaches in the
traced ticks that ran a prefill chunk, in %: the bytes its calls need (the
family's ``ssd_bytes`` over each chunk's real tokens) over peak bandwidth,
over the kernel's device time.  Decode steps the SSM state without the
kernel, so only prefill chunks call it, once per SSM layer.  The kernel is
found by name in the trace; a run in which it does not appear reads
nothing."""

from bench.trace_reduce import op_of

KERNEL = "ssd_chunk"        # the custom call's instruction name


def read(ctx):
    ssd_bytes = getattr(ctx.family, "ssd_bytes", None)
    if ssd_bytes is None or ctx.trace is None:
        return None
    need, secs = 0.0, 0.0
    for tick, ops in zip(ctx.traced_ticks, ctx.trace.tick_ops):
        if not tick.prefill:
            continue
        durs = [d for name, ds in ops.items()
                if op_of(name).startswith(KERNEL) for d in ds]
        if not durs:
            continue
        # each chunk of the tick calls the kernel equally often
        per_call = sum(ssd_bytes(ctx.dims, n) for _, n, _ in tick.prefill) \
            / len(tick.prefill)
        need += len(durs) * per_call
        secs += sum(durs)
    if secs <= 0:
        return None
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / secs
