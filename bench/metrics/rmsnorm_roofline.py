"""Share of its roofline that the RMSNorm Pallas kernel reaches in the
decode-only ticks of the traced window, in %: the bytes one call needs
(the family's ``rmsnorm_bytes`` over the batch's rows) over peak bandwidth,
over the kernel's device time per call.  The kernel is found by name in
the trace; a run in which it does not appear reads nothing."""

from bench.metrics._ticks import decode_only
from bench.trace_reduce import op_of

KERNEL = "rmsnorm"          # the custom call's instruction name


def read(ctx):
    rmsnorm_bytes = getattr(ctx.family, "rmsnorm_bytes", None)
    if rmsnorm_bytes is None:
        return None
    calls, secs = 0, 0.0
    for _, _, ops in decode_only(ctx):
        for name, durs in ops.items():
            if op_of(name).startswith(KERNEL):
                calls += len(durs)
                secs += sum(durs)
    if not calls or secs <= 0:
        return None
    rows = int(ctx.config["engine"]["batch"])
    need = calls * rmsnorm_bytes(ctx.dims, rows)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / secs
