"""Median duration of the overlay's dispatch of the decode step, in us:
the program's ``overlay.dispatch`` spans inside its ``engine.decode`` spans
in the traced window (the program's own twin of ``dispatch_host_us``)."""

from bench import program_spans


def read(ctx):
    prog = program_spans.of_run(ctx)
    return None if prog is None else program_spans.decode_dispatch_us(prog)
