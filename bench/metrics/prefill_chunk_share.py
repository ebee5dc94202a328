"""Share of the engine's ticks that ran a prefill chunk, in %: the
program's ``engine.step`` spans in the traced window that hold an
``engine.prefill_chunk`` span (the program's own twin of
``prefill_tick_share``)."""

from bench import program_spans


def read(ctx):
    prog = program_spans.of_run(ctx)
    return None if prog is None else program_spans.chunk_share(prog)
