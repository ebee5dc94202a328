"""Share of the engine's ticks in which no operation ran on the chip, in %:
the union of the program's ``engine.step`` spans in the traced window,
less the device busy time inside it.  Unlike ``device_idle`` it leaves out
the time between ticks, such as waits for an arrival."""

from bench import program_spans


def read(ctx):
    prog = program_spans.of_run(ctx)
    if prog is None or prog.step_s <= 0:
        return None
    return 100.0 * (1.0 - prog.step_busy_s / prog.step_s)
