"""Host time between device steps (`serving/engine.py`): the median, over
the window's pairs of consecutive steps, of the time from the end of step
k's `engine.readback` span (the host holds the sampled tokens) to the start
of step k+1's `engine.decode` span (the next decode is dispatched).  From
the program's own spans in the trace; nothing where the program has none."""

from bench.lib import program


def read(run):
    prog = program.of(run)
    return None if prog is None else program.median_host_gap_ms(prog)
