"""Share of the window's decode steps on which some live row probed (the
decode program's probe pass, `launch/steps.py` over `models/*`): the window
difference of the engine's `probe_steps` counter over that of `steps`, as
the trace's `engine.step` spans carry them; nothing where the program
counts neither."""

from bench.lib import program


def read(run):
    prog = program.of(run)
    if prog is None:
        return None
    d = program.window_counters(prog, "engine.step")
    if not d or not d.get("steps"):
        return None
    return 100.0 * d["probe_steps"] / d["steps"]
