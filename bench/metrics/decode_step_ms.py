"""Device time of the decode program per execution in the window, from the
trace's program spans (`launch/steps.py` decode step over `models/*`)."""

PROGRAM = "jit_decode"


def read(run):
    tr = run["trace"]
    if tr is None or PROGRAM not in tr["modules"]:
        return None
    count, seconds = tr["modules"][PROGRAM]
    return 1e3 * seconds / count if count else None
