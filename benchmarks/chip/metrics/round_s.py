"""round_s: wall time of a boosting round.  Every round that starts in the
window runs to its end (which is reached on host numpy, so it is
blocked), and the time from the window's start to the last round's end is
divided by their number."""


def read(run):
    if not run.units:
        return None
    return run.window_s / len(run.units)
