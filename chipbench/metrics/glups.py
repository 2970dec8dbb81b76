"""glups: useful lattice updates per second over the measured window, in 1e9.

Global grid cells x steps of every call that completed in the window, over
the host-clock time from the first call's start to the last call's end.
Each call ends in `block_until_ready`, so the time covers all device work.
"""


def read(run):
    """GLUP/s of the window, or None when no call completed."""
    if not run.calls:
        return None
    lups = sum(c[2] for c in run.calls)
    return lups / (run.calls[-1][1] - run.calls[0][0]) / 1e9
