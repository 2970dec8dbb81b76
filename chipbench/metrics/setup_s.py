"""setup_s: process start to the first timed call, in seconds.

Initialisation, the seeded draw on the device, plan resolution, loading or
compiling the programs, and the warm-up call.
"""


def read(run):
    """Seconds of set-up before the window opened."""
    return run.setup_s
