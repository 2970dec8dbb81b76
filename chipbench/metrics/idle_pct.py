"""idle_pct: share of the traced window in which no op runs, in percent.

1 - (union of the device's op intervals) / window, as a mean over the
cell's devices.
"""


def read(run):
    """Mean idle share of the traced window, or None without a trace."""
    if run.attributions is None or run.window_ns <= 0:
        return None
    idle = [1.0 - a.busy / run.window_ns for a in run.attributions]
    return 100.0 * sum(idle) / len(idle)
