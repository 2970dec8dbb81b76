"""collective_exposed_pct: collective time that nothing else hides, in percent.

Per device, the time in which a collective op runs and no other op does,
over the traced window; the largest over the cell's devices. None when the
trace holds no collective at all.
"""

from chipbench.trace import COLLECTIVE


def read(run):
    """Largest exposed-collective share over the devices."""
    if run.attributions is None or run.window_ns <= 0:
        return None
    if not any(a.by_category.get(COLLECTIVE, 0.0) > 0
               or a.exposed_collective > 0 for a in run.attributions):
        return None
    return 100.0 * max(a.exposed_collective
                       for a in run.attributions) / run.window_ns
