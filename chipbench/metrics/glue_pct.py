"""glue_pct: device time outside the kernel and the collectives, in percent.

Self time of every device op that is neither `mwd_*` nor a collective (the
pads, Dirichlet frame sync and crops of `_mwd_run_impl`, and the stepper's
halo assembly), over device busy time, both summed over the cell's devices.
"""

from chipbench.trace import GLUE


def read(run):
    """Glue share of busy time, or None when no device op ran."""
    if run.attributions is None:
        return None
    busy = sum(a.busy for a in run.attributions)
    if busy <= 0:
        return None
    glue = sum(a.by_category.get(GLUE, 0.0) for a in run.attributions)
    return 100.0 * glue / busy
