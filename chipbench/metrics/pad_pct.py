"""pad_pct: device time of the launch padding, in percent of busy time.

Self time of the glue ops under the ``mwd.pad`` scope (the edge pads of
both parity grids and of the coefficient stack), over device busy time,
both summed over the cell's chips. The frame sync (``mwd.frame_sync``) and
crop (``mwd.crop``) make up the rest of `glue_pct`. None when no op of the
trace carries the scope.
"""

from chipbench import regions


def read(run):
    """Pad share of busy time, or None."""
    reg = regions.for_run(run)
    if reg is None or run.attributions is None:
        return None
    if "mwd.pad" not in reg.scopes.values():
        return None
    busy = sum(a.busy for a in run.attributions)
    if busy <= 0:
        return None
    return 100.0 * regions.scope_ns(run, reg, "mwd.pad") / busy
