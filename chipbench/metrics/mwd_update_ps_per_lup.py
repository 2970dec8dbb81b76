"""mwd_update_ps_per_lup: the MWD kernel's in-tile update time, in ps per LUP.

Device time of the kernel's ``mwd.update`` regions (a grid step's in-tile
updates: each time level whose rows meet the diamond and whose slab meets
the interior, over the sublane tiles that cover its rows, with the level's
bounds and masks), summed over the cell's chips, over the LUPs of the
traced calls. None when the trace holds no complete
regions (`chipbench.regions`).
"""

from chipbench import regions


def read(run):
    """Update region time per LUP, or None."""
    return regions.region_ps_per_lup(run, ("mwd.update",))
