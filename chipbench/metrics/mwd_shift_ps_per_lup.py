"""mwd_shift_ps_per_lup: the MWD kernel's window shift time, in ps per LUP.

Device time of the kernel's ``mwd.shift`` regions (every grid step moves
each stream's whole VMEM window down by N_F rows), summed over the cell's
chips, over the LUPs of the traced calls. None when the trace holds no
complete regions (`chipbench.regions`).
"""

from chipbench import regions


def read(run):
    """Shift region time per LUP, or None."""
    return regions.region_ps_per_lup(run, ("mwd.shift",))
