"""mwd_dma_ps_per_lup: the MWD kernel's slab DMA time, in ps per LUP.

Device time of the kernel's ``mwd.fetch`` (inbound slab copies, started and
waited one after another) and ``mwd.emit`` (outbound copies) regions,
summed over the cell's chips, over the LUPs of the traced calls. None when
the trace holds no complete regions (`chipbench.regions`).
"""

from chipbench import regions


def read(run):
    """Fetch + emit region time per LUP, or None."""
    return regions.region_ps_per_lup(run, ("mwd.fetch", "mwd.emit"))
