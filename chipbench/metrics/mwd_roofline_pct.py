"""mwd_roofline_pct: the MWD kernel's share of its roofline, in percent.

The least time the chip could take for the traced calls' useful work is
max(F / peak FLOP/s, B / peak HBM bytes/s), shared over the cell's chips,
where F = useful FLOPs per LUP x LUPs, and B = the compulsory arrays of a
call (every input read once, both returned levels written once) x global
grid cells x word x calls. Both counts come from the configuration file
and the peaks from peaks.json: they are fixed by the problem, not by how
the kernel moves its bytes, so a kernel that moves fewer bytes still reads
at most 100%. That least time is divided by the kernel's device time (self
time of `mwd_*` events, summed over devices, then divided by the chip
count). The compute term uses the published bf16 peak as the ceiling of
any arithmetic on the chip, since no f32 VPU peak is published.
"""

import math

from chipbench.trace import KERNEL


def least_time_s(config, traffic, peaks, n_calls, chips):
    """Roofline time of `n_calls` calls of the cell, per chip, in seconds."""
    cells = math.prod(traffic["grid"])
    flops = config["useful_flops_per_lup"] * cells * traffic["steps_per_call"]
    arrays = config["compulsory_arrays_per_call"]
    nbytes = (arrays["read"] + arrays["write"]) * cells * config["word_bytes"]
    return n_calls * max(flops / peaks["flops_per_s"],
                         nbytes / peaks["hbm_bytes_per_s"]) / chips


def read(run):
    """Roofline share of the traced window's calls, or None with no kernel."""
    if run.attributions is None or not run.calls:
        return None
    kernel_ns = sum(a.by_category.get(KERNEL, 0.0) for a in run.attributions)
    kernel_s = kernel_ns * 1e-9 / run.chips
    if kernel_s <= 0:
        return None
    least = least_time_s(run.config, run.traffic, run.peaks, len(run.calls),
                         run.chips)
    return 100.0 * least / kernel_s
