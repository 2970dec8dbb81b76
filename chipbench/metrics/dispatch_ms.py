"""dispatch_ms: host time of one `ops.mwd` call, in ms.

Mean duration of the program's ``repro.mwd`` host span (plan resolution,
then the dispatch of the jitted program) over the calls that start in the
traced window. It bounds the device's idle gap between chained calls. None
when the trace holds no such span.
"""

from chipbench import regions


def read(run):
    """Mean ``repro.mwd`` span in ms, or None."""
    reg = regions.for_run(run)
    if reg is None:
        return None
    spans = regions.entry_spans(reg)
    if not spans:
        return None
    return sum(e - s for s, e, _ in spans) / len(spans) * 1e-6
