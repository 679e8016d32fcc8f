"""Share of its roofline that one simulated cycle reaches (%).

The least time a cycle could take is the design's bytes per cycle
(``bytes_per_cycle`` of its design file, counted from the design's shapes)
over the HBM bandwidth of the chips in use (``peaks.json``): the cycle is
memory-bound by construction.  The time it takes is the device time of
the program executions in the traced window per simulated cycle advanced
there.  A share above 100% means the bytes are counted too high or the
time misses part of the work, and is an error."""
from chipbench.metrics import reduce


def read(run):
    if run.unit != "cycles" or not run.count or run.bytes_per_cycle is None:
        return None
    t = reduce.module_seconds(run.lines)
    if not t:
        return None
    least = run.bytes_per_cycle / (run.peak["hbm_bytes_per_s"] * run.chips)
    share = 100.0 * least / (t / run.count)
    if share > 100.0:
        raise ValueError(f"cycle roofline share {share}% exceeds 100%")
    return share
