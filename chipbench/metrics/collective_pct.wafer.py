"""Share of the traced window in which a cross-chip collective ran (%), in
the wafer cells that span chips.

A collective is an ``XLA Ops`` event whose kind (``reduce.op_kind``) or
HLO opcode starts with one of ``KINDS``, its ``-start`` and ``-done``
halves included: the tier exchange's ``ppermute``s and ``run_until``'s
done ``psum`` (an instruction named ``%psum.<n>`` whose opcode is
``all-reduce``).  Per chip, the union of those events' intervals clipped
to the window, over the window; averaged over the chips.  An
asynchronous collective counts for its start and done ops, not for the
transfer in flight between them.  None on a one-chip trace, where
nothing crosses chips."""
import re

import numpy as np

from chipbench.metrics import reduce

KINDS = ("collective-permute", "all-reduce", "all-gather", "reduce-scatter",
         "all-to-all")
# "%psum.18 = s32[]{:T(128)} all-reduce(s32[] %sub.154), ...": the opcode
# is the word before the instruction's first "(" after its shape
_OPCODE = re.compile(r"\s(?:%s)(?:-start|-done)?\(" % "|".join(KINDS))


def _is_collective(name: str) -> bool:
    return (reduce.op_kind(name).startswith(KINDS)
            or _OPCODE.search(name) is not None)


def read(run):
    devs = reduce.devices(run.lines)
    if len(devs) < 2:
        return None
    t0, t1 = reduce.window(run.lines)
    shares = []
    for dev in devs.values():
        covered = 0.0
        if reduce.OPS in dev:
            ops = dev[reduce.OPS]
            coll = np.array([_is_collective(n) for n in ops.names], bool)
            sub = reduce._clip(ops.select(coll[ops.ids]), t0, t1)
            s, e = reduce.union(sub.start, sub.end)
            covered = float(np.sum(e - s))
        shares.append(covered / (t1 - t0))
    return 100.0 * float(np.mean(shares))
