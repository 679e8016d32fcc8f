"""Reductions from a profiler trace to numbers (shared by the per-layer
readers beside this file and by the run's ``device`` and ``breakdown``).

Input is ``chipbench.tracing.Line`` lists.  A device is a plane named
``/device:TPU:<n>``; its ``XLA Modules`` line holds one event per program
execution and its ``XLA Ops`` line the operations inside them.  The
window is the harness's ``WINDOW`` host span.  Every device quantity is
clipped to the window and averaged over the devices in the trace.
"""
from __future__ import annotations

import re

import numpy as np

from chipbench.tracing import WINDOW

_DEVICE = re.compile(r"^/device:TPU:\d+$")
MODULES = "XLA Modules"
OPS = "XLA Ops"
# "%dynamic-slice_select_fusion.73 = f32[...] fusion(...)" -> the kind of
# op, "dynamic-slice_select_fusion": the instruction name less its number
_KIND = re.compile(r"^%?([^\s=.]+(?:\.[^\s=.\d][^\s=.]*)*)")


def op_kind(name: str) -> str:
    m = _KIND.match(name)
    return m.group(1) if m else name


def window(lines) -> tuple[float, float]:
    """(start, end) ns of the harness's window span."""
    for ln in lines:
        if _DEVICE.match(ln.plane) or WINDOW not in ln.names:
            continue
        i = np.flatnonzero(ln.ids == ln.names.index(WINDOW))
        return float(ln.start[i[0]]), float(ln.end[i[0]])
    raise ValueError("the trace has no window span")


def devices(lines) -> dict:
    """{device plane: {line name: Line}} for the TPU planes."""
    out: dict = {}
    for ln in lines:
        if _DEVICE.match(ln.plane):
            out.setdefault(ln.plane, {})[ln.name] = ln
    return out


def _clip(ln, t0, t1):
    keep = (ln.end > t0) & (ln.start < t1)
    sub = ln.select(keep)
    sub.start = np.maximum(sub.start, t0)
    sub.end = np.minimum(sub.end, t1)
    return sub


def union(start: np.ndarray, end: np.ndarray):
    """Merged, sorted intervals covering the union of the given ones."""
    if len(start) == 0:
        return np.zeros(0), np.zeros(0)
    o = np.argsort(start, kind="stable")
    s, e = start[o], end[o]
    reach = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.maximum.reduceat(e, idx)


def covered(ms: np.ndarray, me: np.ndarray, a, b) -> np.ndarray:
    """Length of ``[a, b]`` (arrays) covered by merged intervals."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if len(ms) == 0:
        return np.zeros_like(a)
    cum = np.concatenate([[0.0], np.cumsum(me - ms)])

    def upto(x):
        k = np.searchsorted(ms, x, side="right")  # intervals starting <= x
        full = cum[np.maximum(k - 1, 0)]
        part = np.where(k > 0, np.minimum(x, me[np.maximum(k - 1, 0)])
                        - ms[np.maximum(k - 1, 0)], 0.0)
        return np.where(k > 0, full + np.maximum(part, 0.0), 0.0)

    return upto(b) - upto(a)


def _busy(dev: dict, t0, t1):
    parts = [_clip(ln, t0, t1) for name, ln in dev.items()
             if name in (MODULES, OPS)]
    if not parts:
        return np.zeros(0), np.zeros(0)
    return union(np.concatenate([p.start for p in parts]),
                 np.concatenate([p.end for p in parts]))


def busy_s(lines) -> float | None:
    """Seconds in which an operation ran on a device, mean over devices."""
    t0, t1 = window(lines)
    devs = devices(lines)
    if not devs:
        return None
    tot = [float(np.sum(e - s)) for s, e in
           (_busy(d, t0, t1) for d in devs.values())]
    return float(np.mean(tot)) * 1e-9


def window_s(lines) -> float:
    t0, t1 = window(lines)
    return (t1 - t0) * 1e-9


def idle_pct(lines) -> float | None:
    b = busy_s(lines)
    return None if b is None else 100.0 * (1.0 - b / window_s(lines))


def module_seconds(lines) -> float | None:
    """Device time of program executions in the window, mean over
    devices."""
    t0, t1 = window(lines)
    got = []
    for dev in devices(lines).values():
        if MODULES in dev:
            m = _clip(dev[MODULES], t0, t1)
            s, e = union(m.start, m.end)
            got.append(float(np.sum(e - s)) * 1e-9)
    return float(np.mean(got)) if got else None


def self_times(ln) -> np.ndarray:
    """Each event's duration less that of the events nested directly in
    it (events of one line nest or are disjoint)."""
    n = len(ln.start)
    dur = ln.end - ln.start
    o = np.lexsort((-dur, ln.start))
    s, e = ln.start[o], ln.end[o]
    if n < 2 or np.all(s[1:] >= e[:-1]):
        return dur
    child = np.zeros(n)
    stack: list[int] = []
    for i in range(n):
        while stack and e[stack[-1]] <= s[i]:
            stack.pop()
        if stack:
            child[stack[-1]] += e[i] - s[i]
        stack.append(i)
    out = np.empty(n)
    out[o] = (e - s) - child
    return out


def top_ops(lines, n: int = 10) -> list:
    """[[op kind, seconds]]: device operations by self time in the
    window, summed by kind (``op_kind``), mean over devices, longest
    first."""
    t0, t1 = window(lines)
    devs = devices(lines)
    tot: dict[str, float] = {}
    for dev in devs.values():
        if OPS not in dev:
            continue
        ops = _clip(dev[OPS], t0, t1)
        per = np.bincount(ops.ids, weights=self_times(ops),
                          minlength=len(ops.names))
        for name, v in zip(ops.names, per):
            kind = op_kind(name)
            tot[kind] = tot.get(kind, 0.0) + float(v)
    k = max(len(devs), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, v * 1e-9 / k] for name, v in best if v > 0]


def idle_gaps(lines, labels, n: int = 10) -> list:
    """[[what the host was doing, seconds]]: the device's idle time in
    the window, each gap given to the host span in ``labels`` that
    overlaps it most, summed by span name and averaged over devices."""
    t0, t1 = window(lines)
    spans: dict[str, tuple] = {}
    for ln in lines:
        if _DEVICE.match(ln.plane):
            continue
        for name in set(labels) & set(ln.names):
            sel = ln.ids == ln.names.index(name)
            prev = spans.get(name, (np.zeros(0), np.zeros(0)))
            spans[name] = union(np.concatenate([prev[0], ln.start[sel]]),
                                np.concatenate([prev[1], ln.end[sel]]))
    devs = devices(lines)
    tot: dict[str, float] = {}
    for dev in devs.values():
        bs, be = _busy(dev, t0, t1)
        gs = np.concatenate([[t0], be])
        ge = np.concatenate([bs, [t1]])
        keep = ge > gs
        gs, ge = gs[keep], ge[keep]
        if not len(gs):
            continue
        names = sorted(spans)
        cover = (np.stack([covered(*spans[k], gs, ge) for k in names])
                 if names else np.zeros((0, len(gs))))
        label = np.full(len(gs), "host, outside any span", object)
        if names:
            best = np.argmax(cover, axis=0)
            has = cover[best, np.arange(len(gs))] > 0
            label[has] = np.asarray(names, object)[best[has]]
        for lab in set(label):
            tot[lab] = tot.get(lab, 0.0) + float(np.sum((ge - gs)[label == lab]))
    k = max(len(devs), 1)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, v * 1e-9 / k] for name, v in best]
