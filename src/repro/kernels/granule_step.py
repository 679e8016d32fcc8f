"""Generalized per-granule fused-epoch body (§Perf).

``systolic_step`` fuses ONE hand-written block type (the systolic MAC
cell) into a Pallas kernel.  This module generalizes that move to ANY
lowered ``ChannelGraph`` granule: the fused engine
(``repro.core.fused``) hands over a pure single-cycle function — depth-1
register channels + boundary queues + the vmapped block steps — and
``epoch_loop`` executes the whole K-cycle tier-inner epoch as one fused
computation instead of ~10 interpreted queue ops per cycle: one
``fori_loop`` whose carry is the compact register-file state (the deep
queue buffers and lookup tables stay out of the carry), inside the one
jitted XLA computation of the engine's epoch.  ``epoch_program`` walks a
whole multi-epoch span of cycle blocks and tier exchanges the same way.

Contract for ``cycle_fn``: pytree -> pytree with identical treedef,
shapes, and dtypes (the fused engine's local cycle satisfies it; the
wrapper checks and raises otherwise).
"""
from __future__ import annotations

import os
from typing import Any, Callable, Sequence, Tuple

import jax

PyTree = Any

#: Op list executed by :func:`epoch_program`: ``("C", n)`` runs ``n``
#: cycles of the cycle body; ``("X", t)`` runs the caller's exchange
#: function for tier ``t``; ``("XI", t)`` / ``("XC", t)`` are the split
#: form of the same exchange — issue (drain + start transfer) and commit
#: (finish transfer + fill) — so intervening ops overlap the transfer.
#: The whole program is ONE fused computation.
Program = Sequence[Tuple[str, int]]


def resolve_overlap(overlap: Any = "auto") -> bool:
    """Resolve the overlapped-exchange knob (split issue/commit phases).

    An explicit non-"auto" argument (bool, or one of
    ``on|off|1|0|true|false``) always wins; for a caller-passed
    ``"auto"`` the environment variable ``REPRO_OVERLAP`` (same values, or
    ``auto``) decides, so CI can flip every engine to the split schedule
    without threading a flag through; "auto" with no env resolves to off
    — the serial schedule stays the default, and the split schedule is
    bit-identical by construction so flipping it per-run is always safe.
    """
    def parse(v: Any, src: str) -> bool:
        if isinstance(v, bool):
            return v
        s = str(v).strip().lower()
        if s in ("1", "on", "true", "yes"):
            return True
        if s in ("0", "off", "false", "no"):
            return False
        raise ValueError(f"{src}={v!r} not a boolean (on|off|1|0|auto)")

    if not (isinstance(overlap, str) and overlap.strip().lower() == "auto"):
        return parse(overlap, "overlap")
    env = os.environ.get("REPRO_OVERLAP", "auto").strip().lower()
    if env and env != "auto":
        return parse(env, "REPRO_OVERLAP")
    return False


def overlap_program(program: Program) -> Program:
    """Rewrite a serial op program into the split-exchange schedule.

    Every maximal run of consecutive ``("X", t)`` ops — the tiers firing
    at one sync boundary — becomes all their issues followed by all their
    commits: ``X_a, X_b -> XI_a, XI_b, XC_a, XC_b``.  A slab drained at
    the end of epoch window *w* is only consumed at the start of window
    *w+1*, and drains touch only egress queues while fills touch only
    ingress queues (disjoint state), so this reorder is bit-safe: every
    tier's drain still precedes its own fill, and every fill still
    precedes the first cycle that could pop its packets.  What it buys:
    all of a boundary's transfers are in flight at once, and each
    transfer's completion is only awaited at fill time (next-window
    start), giving XLA's latency-hiding scheduler the whole boundary to
    hide the transfer latency.
    """
    out: list[Tuple[str, int]] = []
    run: list[int] = []

    def flush() -> None:
        out.extend(("XI", t) for t in run)
        out.extend(("XC", t) for t in run)
        run.clear()

    for op, arg in program:
        if op == "X":
            run.append(arg)
        else:
            flush()
            out.append((op, arg))
    flush()
    return tuple(out)


def _check_stable(step: Any, carry: PyTree) -> None:
    """Abstractly evaluate one cycle and verify the carry contract."""
    out = jax.eval_shape(step, carry)
    ok = jax.tree.structure(carry) == jax.tree.structure(out) and all(
        a.shape == b.shape and a.dtype == b.dtype
        for a, b in zip(jax.tree.leaves(carry), jax.tree.leaves(out))
    )
    if not ok:
        raise TypeError(
            "epoch_loop cycle_fn must preserve the carry's treedef, shapes "
            "and dtypes"
        )


def epoch_loop(
    cycle_fn: Callable[..., PyTree],
    carry: PyTree,
    k_cycles: int,
    *,
    consts: PyTree | None = None,
) -> PyTree:
    """Execute ``k_cycles`` of ``cycle_fn`` as one fused epoch body: the
    carry itself at ``k_cycles=0``, one plain call at 1, a ``fori_loop``
    otherwise.

    ``cycle_fn(carry)`` — or ``cycle_fn(carry, consts)`` when ``consts``
    is given — must return a carry with identical structure/shapes/dtypes
    (checked abstractly up front).
    """
    if k_cycles == 0:
        return carry
    step = (lambda c: cycle_fn(c, consts)) if consts is not None else cycle_fn
    _check_stable(step, carry)
    if k_cycles == 1:
        return step(carry)
    return jax.lax.fori_loop(0, k_cycles, lambda _, c: step(c), carry)


def validate_program(program: Program) -> Tuple[Tuple[str, int], ...]:
    """Normalize + statically validate an op program.

    Checks the op vocabulary and the split-exchange pairing discipline:
    every ``("XI", t)`` must be followed by exactly one ``("XC", t)``
    before the tier issues again, and the program must end with every
    issue committed (a pending transfer crossing the program boundary
    would leak the in-flight slab).
    """
    program = tuple((op, int(arg)) for op, arg in program)
    pending: set = set()
    for op, arg in program:
        if op not in ("C", "X", "XI", "XC"):
            raise ValueError(f"unknown program op {op!r} (C|X|XI|XC)")
        if op == "XI":
            if arg in pending:
                raise ValueError(
                    f"tier {arg} issued twice without an intervening commit")
            pending.add(arg)
        elif op == "XC":
            if arg not in pending:
                raise ValueError(f"tier {arg} committed with no pending issue")
            pending.remove(arg)
        elif op == "X" and arg in pending:
            raise ValueError(
                f"tier {arg} has a serial exchange while a split one is "
                f"pending")
    if pending:
        raise ValueError(
            f"program ends with uncommitted exchanges for tiers "
            f"{sorted(pending)}")
    return program


def epoch_program(
    cycle_fn: Callable[..., PyTree],
    carry: PyTree,
    program: Program,
    *,
    exchange_fn: Callable[..., PyTree] | None = None,
    issue_fn: Callable[..., Tuple[PyTree, PyTree]] | None = None,
    commit_fn: Callable[..., PyTree] | None = None,
    consts: PyTree | None = None,
) -> PyTree:
    """Execute a multi-epoch op program as ONE fused computation.

    ``program`` is a flat op list: ``("C", n)`` steps the cycle body ``n``
    cycles; ``("X", t)`` applies ``exchange_fn`` for tier ``t`` (a pure
    local tier exchange — drain egress queues into slab rows, scatter
    ingress rows back).  This is the multi-epoch generalization of
    :func:`epoch_loop`: a whole K_outer x K_inner span between two
    device-boundary exchanges runs as one body — each ``("C", n)`` block
    as :func:`epoch_loop` runs it, each exchange inline between them — so
    the register/queue state never leaves the traced computation between
    the inner epochs and local tier boundaries it contains.

    Split ops ``("XI", t)`` / ``("XC", t)`` (see :func:`overlap_program`)
    run the exchange in two phases: ``issue_fn(carry, t[, consts]) ->
    (carry, pending)`` drains and starts the transfer, and
    ``commit_fn(carry, t, pending[, consts]) -> carry`` finishes it and
    fills.  The pending pytree is threaded between the two phases as
    ordinary values, so every op emitted between issue and commit is
    data-independent of the in-flight slab and XLA's latency-hiding
    scheduler is free to overlap the transfer with it.

    ``cycle_fn``, ``exchange_fn``, and the issue/commit round trip must
    preserve the carry's treedef/shapes/dtypes (checked abstractly up
    front).
    """
    program = validate_program(program)
    if any(op == "X" for op, _ in program) and exchange_fn is None:
        raise ValueError("program has ('X', t) ops but no exchange_fn")
    if any(op in ("XI", "XC") for op, _ in program) and (
            issue_fn is None or commit_fn is None):
        raise ValueError(
            "program has split ('XI'/'XC') ops but no issue_fn/commit_fn")
    if not program:
        return carry
    step = (lambda c: cycle_fn(c, consts)) if consts is not None else cycle_fn
    _check_stable(step, carry)
    for t in sorted({arg for op, arg in program if op == "X"}):
        _check_stable(
            (lambda c, _t=t: exchange_fn(c, _t, consts)) if consts is not None
            else (lambda c, _t=t: exchange_fn(c, _t)),
            carry,
        )
    for t in sorted({arg for op, arg in program if op == "XI"}):
        def _roundtrip(c, _t=t):
            if consts is not None:
                c2, pend = issue_fn(c, _t, consts)
                return commit_fn(c2, _t, pend, consts)
            c2, pend = issue_fn(c, _t)
            return commit_fn(c2, _t, pend)
        _check_stable(_roundtrip, carry)
    out = carry
    pending: dict = {}
    for op, arg in program:
        if op == "C":
            if arg == 1:
                out = step(out)
            elif arg > 1:
                out = jax.lax.fori_loop(0, arg, lambda _, c: step(c), out)
        elif op == "X":
            out = (exchange_fn(out, arg, consts) if consts is not None
                   else exchange_fn(out, arg))
        elif op == "XI":
            out, pending[arg] = (
                issue_fn(out, arg, consts) if consts is not None
                else issue_fn(out, arg))
        else:  # "XC"
            out = (commit_fn(out, arg, pending.pop(arg), consts)
                   if consts is not None
                   else commit_fn(out, arg, pending.pop(arg)))
    return out
