"""Generalized per-granule fused-epoch kernel (§Perf).

``systolic_step`` fuses ONE hand-written block type (the systolic MAC
cell) into a Pallas kernel.  This module generalizes that move to ANY
lowered ``ChannelGraph`` granule: the fused engine
(``repro.core.fused``) hands over a pure single-cycle function — depth-1
register channels + boundary queues + the vmapped block steps — and
``epoch_loop`` executes the whole K-cycle tier-inner epoch as one fused
computation instead of ~10 interpreted queue ops per cycle:

  * ``mode="xla"`` — one ``fori_loop`` whose carry is the compact
    register-file state (the deep queue buffers and lookup tables stay
    out of the carry).  One jitted XLA computation per epoch; the default
    on every backend.
  * ``mode="unroll"`` — the cycle body is Python-unrolled into a single
    straight-line computation.  Opt-in: on XLA:CPU the loop form measures
    ~3x faster, but the unrolled form can win where cross-cycle fusion
    pays (small K, wide granules).
  * ``mode="pallas"`` — the same body wrapped in ONE ``pallas_call`` so
    the epoch executes with the granule state resident in kernel memory.
    Opt-in, and CPU-only for now: ``interpret=True`` runs it under the
    Pallas interpreter.  The TPU compiler refuses it — the cycle body's
    row gathers (``fronts[rxm]``) lower to N-D gathers that Mosaic does
    not support ("Only 2D gather is supported"), and the rank-0 cycle
    counter is not a legal block — so ``fuse="pallas"`` on a TPU fails
    at compile time and never falls back (``tests/test_tpu_compile.py``
    rehearses that refusal for a v5e).

Contract for ``cycle_fn``: pytree -> pytree with identical treedef,
shapes, and dtypes (the fused engine's local cycle satisfies it; the
wrapper checks and raises otherwise).
"""
from __future__ import annotations

import os
from typing import Any, Callable, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PyTree = Any

#: Op list executed by :func:`epoch_program`: ``("C", n)`` runs ``n``
#: cycles of the cycle body; ``("X", t)`` runs the caller's exchange
#: function for tier ``t``; ``("XI", t)`` / ``("XC", t)`` are the split
#: form of the same exchange — issue (drain + start transfer) and commit
#: (finish transfer + fill) — so intervening ops overlap the transfer.
#: The whole program is ONE fused computation.
Program = Sequence[Tuple[str, int]]

_MODES = ("auto", "unroll", "xla", "pallas")


def resolve_mode(mode: str = "auto") -> str:
    """Pick the execution strategy for a K-cycle epoch body.

    The environment variable ``REPRO_EPOCH_MODE`` (one of
    ``auto|unroll|xla|pallas``) overrides a caller-passed ``"auto"`` so CI
    can force the pallas body (under interpret, see
    :func:`resolve_interpret`) without threading a flag through every
    engine.  An explicit non-"auto" argument always wins over the env.

    "auto" resolves to the ``fori_loop`` body on every backend: the
    Pallas body does not compile for a TPU (see the module docstring), and
    on XLA:CPU the loop beats full unrolling ~3x (the straight-line body
    defeats the emitter's locality), so "unroll" is opt-in only.
    """
    if mode == "auto":
        env = os.environ.get("REPRO_EPOCH_MODE", "auto").strip().lower()
        if env and env != "auto":
            if env not in _MODES:
                raise ValueError(
                    f"REPRO_EPOCH_MODE={env!r} not in {_MODES}")
            return env
    return "xla" if mode == "auto" else mode


def resolve_interpret(interpret: Any = "auto") -> bool:
    """Resolve the pallas ``interpret`` knob.

    On a TPU the kernel runs natively unless the caller passes ``True``
    itself: neither ``"auto"`` nor the environment can put the chip into
    the interpreter.  Elsewhere ``"auto"`` means the Pallas interpreter
    (so ``mode="pallas"`` is never dead code on CPU CI), and the env
    override ``REPRO_PALLAS_INTERPRET=0|1`` beats the argument.
    """
    if jax.default_backend() == "tpu":
        return interpret is True
    env = os.environ.get("REPRO_PALLAS_INTERPRET", "").strip()
    if env:
        return env not in ("0", "false", "False")
    if interpret == "auto":
        return True
    return bool(interpret)


def resolve_overlap(overlap: Any = "auto") -> bool:
    """Resolve the overlapped-exchange knob (split issue/commit phases).

    Same precedence as :func:`resolve_mode`: an explicit non-"auto"
    argument (bool, or one of ``on|off|1|0|true|false``) always wins; the
    environment variable ``REPRO_OVERLAP`` overrides a caller-passed
    ``"auto"`` so CI can flip every engine to the split schedule without
    threading a flag through; "auto" resolves to off — the serial
    schedule stays the default, and the split schedule is bit-identical
    by construction so flipping it per-run is always safe.
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
    start), giving the scheduler/DMA engine the whole boundary to hide
    the transfer latency.
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


def pallas_program(
    cycle_fn: Callable[..., PyTree],
    carry: PyTree,
    program: Program,
    *,
    exchange_fn: Callable[..., PyTree] | None = None,
    issue_fn: Callable[..., Tuple[PyTree, PyTree]] | None = None,
    commit_fn: Callable[..., PyTree] | None = None,
    consts: PyTree | None = None,
    interpret: Any = "auto",
) -> PyTree:
    """Run a ``("C", n)`` / ``("X", t)`` op program inside ONE
    ``pallas_call`` — the resident multi-epoch kernel.

    The carry pytree is flattened into kernel refs; the kernel loads every
    leaf once, then walks the whole program — every inner-epoch cycle
    block as a ``fori_loop`` of the cycle body and every tier exchange as
    an inline call to ``exchange_fn`` — with the granule state resident in
    kernel memory (VMEM on TPU) for the program's whole lifetime.  The
    state touches HBM exactly twice regardless of how many epochs and
    tier boundaries the program spans; ``pallas_call`` stages the
    HBM<->VMEM slab transfers at kernel entry/exit asynchronously, so the
    boundary staging overlaps the surrounding dispatch.  ``consts``
    (lookup tables) are extra read-only refs.  Zero-size leaves carry no
    data and ``pallas_call`` rejects them, so they are filtered out and
    reconstructed inside the kernel.

    Split ops ``("XI", t)`` / ``("XC", t)`` double-buffer the exchange
    slabs: the issued slab pytree is written into one of two VMEM staging
    buffers per tier and moved by an async DMA copy that is started at
    issue and only awaited at commit, so every op between the two phases
    — the other tiers' issues and fills, and on TPU the next window's
    step loop — runs while the copy is in flight.  Two slots per tier
    (selected by a compile-time firing counter) let a second issue start
    before the previous window's copy is awaited.
    """
    c_leaves, c_def = jax.tree.flatten(carry)
    k_leaves, k_def = jax.tree.flatten(consts if consts is not None else ())
    c_live = [i for i, l in enumerate(c_leaves) if l.size > 0]
    k_live = [i for i, l in enumerate(k_leaves) if l.size > 0]
    nc, nk = len(c_live), len(k_live)

    def rebuild(live_vals, idx, template, treedef):
        full = [jnp.zeros(l.shape, l.dtype) for l in template]
        for i, v in zip(idx, live_vals):
            full[i] = v
        return jax.tree.unflatten(treedef, full)

    def call_with_consts(fn, *a, consts_v):
        return fn(*a, consts_v) if consts is not None else fn(*a)

    # Per-tier staging for split exchanges: the pending pytree's shape is
    # derived abstractly, then each live leaf gets (src, dst) VMEM staging
    # buffers with two slots and a 2-slot DMA semaphore.
    split_tiers = sorted({arg for op, arg in program if op == "XI"})
    scratch_shapes: list = []
    stage_info: dict = {}
    for t in split_tiers:
        _, p_shape = jax.eval_shape(
            lambda c, _t=t: call_with_consts(issue_fn, c, _t, consts_v=consts),
            carry)
        p_leaves, p_def = jax.tree.flatten(p_shape)
        p_live = [i for i, l in enumerate(p_leaves) if l.size > 0]
        base = len(scratch_shapes)
        for i in p_live:
            leaf = p_leaves[i]
            scratch_shapes.append(pltpu.VMEM((2,) + leaf.shape, leaf.dtype))
            scratch_shapes.append(pltpu.VMEM((2,) + leaf.shape, leaf.dtype))
            scratch_shapes.append(pltpu.SemaphoreType.DMA((2,)))
        stage_info[t] = (p_leaves, p_def, p_live, base)

    def kernel(*refs):
        cvals = tuple(r[...] for r in refs[:nc])
        consts_v = rebuild(
            tuple(r[...] for r in refs[nc:nc + nk]), k_live, k_leaves, k_def
        )
        scratch = refs[nc + nk + nc:]

        def live_out(out):
            out_leaves = jax.tree.leaves(out)
            return tuple(out_leaves[i] for i in c_live)

        def body(_, vs):
            c = rebuild(vs, c_live, c_leaves, c_def)
            out = cycle_fn(c, consts_v) if consts is not None else cycle_fn(c)
            return live_out(out)

        def stage_refs(t, j):
            base = stage_info[t][3]
            return scratch[base + 3 * j], scratch[base + 3 * j + 1], \
                scratch[base + 3 * j + 2]

        fired = {t: 0 for t in split_tiers}
        pending_slot: dict = {}
        for op, arg in program:
            if op == "C":
                if arg == 1:
                    cvals = body(0, cvals)
                elif arg > 1:
                    cvals = jax.lax.fori_loop(0, arg, body, cvals)
            elif op == "X":
                c = rebuild(cvals, c_live, c_leaves, c_def)
                out = (exchange_fn(c, arg, consts_v) if consts is not None
                       else exchange_fn(c, arg))
                cvals = live_out(out)
            elif op == "XI":
                c = rebuild(cvals, c_live, c_leaves, c_def)
                out, pend = (issue_fn(c, arg, consts_v) if consts is not None
                             else issue_fn(c, arg))
                cvals = live_out(out)
                slot = fired[arg] % 2
                fired[arg] += 1
                pending_slot[arg] = slot
                p_vals = jax.tree.leaves(pend)
                for j, i in enumerate(stage_info[arg][2]):
                    src, dst, sem = stage_refs(arg, j)
                    src[slot] = p_vals[i]
                    pltpu.make_async_copy(
                        src.at[slot], dst.at[slot], sem.at[slot]).start()
            else:  # "XC"
                slot = pending_slot.pop(arg)
                p_leaves_t, p_def_t, p_live_t, _ = stage_info[arg]
                vals = []
                for j in range(len(p_live_t)):
                    src, dst, sem = stage_refs(arg, j)
                    pltpu.make_async_copy(
                        src.at[slot], dst.at[slot], sem.at[slot]).wait()
                    vals.append(dst[slot])
                pend = rebuild(vals, p_live_t, p_leaves_t, p_def_t)
                c = rebuild(cvals, c_live, c_leaves, c_def)
                out = (commit_fn(c, arg, pend, consts_v)
                       if consts is not None else commit_fn(c, arg, pend))
                cvals = live_out(out)
        for r, v in zip(refs[nc + nk:nc + nk + nc], cvals):
            r[...] = v

    outs = pl.pallas_call(
        kernel,
        out_shape=tuple(
            jax.ShapeDtypeStruct(c_leaves[i].shape, c_leaves[i].dtype)
            for i in c_live
        ),
        scratch_shapes=scratch_shapes,
        interpret=resolve_interpret(interpret),
    )(*(c_leaves[i] for i in c_live), *(k_leaves[i] for i in k_live))
    return rebuild(list(outs), c_live, c_leaves, c_def)


def pallas_epoch(
    cycle_fn: Callable[..., PyTree],
    carry: PyTree,
    k_cycles: int,
    *,
    consts: PyTree | None = None,
    interpret: Any = False,
) -> PyTree:
    """Run ``k_cycles`` of ``cycle_fn`` inside ONE ``pallas_call``.

    The single-epoch special case of :func:`pallas_program` (a program of
    one ``("C", k_cycles)`` op); see there for the memory contract.
    """
    return pallas_program(
        cycle_fn, carry, (("C", k_cycles),), consts=consts,
        interpret=interpret,
    )


def epoch_loop(
    cycle_fn: Callable[..., PyTree],
    carry: PyTree,
    k_cycles: int,
    *,
    consts: PyTree | None = None,
    mode: str = "auto",
    interpret: Any = False,
) -> PyTree:
    """Execute ``k_cycles`` of ``cycle_fn`` as one fused epoch body.

    ``cycle_fn(carry)`` — or ``cycle_fn(carry, consts)`` when ``consts``
    is given — must return a carry with identical structure/shapes/dtypes
    (checked abstractly up front on every mode).
    """
    if k_cycles == 0:
        return carry
    step = (lambda c: cycle_fn(c, consts)) if consts is not None else cycle_fn
    _check_stable(step, carry)
    mode = resolve_mode(mode)
    if mode == "unroll":
        out = carry
        for _ in range(k_cycles):
            out = step(out)
        return out
    if mode == "xla":
        if k_cycles == 1:
            return step(carry)
        return jax.lax.fori_loop(0, k_cycles, lambda _, c: step(c), carry)
    if mode == "pallas":
        return pallas_epoch(
            cycle_fn, carry, k_cycles, consts=consts, interpret=interpret
        )
    raise ValueError(f"unknown epoch mode {mode!r} (auto|unroll|xla|pallas)")


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
    mode: str = "auto",
    interpret: Any = "auto",
) -> PyTree:
    """Execute a multi-epoch op program as ONE fused computation.

    ``program`` is a flat op list: ``("C", n)`` steps the cycle body ``n``
    cycles; ``("X", t)`` applies ``exchange_fn`` for tier ``t`` (a pure
    local tier exchange — drain egress queues into slab rows, scatter
    ingress rows back).  This is the resident-kernel generalization of
    :func:`epoch_loop`: a whole K_outer x K_inner span between two
    device-boundary exchanges runs as one body, so under ``mode="pallas"``
    the register/queue state stays resident in VMEM across every inner
    epoch and local tier boundary it contains.  The xla/unroll modes
    execute the *same* op sequence (bit-exact twins for CPU CI), just as
    jitted XLA loops instead of one kernel.

    Split ops ``("XI", t)`` / ``("XC", t)`` (see :func:`overlap_program`)
    run the exchange in two phases: ``issue_fn(carry, t[, consts]) ->
    (carry, pending)`` drains and starts the transfer, and
    ``commit_fn(carry, t, pending[, consts]) -> carry`` finishes it and
    fills.  In the xla/unroll lowerings the pending pytree is threaded
    between the two phases as ordinary values, so every op emitted between
    issue and commit is data-independent of the in-flight slab and XLA's
    latency-hiding scheduler is free to overlap the transfer with it; the
    pallas lowering stages the slab through double-buffered VMEM with an
    async copy (started at issue, awaited at commit).  All lowerings
    remain bit-exact twins.

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
    mode = resolve_mode(mode)
    if mode == "pallas":
        return pallas_program(
            cycle_fn, carry, program, exchange_fn=exchange_fn,
            issue_fn=issue_fn, commit_fn=commit_fn, consts=consts,
            interpret=interpret,
        )
    if mode not in ("xla", "unroll"):
        raise ValueError(f"unknown epoch mode {mode!r} (auto|unroll|xla|pallas)")
    out = carry
    pending: dict = {}
    for op, arg in program:
        if op == "C":
            if mode == "unroll":
                for _ in range(arg):
                    out = step(out)
            elif arg == 1:
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
