"""Performance-measurement model (paper §II-C) and rate control.

The paper measures performance *approximately* in an unsynchronized modular
simulation by (a) matching wall-clock rate ratios to simulated-clock rate
ratios, and (b) keeping wall rates low enough that inter-simulator latency
T_comm is negligible:

    N_meas = N * (F_A_wall / F_B_wall)
           + 2 * T_comm * F_A_wall
           + (N_RX + N_TX) * (1 + F_A_wall / F_B_wall)

In our bulk-synchronous adaptation, rate control is **deterministic**: block
i is stepped on cycles divisible by ``divider_i``, so
``F_i_sim = F_base / divider_i`` holds *exactly* (the paper's sleep-based
controller only achieves this in expectation).  The T_comm nonideality maps
to the epoch length K: a packet crossing a granule boundary waits up to K
cycles, so for a round trip ``T_comm ≈ K / F_wall`` and the error term
``2*T_comm*F_A_wall`` becomes ``≈ 2*K`` cycles per boundary crossing — a
*bound*, not a distribution.  ``examples/systolic_matmul.py`` sweeps K
(the accuracy-vs-rate table of Fig. 15).
"""
from __future__ import annotations

import math
from typing import Sequence


def n_meas_ideal(n_cycles: float, f_a_sim: float, f_b_sim: float) -> float:
    """Ideal measured processing delay (cycles of A's clock)."""
    return n_cycles * f_a_sim / f_b_sim


def n_meas_actual(
    n_cycles: float,
    f_a_wall: float,
    f_b_wall: float,
    t_comm: float,
    n_rx: int = 1,
    n_tx: int = 1,
) -> float:
    """Paper §II-C equation for the *observed* processing delay."""
    ratio = f_a_wall / f_b_wall
    return n_cycles * ratio + 2.0 * t_comm * f_a_wall + (n_rx + n_tx) * (1.0 + ratio)


def max_wall_rate(n_meas_ideal_cycles: float, t_comm: float, rel_err: float = 0.05) -> float:
    """Largest F_A_wall for which the T_comm term stays under ``rel_err``.

    From F_A_wall << N_ideal / (2*T_comm): we return the rate at which the
    communication term equals ``rel_err * N_ideal``.
    """
    return rel_err * n_meas_ideal_cycles / (2.0 * t_comm)


def bsp_error_bound(k_epoch: int, boundary_crossings: int, n_ideal_cycles: float) -> float:
    """Deterministic relative-error bound for epoch-batched simulation.

    Each granule-boundary crossing on the measured path adds at most
    ``k_epoch`` cycles of waiting (the packet arrives just after an
    exchange); backpressure can reflect it once more, hence the factor 2
    (the paper's 2*T_comm term).
    """
    return 2.0 * k_epoch * boundary_crossings / max(n_ideal_cycles, 1.0)


# -- tiered (hierarchical-partition) accounting, DESIGN.md §3/§5 -------------

def tier_periods(k_tiers: Sequence[int]) -> list[int]:
    """Cycles between tier-t synchronizations for a nested epoch schedule.

    ``k_tiers`` lists per-tier rates outermost first (matching
    ``graph.Tier``): the innermost rate is local cycles per innermost
    round, each outer rate is sub-rounds per round.  Tier t's boundary
    channels are exchanged every ``prod(k_tiers[t:])`` cycles — its T_comm.
    """
    periods, acc = [], 1
    for k in reversed(list(k_tiers)):
        acc *= int(k)
        periods.append(acc)
    return list(reversed(periods))


def tiered_comm_cycles(
    k_tiers: Sequence[int], crossings_per_tier: Sequence[int]
) -> float:
    """Total communication-nonideality cycles on a measured path.

    A tier-t crossing waits up to ``period_t`` cycles for its exchange and
    backpressure can reflect it once (the paper's 2*T_comm term), so each
    contributes ``<= 2 * period_t`` cycles.
    """
    periods = tier_periods(k_tiers)
    if len(crossings_per_tier) != len(periods):
        raise ValueError(
            f"{len(periods)} tiers but {len(crossings_per_tier)} crossing counts"
        )
    return sum(2.0 * p * x for p, x in zip(periods, crossings_per_tier))


def n_meas_actual_tiered(
    n_cycles: float,
    f_a_wall: float,
    f_b_wall: float,
    k_tiers: Sequence[int],
    crossings_per_tier: Sequence[int],
    n_rx: int = 1,
    n_tx: int = 1,
) -> float:
    """§II-C observed delay with the T_comm term split per partition tier.

    The flat model folds all boundary latency into one ``2*T_comm*F_wall``
    term; under a hierarchical partition a path may cross both fast (ICI)
    and slow (DCI) tiers, and the slow tier's longer sync period dominates.
    Feeding the per-tier sum through the same equation keeps the flat
    single-tier case identical to ``n_meas_actual``.
    """
    ratio = f_a_wall / f_b_wall
    comm = tiered_comm_cycles(k_tiers, crossings_per_tier)
    return n_cycles * ratio + comm + (n_rx + n_tx) * (1.0 + ratio)


def bsp_error_bound_tiered(
    k_tiers: Sequence[int],
    crossings_per_tier: Sequence[int],
    n_ideal_cycles: float,
) -> float:
    """Per-tier generalization of ``bsp_error_bound``: each tier-t crossing
    adds at most ``2 * period_t`` cycles.  Reduces to the flat bound for a
    single tier."""
    return tiered_comm_cycles(k_tiers, crossings_per_tier) / max(
        n_ideal_cycles, 1.0
    )


# -- signature-batched dispatch accounting (ISSUE 6, DESIGN.md §Perf) --------

def batched_epoch_time(
    batch: int, t_step: float, t_dispatch: float, pad_factor: float = 1.0
) -> float:
    """Wall time for ONE vmapped dispatch stepping ``batch`` same-signature
    granules: the per-dispatch overhead (trace/launch/coordination) is paid
    once and the per-granule compute ``batch`` times.  ``pad_factor >= 1``
    models heterogeneous batching, where every member is padded to the
    largest signature in the stack and steps ``pad_factor * t_step``."""
    return t_dispatch + batch * t_step * pad_factor


def unbatched_epoch_time(batch: int, t_step: float, t_dispatch: float) -> float:
    """Wall time for ``batch`` separate per-granule dispatches."""
    return batch * (t_dispatch + t_step)


def dispatch_amortization(
    batch: int, t_step: float, t_dispatch: float, pad_factor: float = 1.0
) -> float:
    """Predicted speedup of signature-batched over per-granule dispatch:

        S(B) = B * (t_disp + t_step) / (t_disp + B * t_step * pad)

    S(1) = 1 for pad = 1 (batching a single granule is free), and
    S -> (t_disp + t_step) / (t_step * pad) as B -> inf: the per-dispatch
    overhead amortizes away and only the padding waste remains."""
    return unbatched_epoch_time(batch, t_step, t_dispatch) / batched_epoch_time(
        batch, t_step, t_dispatch, pad_factor
    )


def fit_dispatch_overhead(
    t_unbatched: float, t_batched: float, batch: int
) -> tuple[float, float]:
    """Recover ``(t_step, t_dispatch)`` from ONE measured A/B pair.

    Inverts the two-equation model ``t_unbatched = B*(t_disp + t_step)``,
    ``t_batched = t_disp + B*t_step`` (pad = 1); validate it against a
    second, differently-shaped measured pair.  Degenerate measurements
    (batched slower than unbatched) clamp to t_disp = 0."""
    if batch < 2:
        raise ValueError("need batch >= 2 to separate t_step from t_dispatch")
    t_disp = max((t_unbatched - t_batched) / (batch - 1), 0.0)
    t_step = max((t_batched - t_disp) / batch, 0.0)
    return t_step, t_disp


def batching_crossover(
    t_step: float, t_dispatch: float, pad_factor: float
) -> float:
    """Smallest batch size B at which batching WINS (S(B) > 1) despite a
    ``pad_factor`` padding waste; ``inf`` when padding always loses.

    From B*(t_disp + t_step) > t_disp + B*t_step*pad:
    batching wins iff the amortized dispatch saving outruns the padding
    waste — when ``t_step * pad >= t_disp + t_step`` it never does."""
    gain = t_dispatch + t_step - t_step * pad_factor
    if gain <= 0.0:
        return math.inf
    return max(t_dispatch / gain, 1.0)


# -- overlapped exchange accounting (ISSUE 7, DESIGN.md §Perf) ---------------

def serial_epoch_time(t_step: float, t_comm: float,
                      t_residual: float = 0.0) -> float:
    """Wall time for one epoch under the serial schedule: the exchange
    (drain + transfer + fill) strictly follows the window's compute, so
    the two costs add.  ``t_residual`` is the schedule-independent part
    (dispatch, host work) paid either way."""
    return t_step + t_comm + t_residual


def overlapped_epoch_time(t_step: float, t_comm: float,
                          t_residual: float = 0.0) -> float:
    """Wall time for one epoch under the split issue/commit schedule.

    Transfers issued at window end complete under the next window's
    compute, so the additive ``t_step + t_comm`` becomes
    ``max(t_step, t_comm)``: whichever of compute and communication is
    longer sets the pace and fully hides the other.  ``t_residual``
    collects what neither phase can hide — the drain/fill bookkeeping at
    the sync point and per-dispatch overhead — and is what
    ``fit_overlap_residual`` recovers from a measured row."""
    return max(t_step, t_comm) + t_residual


def overlap_fraction(t_step: float, t_comm: float) -> float:
    """Fraction of the serial epoch the split schedule can hide:
    ``min(T_step, T_comm) / (T_step + T_comm)`` — 0 when either phase is
    empty (nothing to overlap), 1/2 at perfect balance (the best case:
    half the serial time disappears)."""
    tot = t_step + t_comm
    if tot <= 0.0:
        return 0.0
    return min(t_step, t_comm) / tot


def overlap_speedup(t_step: float, t_comm: float,
                    t_residual: float = 0.0) -> float:
    """Predicted serial/overlapped epoch-time ratio (>= 1; equals
    ``1 / (1 - overlap_fraction)`` when ``t_residual`` is 0)."""
    over = overlapped_epoch_time(t_step, t_comm, t_residual)
    if over <= 0.0:
        return 1.0
    return serial_epoch_time(t_step, t_comm, t_residual) / over


def fit_overlap_residual(t_step: float, t_comm: float,
                         t_overlapped_meas: float) -> float:
    """Recover ``t_residual`` from ONE measured overlapped epoch time and
    the serial run's phase split (step vs drain+transfer+fill).

    Inverts ``t_meas = max(t_step, t_comm) + residual``; clamped at 0 for
    a measurement faster than the model floor (timer noise).  Fit the
    residual on one configuration and predict the others' overlapped
    times with it — the residual absorbs whatever fraction of the
    exchange the backend's scheduler failed to hide, so the model's claim
    is that the residual is a stable per-configuration constant, not
    that overlap is perfect."""
    return max(t_overlapped_meas - max(t_step, t_comm), 0.0)


def dividers_for_rates(f_sims: Sequence[float]) -> list[int]:
    """Clock dividers that realize simulated-frequency ratios exactly.

    Given per-block simulated frequencies, returns integer dividers
    ``d_i`` with ``F_i = F_base / d_i`` where ``F_base = lcm-normalized``.
    Frequencies must be rationally related; we scale to integers first.
    """
    if not f_sims:
        return []
    # Scale to integers (handle floats like 2.5 GHz by rationalizing).
    scaled = [int(round(f * 1_000_000)) for f in f_sims]
    g = 0
    for s in scaled:
        g = math.gcd(g, s)
    units = [s // g for s in scaled]
    l = 1
    for u in units:
        l = l * u // math.gcd(l, u)
    return [l // u for u in units]
