"""Energy model — paper §III-C, equation (15).

E = FLOPs x e_flop + M x e_byte   (joules per step / per token)

``step_energy`` is the raw equation over any (FLOPs, bytes) pair;
``energy`` applies it to a full ``Analysis``; ``serve_energy_per_token``
is the serving form the continuous-batching predictor uses (one
scheduler iteration's dynamic energy plus the board's static draw over
the iteration, divided by the tokens the iteration commits) — the
number the paper's 35-50% INT4 reduction band is asserted against
(tests/test_analytical.py).  The static term matters: dynamic INT4
energy drops near the byte ratio (~8x), but the board burns
``p_static`` watts for the whole step either way, which is exactly why
measured edge savings sit at 35-50% rather than 80%+.
"""
from __future__ import annotations

from dataclasses import dataclass

from repro.core.analytical import Analysis
from repro.core.hardware import HardwareSpec
from repro.core.precision import PrecisionSpec


@dataclass
class EnergyBreakdown:
    compute_j: float
    data_j: float
    static_j: float = 0.0

    @property
    def total(self) -> float:
        return self.compute_j + self.data_j + self.static_j


def step_energy(flops: float, bytes_moved: float, hw: HardwareSpec,
                precision: PrecisionSpec,
                duration_s: float = 0.0) -> EnergyBreakdown:
    """Eq. (15) over one step's FLOP/byte counts.  Low-bit compute
    scales e_flop by bits/32 down to the int8 floor (INT4 executes on
    the int8 ALU datapath on the paper's targets) — the INT4 dynamic
    saving then arises mostly from fewer bytes moved.  ``duration_s``
    adds the static board draw over the step (0 = dynamic only)."""
    flop_scale = min(1.0, max(precision.bits, 8) / 32.0)
    return EnergyBreakdown(flops * hw.e_flop * flop_scale,
                           bytes_moved * hw.e_byte,
                           hw.p_static * duration_s)


def energy(an: Analysis, hw: HardwareSpec, precision: PrecisionSpec) -> EnergyBreakdown:
    """Eq. (15) for one analyzed cell (dynamic terms only — the
    paper-faithful form)."""
    bytes_moved = (an.params * precision.bytes_per_param
                   + an.memory.kv_cache + an.memory.activations)
    return step_energy(an.step_flops, bytes_moved, hw, precision)


def serve_energy_per_token(flops: float, bytes_moved: float,
                           iteration_s: float, tokens: float,
                           hw: HardwareSpec,
                           precision: PrecisionSpec) -> float:
    """Joules per committed token of one continuous-batching iteration:
    dynamic eq.-(15) energy plus the static board draw for the
    iteration's duration, amortized over every token the iteration
    emits.  Batching and speculative decoding both lower this by
    raising ``tokens`` while the weight-stream term stays fixed."""
    e = step_energy(flops, bytes_moved, hw, precision,
                    duration_s=iteration_s)
    return e.total / max(1e-12, tokens)
