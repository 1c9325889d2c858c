"""In-jit health guards: finiteness/feasibility checks packed into one word.

Every check runs inside an already-compiled program and produces an int32
bit; the bits OR into a single *health word* so the host learns everything
it needs for the degradation ladder from ONE extra scalar per epoch -- the
loop's sync budget stays at PR 8's two scalars plus this word. The
planner-side plan check does not even cost that: it rides the existing
s*-sync as ``(health << PLAN_WORD_SHIFT) | s`` (``plan_word`` /
``split_plan_word``), so a guarded replan still syncs exactly one scalar.

Bit layout (LSB first; 0 = healthy):

  0 plan_utility   plan utility or per-layer utility non-finite
  1 plan_power     power vector non-finite or outside [0, p_max]
  2 plan_alloc     edge compute allocation non-finite or outside [0, r_max]
  3 plan_subch     subchannel index outside [0, M)
  4 profile        measured-profile tables (fl/w/m_down) non-finite
  5 kappa          congestion estimate non-finite or past ``kappa_max``
  6 telemetry      this epoch's observation non-finite
  7 service        this epoch's modeled service times non-finite
  8 edge           the served model's logits this epoch non-finite

Bits 0-3 are planner-side (checked at replan, ``PLAN_MASK``); bits 4-6 are
the telemetry-quarantine trigger (``TELEMETRY_MASK``); bit 7 is
informational (service corruption surfaces in shedding/QoS); bit 8 is set
by the served model's edge program (``online.edge``).
"""
from __future__ import annotations

from typing import TYPE_CHECKING

import jax
import jax.numpy as jnp

from repro.core.types import Array, SplitPlan

if TYPE_CHECKING:  # repro.online imports the loop, which imports this
    # package back -- annotation-only here keeps the import acyclic
    from repro.online.telemetry import Observation, TelemetryState

HEALTH_BITS: dict[str, int] = {
    "plan_utility": 0,
    "plan_power": 1,
    "plan_alloc": 2,
    "plan_subch": 3,
    "profile": 4,
    "kappa": 5,
    "telemetry": 6,
    "service": 7,
    "edge": 8,
}

PLAN_MASK = 0b1111
TELEMETRY_MASK = (1 << HEALTH_BITS["profile"]) | (1 << HEALTH_BITS["kappa"]) \
    | (1 << HEALTH_BITS["telemetry"])

# The planner's packed word: health in the high bits, s* in the low 16.
PLAN_WORD_SHIFT = 16


def _bit(unhealthy: Array, name: str) -> Array:
    return jnp.where(unhealthy, jnp.int32(1 << HEALTH_BITS[name]),
                     jnp.int32(0))


def _all_finite(*xs: Array) -> Array:
    ok = jnp.bool_(True)
    for x in xs:
        ok = ok & jnp.all(jnp.isfinite(x))
    return ok


def plan_health(plan: SplitPlan, *, n_sub: int, p_up_max: float,
                p_dn_max: float, r_max: float, slack: float = 1.05) -> Array:
    """() int32 over bits 0-3. ``slack`` absorbs rounding noise at the box
    boundaries -- the guard exists to catch corruption (NaN/Inf, wildly
    infeasible values), not to re-litigate the solver's projection."""
    bad_util = ~_all_finite(plan.utility, plan.per_layer_utility)
    ok_pow = (_all_finite(plan.p_up, plan.p_dn)
              & jnp.all(plan.p_up >= 0.0)
              & jnp.all(plan.p_up <= p_up_max * slack)
              & jnp.all(plan.p_dn >= 0.0)
              & jnp.all(plan.p_dn <= p_dn_max * slack))
    ok_alloc = (_all_finite(plan.r) & jnp.all(plan.r >= 0.0)
                & jnp.all(plan.r <= r_max * slack))
    ok_sub = (jnp.all((plan.sub_up >= 0) & (plan.sub_up < n_sub))
              & jnp.all((plan.sub_dn >= 0) & (plan.sub_dn < n_sub)))
    return (_bit(bad_util, "plan_utility") | _bit(~ok_pow, "plan_power")
            | _bit(~ok_alloc, "plan_alloc") | _bit(~ok_sub, "plan_subch"))


def telemetry_health(state: TelemetryState, kappa_max: float) -> Array:
    """() int32 over bits 4-5: is the measured profile still a sane planner
    operand? A kappa past ``kappa_max`` is finite but no longer a credible
    congestion estimate (a spiked sample landed) -- quarantine territory."""
    bad_prof = ~_all_finite(state.fl, state.w, state.m_down, state.rate_dn,
                            state.r_units)
    bad_kappa = ~(jnp.isfinite(state.kappa) & (state.kappa <= kappa_max))
    return _bit(bad_prof, "profile") | _bit(bad_kappa, "kappa")


def observation_health(obs: Observation) -> Array:
    """() int32, bit 6: this epoch's telemetry sample arrived intact."""
    bad = ~_all_finite(obs.t_layer, obs.t_up, obs.rate_up, obs.rate_dn,
                       obs.r_units)
    return _bit(bad, "telemetry")


def service_health(service: Array) -> Array:
    """() int32, bit 7: modeled service times are finite."""
    return _bit(~_all_finite(service), "service")


def edge_health(finite: Array) -> Array:
    """() int32, bit 8: the served model's logits this epoch were finite
    (``finite``, a device bool)."""
    return _bit(~finite, "edge")


def pack_health(*words: Array) -> Array:
    """OR component words into the epoch's single health scalar."""
    out = jnp.int32(0)
    for w in words:
        out = out | w
    return out


def plan_word(plan: SplitPlan, *, n_sub: int, p_up_max: float,
              p_dn_max: float, r_max: float) -> Array:
    """() int32 ``(plan_health << PLAN_WORD_SHIFT) | s``: the guarded
    replan's one host sync carries both the re-cut decision and the plan's
    health. s is clamped into the low half-word; a non-finite or negative
    s maps to 0 with the utility bit necessarily set alongside it."""
    h = plan_health(plan, n_sub=n_sub, p_up_max=p_up_max, p_dn_max=p_dn_max,
                    r_max=r_max)
    s = jnp.clip(plan.s.astype(jnp.int32), 0, (1 << PLAN_WORD_SHIFT) - 1)
    return (h << PLAN_WORD_SHIFT) | s


def split_plan_word(word: int) -> tuple[int, int]:
    """Host-side unpack of ``plan_word`` -> (health, s)."""
    w = int(word)
    return w >> PLAN_WORD_SHIFT, w & ((1 << PLAN_WORD_SHIFT) - 1)


def decode_health(word: int) -> dict[str, bool]:
    """Host-side: name -> bit set? (metrics/debugging; never in-jit)."""
    w = int(word)
    return {name: bool(w & (1 << bit)) for name, bit in HEALTH_BITS.items()}


def tree_select(keep_new: Array, new, old):
    """Per-leaf where over matching pytrees: the in-jit quarantine gate
    (corrupt observation -> hold the previous telemetry state)."""
    return jax.tree.map(lambda a, b: jnp.where(keep_new, a, b), new, old)
