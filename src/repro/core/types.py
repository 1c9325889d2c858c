"""Core datatypes for the ECC / Li-GD NOMA split-inference planner.

Everything is a registered pytree so it can flow through jit/vmap/scan.
Units:
  gains          -- linear power gains |h|^2 (dimensionless, includes path loss)
  powers         -- Watts
  bandwidth      -- Hz
  workloads f    -- FLOPs
  data sizes w,m -- bits
  compute c      -- FLOP/s
  energy coeff   -- xi * c^2 = Joules per FLOP (DVFS-style E ~ xi c^2 f)
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

Array = jax.Array

# ln(2), shared by every rate computation (bit/s = Hz * ln(1+SINR)/LOG2).
# Single definition: core.channel, kernels.ops and kernels.ref import it.
LOG2 = 0.6931471805599453


def _register(cls):
    fields = [f.name for f in dataclasses.fields(cls)]
    meta = [f.name for f in dataclasses.fields(cls) if f.metadata.get("static", False)]
    data = [n for n in fields if n not in meta]
    jax.tree_util.register_dataclass(cls, data_fields=data, meta_fields=meta)
    return cls


def static_field(**kw):
    return dataclasses.field(metadata={"static": True}, **kw)


@_register
@dataclasses.dataclass(frozen=True)
class RadioConstants:
    """Paper Sec. VI.A constants (configurable)."""

    bandwidth_up_hz: float = 10e6
    bandwidth_dn_hz: float = 10e6
    noise_psd_w_per_hz: float = 10 ** ((-174.0 - 30.0) / 10.0)  # -174 dBm/Hz
    p_up_min_w: float = 1e-3          # 0 dBm
    p_up_max_w: float = 0.3162        # 25 dBm (paper)
    p_dn_min_w: float = 0.1
    p_dn_max_w: float = 10.0
    beta_min: float = 1e-3            # numerical floor for relaxed subchannel share
    path_loss_exp: float = 5.0        # paper
    cell_radius_m: float = 250.0


@_register
@dataclasses.dataclass(frozen=True)
class ComputeConstants:
    """Device / edge compute + energy model constants."""

    c_device: float = 2.5e10          # FLOP/s of the mobile device
    c_min_edge: float = 2.5e10        # FLOP/s of one minimum edge compute unit
    r_min: float = 1.0
    r_max: float = 16.0
    lam_exponent: float = 0.85        # lambda(r) = r^0.85 (multicore nonlinearity, [15])
    xi_device: float = 1.3e-31        # J/FLOP = xi * c^2  (~2 W mobile SoC)
    xi_edge: float = 4.0e-33          # quadratic in allocated speed (paper eq. 16)
    phi_device: float = 1.0           # paper's cycles/bit factor, folded to 1 (see DESIGN)
    phi_edge: float = 1.0


@_register
@dataclasses.dataclass(frozen=True)
class NetworkEnv:
    """A realization of the NOMA radio network.

    Shapes: U users, N APs, M subchannels.
      g_up[u, n, m]  uplink |h|^2 from user u to AP n on subchannel m
      g_dn[n, u, m]  downlink |h|^2 from AP n to user u on subchannel m
      ap[u]          nearest-AP association (int32)
    """

    g_up: Array
    g_dn: Array
    ap: Array
    radio: RadioConstants
    comp: ComputeConstants

    @property
    def n_users(self) -> int:
        return self.g_up.shape[0]

    @property
    def n_aps(self) -> int:
        return self.g_up.shape[1]

    @property
    def n_sub(self) -> int:
        return self.g_up.shape[2]

    @property
    def noise_up(self) -> float:
        return self.radio.noise_psd_w_per_hz * self.radio.bandwidth_up_hz / self.n_sub

    @property
    def noise_dn(self) -> float:
        return self.radio.noise_psd_w_per_hz * self.radio.bandwidth_dn_hz / self.n_sub

    def own_gain_up(self) -> Array:  # (U, M)
        return jnp.take_along_axis(
            self.g_up, self.ap[:, None, None], axis=1
        ).squeeze(1)

    def own_gain_dn(self) -> Array:  # (U, M)
        g = jnp.swapaxes(self.g_dn, 0, 1)  # (U, N, M)
        return jnp.take_along_axis(g, self.ap[:, None, None], axis=1).squeeze(1)

    def same_cell(self) -> Array:  # (U, U) bool
        return self.ap[:, None] == self.ap[None, :]


class ProfileShapeError(ValueError):
    """A measured (or otherwise substituted) profile does not match the
    static profile's layer structure; raised at loop start instead of
    failing opaquely inside a jitted planner trace."""


@_register
@dataclasses.dataclass(frozen=True)
class ModelProfile:
    """Per-layer profile of an inference model (device-side units).

    fl[d]       FLOPs of layer d (d = 0..F-1)
    w[s]        bits of the activation produced by layer s (s = 0 is the raw
                input, so splitting at s=0 means full offload; w[F] = 0)
    m_down[s]   bits of the final result sent back down when split at s
                (0 when s == F: nothing was offloaded)
    """

    fl: Array
    w: Array
    m_down: Array
    name: str = static_field(default="model")

    @property
    def n_layers(self) -> int:
        return self.fl.shape[0]

    def validate_like(self, other: "ModelProfile") -> "ModelProfile":
        """Check that ``other`` is drop-in compatible with this profile:
        same layer count, same array shapes/dtypes, and the same static
        name (the name is pytree *metadata*, so a renamed profile would
        silently recompile every planner program that takes it as an
        operand). Returns ``other`` on success; raises ProfileShapeError
        with the offending field named otherwise. Measured-profile loops
        call this once at loop start."""
        if other.n_layers != self.n_layers:
            raise ProfileShapeError(
                f"measured profile has {other.n_layers} layers but the "
                f"static profile '{self.name}' has {self.n_layers}; the "
                "telemetry accumulator must be built from the profile the "
                "planner was constructed with (ModelProfile.like)")
        for field in ("fl", "w", "m_down"):
            a, b = getattr(self, field), getattr(other, field)
            if tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype:
                raise ProfileShapeError(
                    f"measured profile field '{field}' is "
                    f"{tuple(b.shape)}/{b.dtype} but the static profile "
                    f"'{self.name}' expects {tuple(a.shape)}/{a.dtype}; "
                    "a mismatched operand would recompile (or fail inside) "
                    "every compiled planner program")
        if other.name != self.name:
            raise ProfileShapeError(
                f"measured profile is named {other.name!r} but the static "
                f"profile is {self.name!r}; the name is static pytree "
                "metadata, so a rename mints a new jit signature and "
                "recompiles every planner program -- build measured "
                "profiles with ModelProfile.like, which preserves it")
        return other

    def like(self, fl: Array, w: Array, m_down: Array) -> "ModelProfile":
        """A profile with this profile's name and layer structure but new
        per-layer tables (e.g. measured/EMA-smoothed ones). Values are cast
        to the static tables' dtypes (strong-typed: a weak-f32 leaf would
        re-trace the planner once per feedback epoch); shapes are validated
        so a mismatch fails here, not inside a jitted planner trace."""
        made = ModelProfile(
            fl=jnp.asarray(fl, self.fl.dtype),
            w=jnp.asarray(w, self.w.dtype),
            m_down=jnp.asarray(m_down, self.m_down.dtype),
            name=self.name,
        )
        return self.validate_like(made)

    def prefix_flops(self) -> Array:
        """device-side FLOPs for split s = 0..F  (shape F+1)."""
        return jnp.concatenate([jnp.zeros((1,), self.fl.dtype), jnp.cumsum(self.fl)])

    def suffix_flops(self) -> Array:
        """edge-side FLOPs for split s = 0..F  (shape F+1)."""
        total = jnp.sum(self.fl)
        return total - self.prefix_flops()


@_register
@dataclasses.dataclass(frozen=True)
class EccWeights:
    """Per-user tradeoff weights (omega_T + omega_E = 1)."""

    w_T: Array  # (U,)
    w_E: Array  # (U,)


@_register
@dataclasses.dataclass(frozen=True)
class GdConfig:
    step_size: float = static_field(default=5e-3)
    eps: float = static_field(default=1e-5)
    max_iters: int = static_field(default=400)
    # Adam-mode is the beyond-paper optimizer upgrade; "sgd" is paper-faithful.
    optimizer: str = static_field(default="sgd")
    adam_b1: float = static_field(default=0.9)
    adam_b2: float = static_field(default=0.999)
    # First stopping rule (Table I line 6). "pgd" tests the projected-gradient
    # residual ||x - P(x - step_size*g)|| / step_size < eps, which vanishes at
    # a constrained (simplex/box boundary) optimum; "raw" is the paper-parity
    # baseline ||g|| < eps, which never fires on the boundary and silently
    # defers to the looser Gamma/maxdiff rules.
    stop_rule: str = static_field(default="pgd")
    # SINR backend traced into the solver's gradient path ("einsum" |
    # "pallas" | "pallas_interpret"). The Pallas pairwise kernel carries a
    # custom_vjp, so the GD hot loop itself can run stream-tiled at paper
    # scale; "pallas" always compiles for the TPU (it fails elsewhere) and
    # only "pallas_interpret" runs the kernels in the interpreter. Always passed
    # explicitly to utility (never the channel-module global), so compiled
    # solver programs are keyed on -- and immune to -- backend switches.
    sinr_backend: str = static_field(default="einsum")


@_register
@dataclasses.dataclass(frozen=True)
class GdVars:
    """The continuous relaxation optimized by (Li-)GD."""

    beta_up: Array  # (U, M) in simplex rows
    beta_dn: Array  # (U, M)
    p_up: Array     # (U,) Watts
    p_dn: Array     # (U,) Watts
    r: Array        # (U,) edge compute units


@_register
@dataclasses.dataclass(frozen=True)
class SplitPlan:
    """Final discrete plan produced by the planner."""

    s: Array            # () int32 chosen split layer in 0..F
    sub_up: Array       # (U,) int32 chosen uplink subchannel
    sub_dn: Array       # (U,) int32
    p_up: Array         # (U,)
    p_dn: Array         # (U,)
    r: Array            # (U,)
    utility: Array      # () utility at the chosen plan (relaxed)
    per_layer_utility: Array  # (F+1,)
    iters: Array        # (F+1,) GD iterations spent per split point
    rounding_violations: Array  # () count of users whose 0.5-rounding broke (18.e)


def make_weights(n_users: int, w_T: float = 0.5) -> EccWeights:
    t = jnp.full((n_users,), float(w_T))
    return EccWeights(w_T=t, w_E=1.0 - t)


def lam(r: Array, comp: ComputeConstants) -> Array:
    """Multicore speedup lambda(r): monotone, concave (paper Sec III.A.2)."""
    return jnp.power(r, comp.lam_exponent)
