"""The least time the chip needs for a piece of the planner's work, and the
table of peaks it is measured against.

The least time of a piece is the larger of the bytes it must move over the
peak memory bandwidth and the operations it must do over the peak rate.
Only what the result requires counts: each gain tensor read once per pass,
each intra-cell SIC pair once (same-cell pairs only, sum over cells of
n_c^2 per subchannel), each operand and result once. So an implementation
that skips work it does not need reads higher, and none reads above 100 %.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

F32 = 4

# Published peaks per chip, keyed by jax's device_kind.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,        # bf16
        "bytes_per_s": 819e9,         # HBM
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
                  "819 GB/s HBM bandwidth per chip",
    },
}


class Peak(NamedTuple):
    flops_per_s: float
    bytes_per_s: float


def peak(device_kind: str) -> Peak:
    """The peaks of ``device_kind``; a device missing from the table is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it to "
                       "perfbench/work.py PEAKS with its source")
    p = PEAKS[device_kind]
    return Peak(p["flops_per_s"], p["bytes_per_s"])


class Sizes(NamedTuple):
    n_users: int
    n_aps: int
    n_sub: int
    cells: tuple[int, ...]       # users served by each AP

    @property
    def sic_pairs(self) -> int:
        """Same-cell (receiver, interferer) pairs, sum over cells of n_c^2."""
        return int(sum(n * n for n in self.cells))


def sizes(n_users: int, n_aps: int, n_sub: int, ap) -> Sizes:
    cells = np.bincount(np.asarray(ap), minlength=n_aps)
    return Sizes(n_users, n_aps, n_sub, tuple(int(c) for c in cells))


def least_s(flops: float, nbytes: float, pk: Peak) -> float:
    return max(flops / pk.flops_per_s, nbytes / pk.bytes_per_s)


def gd_step(z: Sizes) -> tuple[float, float]:
    """(operations, bytes) of one gradient step: one forward and one
    backward evaluation of Gamma_s over both links. Each pass reads both
    (U, N, M) gain tensors once; the forward reads the two (U, M) shares
    and the backward writes their gradients. Per link and pass: one
    compare and one add per same-cell SIC pair and subchannel, and one
    multiply and one add per (user, AP, subchannel) for the other-cell
    term."""
    u, n, m = z.n_users, z.n_aps, z.n_sub
    per_link_pass = 2.0 * z.sic_pairs * m + 2.0 * u * n * m
    flops = 2 * 2 * per_link_pass
    nbytes = 2 * 2 * u * n * m * F32 + 4 * u * m * F32
    return flops, nbytes


def kernel_call(kind: str, z: Sizes) -> tuple[float, float]:
    """(operations, bytes) one call of a NOMA kernel requires.

    ``intra``     the SIC reduction: reads the own gains and the weights
                  (U, M) and writes (U, M); a compare and an add per
                  same-cell pair and subchannel.
    ``per_ap``    the other-cell per-AP table: reads the (U, N, M) gains and
                  the (U, M) weights, writes (N, M); a multiply and an add
                  per gain.
    ``contract``  the other-cell contraction: reads the gains and an (N, M)
                  table, writes (U, M); a multiply and an add per gain."""
    u, n, m = z.n_users, z.n_aps, z.n_sub
    if kind == "intra":
        return 2.0 * z.sic_pairs * m, 3 * u * m * F32
    if kind == "per_ap":
        return 2.0 * u * n * m, (u * n * m + u * m + n * m) * F32
    if kind == "contract":
        return 2.0 * u * n * m, (u * n * m + n * m + u * m) * F32
    raise KeyError(kind)


def noma_kernels(z: Sizes) -> dict[str, str]:
    """Regular expressions that pick each kind of NOMA kernel call out of
    the operation events of a TPU trace. Those events carry the HLO text of
    a Mosaic custom call (``%jvp__.7 = f32[U,M]{...} custom-call(...),
    custom_call_target="tpu_custom_call"``) but not the kernel's name, so a
    call is told by its result and first operand: the cell-intra kernel
    returns (U, M) and takes the two scalar-prefetched tile lists first;
    the per-AP kernel returns (N, M) and the AP contraction (U, M), both
    taking the (U, 1) AP ids first."""
    u, n, m = z.n_users, z.n_aps, z.n_sub

    def call(rows: int, first: str) -> str:
        return (rf"= f32\[{rows},{m}\]\{{[^}}]*\}} custom-call\({first}"
                r".*tpu_custom_call")
    return {"intra": call(u, r"s32\[\d+\]\{"),
            "per_ap": call(n, rf"s32\[{u},1\]"),
            "contract": call(u, rf"s32\[{u},1\]")}
