"""Generic jaxpr visitor for the program auditor.

One traversal implementation serves every rule: it recurses through any
equation parameter that holds a sub-jaxpr (closed calls / pjit, scan and
while bodies, cond branches, custom_vjp/custom_jvp call jaxprs) and knows
how to present ``pallas_call`` equations structurally -- the launch grid,
the scalar-prefetch operand count, and the kernel body's VMEM working set
derived from the body's memory-ref avals (which matches the analytic
``noma_rates.vmem_block_bytes`` exactly for the NOMA kernels; asserted in
tests/test_analysis_rules.py).

The previous per-test walkers in tests/test_grad_kernels.py and
tests/test_cell_layout.py are re-expressed on top of this module via the
rule catalog (analysis/rules.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterator

import jax
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr


def subjaxprs(param: Any) -> Iterator[Jaxpr]:
    """Yield every (open) jaxpr held by one equation parameter value."""
    vals = param if isinstance(param, (tuple, list)) else [param]
    for p in vals:
        if isinstance(p, ClosedJaxpr):
            yield p.jaxpr
        elif isinstance(p, Jaxpr):
            yield p


def iter_eqns(jaxpr: Jaxpr, enter_pallas: bool = False) -> Iterator[Any]:
    """Every equation of ``jaxpr`` and its sub-jaxprs, depth-first.

    enter_pallas=False (the default, and what the memory-model rules want)
    yields ``pallas_call`` equations themselves but does NOT descend into
    their kernel bodies: the body works on (block,) VMEM refs that at toy
    scale can numerically look like full-tensor shapes but are streamed,
    not materialized.
    """
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not enter_pallas:
            continue
        for param in eqn.params.values():
            for sub in subjaxprs(param):
                yield from iter_eqns(sub, enter_pallas=enter_pallas)


def out_shapes(eqn: Any) -> list[tuple[int, ...]]:
    """Output aval shapes of one equation (missing avals -> ())."""
    return [tuple(getattr(v.aval, "shape", ())) for v in eqn.outvars]


def _is_smem(aval: Any) -> bool:
    ms = getattr(aval, "memory_space", None)
    return ms is not None and "smem" in str(ms).lower()


def _ref_bytes(aval: Any) -> int:
    shape = tuple(getattr(aval, "shape", ()))
    dtype = getattr(aval, "dtype", None)
    itemsize = np.dtype(dtype).itemsize if dtype is not None else 4
    return int(np.prod(shape, dtype=np.int64)) * itemsize


@dataclasses.dataclass(frozen=True)
class PallasCallInfo:
    """Structural summary of one ``pallas_call`` equation.

    grid                 launch grid (vmapped calls carry the batch dim
                         prepended; the trailing dims are the kernel's own).
    num_scalar_prefetch  SMEM scalar-prefetch operand count (the tile-driven
                         intra/SIC kernel is the only NOMA kernel with 2:
                         its (tile_r, tile_s) lists).
    vmem_bytes           working set of one kernel invocation: the summed
                         byte sizes of every non-SMEM memory ref the body
                         binds (inputs + outputs + scratch) -- block-shaped,
                         so independent of vmap batching.
    name                 kernel name when the jaxpr records one.
    """

    grid: tuple[int, ...]
    num_scalar_prefetch: int
    vmem_bytes: int
    name: str = "pallas_call"


def pallas_call_info(eqn: Any) -> PallasCallInfo:
    gm = eqn.params["grid_mapping"]
    body = eqn.params["jaxpr"]
    if isinstance(body, ClosedJaxpr):
        body = body.jaxpr
    vmem = sum(_ref_bytes(v.aval) for v in body.invars
               if not _is_smem(v.aval))
    name = str(eqn.params.get("name_and_src_info",
                              eqn.params.get("name", "pallas_call")))
    # name_and_src_info stringifies as "<name> at <file>:<line>"; keep the name
    name = name.split(" at ")[0] or "pallas_call"
    return PallasCallInfo(
        grid=tuple(int(g) for g in gm.grid),
        num_scalar_prefetch=int(getattr(gm, "num_index_operands", 0)),
        vmem_bytes=int(vmem),
        name=name,
    )


def pallas_calls(jaxpr: Jaxpr) -> list[PallasCallInfo]:
    """Every pallas_call in the program, in traversal order."""
    return [pallas_call_info(e) for e in iter_eqns(jaxpr, enter_pallas=False)
            if e.primitive.name == "pallas_call"]


def trace(fn: Callable, *args: Any, **kwargs: Any) -> ClosedJaxpr:
    """The program under audit: jax.make_jaxpr of ``fn`` at these avals.

    Tracing only -- nothing executes, so auditing an interpret-mode Pallas
    program at paper scale is cheap. Arguments may be concrete arrays or
    jax.ShapeDtypeStruct avals (e.g. a PlanState from jax.eval_shape fed
    back into a replan program)."""
    return jax.make_jaxpr(fn)(*args, **kwargs)
