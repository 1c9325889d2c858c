"""Logical-axis -> mesh-axis sharding rules (neutral module: imported by
both the model zoo and the runtime without circular imports)."""
from __future__ import annotations

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> preferred mesh axis (or tuple for joint sharding)
RULES: dict[str | None, str | tuple | None] = {
    "vocab": "model",
    "qkv": "model",          # flattened heads*hd projections
    "kv": "model",           # flattened kv_heads*hd
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "experts": "model",      # expert parallelism
    "experts_row": None,     # router output dim: small, replicate
    "lru": "model",
    "lru_out": None,         # second dim of the square lru mats: replicate
    "embed": None,           # residual stream replicated (TP gathers on it)
    "layers": None,
    "batch": ("pod", "data"),
    "seq": None,
    "kvseq": "model",        # decode KV-cache sequence sharding (flash-decode)
    "fleet": "fleet",        # planner fleet axis (one scenario batch per device)
    None: None,
}


FLEET_AXIS = "fleet"


def fleet_mesh(n_devices: int | None = None, axis: str = FLEET_AXIS) -> Mesh:
    """A 1-D mesh over the (first n) local devices for fleet planning:
    PlannerEngine.shard(fleet_mesh()) runs plan_many/replan_many via
    shard_map with the fleet dim split across devices."""
    devs = jax.devices() if n_devices is None else jax.devices()[:n_devices]
    return Mesh(np.asarray(devs), (axis,))


def fleet_axis(mesh: Mesh) -> str:
    """The mesh axis carrying the fleet dim: 'fleet' when present, else the
    first axis (so a plain 1-D ('data',) mesh also works)."""
    if FLEET_AXIS in mesh.shape:
        return FLEET_AXIS
    return mesh.axis_names[0]


def fleet_sharding(mesh: Mesh) -> NamedSharding:
    """NamedSharding splitting the leading (fleet) dim over the mesh."""
    return NamedSharding(mesh, P(fleet_axis(mesh)))


def shard_fleet(tree, mesh: Mesh):
    """Explicitly place a fleet-batched pytree (stacked NetworkEnv, fleet
    ScenarioState, batched PlanState) with its leading dim split over the
    mesh's fleet axis. jit would insert the same transfer implicitly; doing
    it once up front keeps steady-state dispatch transfer-free (and clean
    under jax.transfer_guard('disallow'))."""
    return jax.device_put(tree, fleet_sharding(mesh))


def axis_size(mesh: Mesh, name) -> int:
    if name is None:
        return 1
    if isinstance(name, tuple):
        return int(np.prod([axis_size(mesh, n) for n in name]))
    return mesh.shape[name] if name in mesh.shape else 1


def spec_for(mesh: Mesh, logical_axes: tuple, shape: tuple,
             fsdp: bool = False) -> P:
    """Resolve logical axes to a PartitionSpec. A mesh axis is used at most
    once per tensor (first logical dim wins: e.g. MoE (experts, embed, mlp)
    shards experts over 'model' and leaves mlp replicated); non-divisible
    dims are dropped to replication (jit rejects uneven input shardings).

    fsdp=True (parameters only, Perf iteration E): a dim whose logical axis
    is 'embed' additionally shards over the data-parallel axes (ZeRO-3 /
    MaxText-fsdp style) -- GSPMD inserts per-layer weight all-gathers in
    fwd/bwd and reduce-scatters the gradients."""
    out = []
    used: set = set()

    def assign(mesh_ax, dim):
        if isinstance(mesh_ax, tuple):
            mesh_ax = tuple(a for a in mesh_ax if a in mesh.shape
                            and a not in used)
            if not mesh_ax:
                return None
        elif mesh_ax not in mesh.shape or mesh_ax in used:
            return None
        size = axis_size(mesh, mesh_ax)
        if dim % size == 0 and dim >= size:
            used.update(mesh_ax if isinstance(mesh_ax, tuple) else (mesh_ax,))
            return mesh_ax
        return None

    for ax, dim in zip(logical_axes, shape):
        mesh_ax = RULES.get(ax, None)
        got = assign(mesh_ax, dim) if mesh_ax is not None else None
        if got is None and fsdp and ax == "embed":
            got = assign(tuple(a for a in ("pod", "data") if a in mesh.shape),
                         dim)
        out.append(got)
    return P(*out)


def ambient_mesh():
    """The physical mesh activated via `with mesh:` (trace-time), or None.

    Private import on purpose: JAX 0.9.0 has no public getter for the mesh
    a `with mesh:` block enters (jax.sharding.get_mesh/get_abstract_mesh
    see only jax.set_mesh), and the callers here use `with mesh:`."""
    from jax._src import mesh as mesh_lib
    m = mesh_lib.thread_resources.env.physical_mesh
    return None if m.empty else m


def constrain(x, logical_axes: tuple):
    """with_sharding_constraint resolved through the divisibility-aware
    rules against the ambient mesh; no-op outside a mesh context."""
    m = ambient_mesh()
    if m is None:
        return x
    spec = spec_for(m, logical_axes, x.shape)
    if all(a is None for a in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(m, spec))
