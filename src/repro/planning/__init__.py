"""Unified planning stack: PlannerEngine over static, batched, and
time-correlated (online warm-start) environments -- vmapped on one device
or shard_map-sharded over a fleet mesh (see repro.pshard.fleet_mesh)."""
from repro.obs import compile_log  # noqa: F401
from repro.planning.engine import (  # noqa: F401
    PlannerEngine,
    PlanState,
    WarmStateShapeError,
    member,
    stack_envs,
)
from repro.pshard import (  # noqa: F401
    fleet_axis,
    fleet_mesh,
    fleet_sharding,
    shard_fleet,
)
