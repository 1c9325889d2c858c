from repro.configs.base import (  # noqa: F401
    ARCHS,
    SHAPES,
    SUBQUADRATIC,
    ArchConfig,
    all_names,
    get,
    shape_applicable,
)
import repro.configs.archs  # noqa: F401  (registers the assigned archs)
