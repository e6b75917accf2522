from repro.distributed.context import (
    ExecutionContext,
    make_execution_context,
    parse_mesh_spec,
)
from repro.distributed.sharding import (
    batch_shardings,
    cache_shardings,
    dp_axes,
    param_spec,
    tree_param_shardings,
)

__all__ = [
    "ExecutionContext",
    "make_execution_context",
    "parse_mesh_spec",
    "param_spec",
    "tree_param_shardings",
    "batch_shardings",
    "cache_shardings",
    "dp_axes",
]
