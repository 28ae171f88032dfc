"""Distribution layer: sharding rules, ZeRO-1, elastic control plane.

    from repro.distributed import sharding
    from repro.distributed.sharding import (active_mesh, constraint,
                                            param_specs, opt_specs_for_state)
    from repro.distributed.elastic import ElasticPlan, plan_resize
"""
from repro.distributed import elastic, sharding  # noqa: F401
