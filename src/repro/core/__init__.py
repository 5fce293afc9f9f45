"""Core: the paper's contribution — DMR API, elastic resharding, policies."""
from repro.core.actions import Action, Decision, ResizeHandler
from repro.core.dmr import DMR, RMSProtocol
from repro.core.meshes import (make_mesh, mesh_model_ways, mesh_num_slices,
                               resized_mesh)
from repro.core.redistribute import (Transfer, expand_plan, migrate_slice,
                                     plan_stats, shrink_plan,
                                     transfer_time_s)
from repro.core.reshard import (ReshardPlan, checkpoint_reshard,
                                crosses_host, moved_bytes, ownership_map,
                                plan_reshard, reshard, state_shardings)
from repro.core.sharding import (FSDP_RULES, LONG_CONTEXT_RULES, TP_DP_RULES,
                                 ShardingRules)

__all__ = [
    "Action", "Decision", "ResizeHandler", "DMR", "RMSProtocol",
    "make_mesh", "mesh_num_slices", "mesh_model_ways", "resized_mesh",
    "Transfer", "expand_plan", "shrink_plan", "transfer_time_s",
    "plan_stats",
    "migrate_slice", "reshard", "checkpoint_reshard", "moved_bytes",
    "ReshardPlan", "plan_reshard", "crosses_host",
    "state_shardings", "ownership_map",
    "ShardingRules", "TP_DP_RULES", "FSDP_RULES", "LONG_CONTEXT_RULES",
]
