"""Parallelism strategies: mesh-axis sharding rules + sequence-parallel attention.

Axes (SURVEY.md §2.2 — all first-class here, vs. data-parallel-only reference):
dp (data), fsdp (ZeRO param/optstate), tp (tensor), sp (sequence/ring attention),
pp (pipeline), ep (expert).
"""

from ..common.context import build_mesh
from ..ops.attention import (full_attention, ring_attention_local,
                             sharded_attention, ulysses_attention_local)
from .sharding import TP_RULES, make_param_sharding, replicated
from .pipeline import pipeline_apply, stack_stage_params
from .embedding_sharding import (TableSharding, owned_row_range, pad_rows,
                                 row_shard_spec, shard_embedding_tables,
                                 sharded_gather, sharded_table_layers)
from .update_sharding import (collective_counts, flat_exchange, flat_meta,
                              make_update_sharding,
                              shard_spec_over_axis, with_master_weights)

__all__ = [
    "pipeline_apply", "stack_stage_params",
    "TP_RULES", "TableSharding", "build_mesh", "collective_counts",
    "flat_exchange", "flat_meta", "full_attention",
    "make_param_sharding", "make_update_sharding", "owned_row_range",
    "pad_rows", "replicated", "ring_attention_local", "row_shard_spec",
    "shard_embedding_tables", "shard_spec_over_axis", "sharded_attention",
    "sharded_gather", "sharded_table_layers", "ulysses_attention_local",
    "with_master_weights",
]
