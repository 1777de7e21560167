"""Parallelism: mesh/sharding helpers, ring attention (SP/CP), tensor/
expert-parallel rules (TP/EP). The reference's only axis is DP
(AllReduceParameter); everything else is additive TPU-first scope."""
from bigdl_tpu.parallel.mesh import (
    make_mesh, data_parallel_mesh, replicated, batch_sharded)
from bigdl_tpu.parallel.ring_attention import (
    ring_attention, ring_attention_sharded)
from bigdl_tpu.parallel.ulysses import (
    ulysses_attention, ulysses_attention_sharded)
from bigdl_tpu.parallel.sequence import (
    SeqParallelConfig, active_sequence_parallel, use_sequence_parallel)
from bigdl_tpu.parallel.tp import (
    shard_params, shard_opt_state_zero1, spec_for, tree_shardings,
    validate_rules)
from bigdl_tpu.parallel.pipeline import pipeline_forward, spmd_pipeline
from bigdl_tpu.parallel.zero import (
    ZeroConfig, collective_counts, constrain_base, constrain_zero,
    place_zero_opt_state, place_zero_params, place_zero_state,
    record_memory_gauges, reduce_scatter_evidence, shard_zero_tree,
    tree_bytes_per_chip, tree_zero_specs, window_collectives)
