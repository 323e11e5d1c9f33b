"""Parallelism over TPU meshes (SURVEY.md §2.4: P1-P8 + new TP/SP).

The reference scaled via kvstore tiers (local reduce / NCCL / ps-lite —
SURVEY.md §5.8); the TPU-native design scales via ONE mechanism: shard
annotations over a ``jax.sharding.Mesh`` compiled by GSPMD, with XLA
inserting the ICI/DCN collectives.  This package supplies:

- mesh construction (``make_mesh``) with named axes dp/tp/sp/ep (ep =
  expert parallelism for MoE, ops/moe.py + gluon.contrib.MoEFFN);
- ``functionalize``: trace a Gluon Block into a pure fn of
  (params, inputs) — the bridge from the imperative API to pjit;
- sharding rules (regex -> PartitionSpec) with Megatron-style defaults
  for the in-tree transformer blocks;
- pure pytree optimizers (sgd/adamw/lamb) for inside compiled steps;
- ``ShardedTrainer``: one compiled train step = fwd + bwd + update with
  dp/tp shardings (replaces Trainer+kvstore at pod scale);
- ring attention (context parallelism over the ICI ring via ppermute);
- the self-healing layer (docs/training_resilience.md): step watchdog
  (``TrainStepTimeoutError`` instead of a wedged-collective hang),
  ``CheckpointManager`` with verified-marker + integrity-manifest
  restore fallback, and ``TrainingSupervisor`` — bounded restarts
  that resume bit-exactly (RNG + data-cursor checkpointing).

Annotating for SPMD (checked statically by mxlint's mxshard passes —
docs/static_analysis.md, passes 17-19): build meshes with *literal*
axis names and, where possible, literal extents, so every
``PartitionSpec`` checks against the real axis set and dim
divisibility; treat an ``out_specs`` entry of ``P()`` as a *claim*
that every return path reduced the value (``psum``/``pmean``/...) —
the compressed trainer's ``check_vma=False`` disables the runtime
replication check, so the static one is the only net; and donate
(``donate_argnums``) only buffers that flow to a matching output, then
rebind the host name in the same statement (``params = step(params)``)
— the old buffer is dead.
"""
from .mesh import make_mesh, mesh_axis_size
from .placement import replica_groups, replica_mesh
from .functional import functionalize
from .sharding import ShardingRules, MEGATRON_RULES, partition_params
from .optim import sgd_init, sgd_update, adamw_init, adamw_update
from .trainer import ShardedTrainer
from .supervisor import TrainingSupervisor, TrainStepTimeoutError, \
    CrashLoopError, StepWatchdog, run_with_deadline
from .ring_attention import ring_attention, ring_self_attention
from .checkpoint import CheckpointManager, save_checkpoint, \
    load_checkpoint
from .pipeline import pipeline_apply, make_pipeline_mesh
from . import dist

__all__ = ["make_mesh", "mesh_axis_size", "replica_groups",
           "replica_mesh", "functionalize",
           "ShardingRules", "MEGATRON_RULES", "partition_params",
           "sgd_init", "sgd_update", "adamw_init", "adamw_update",
           "ShardedTrainer", "TrainingSupervisor",
           "TrainStepTimeoutError", "CrashLoopError", "StepWatchdog",
           "run_with_deadline",
           "ring_attention", "ring_self_attention",
           "CheckpointManager", "save_checkpoint", "load_checkpoint",
           "pipeline_apply", "make_pipeline_mesh",
           "dist"]
