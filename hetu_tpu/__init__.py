"""hetu_tpu — a TPU-native distributed deep-learning framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of Hetu
(PKU DAIR Lab; reference survey in SURVEY.md): multi-strategy hybrid-parallel
training (DP / ZeRO / TP / PP / CP-ring-attention / EP-MoE, homogeneous or
heterogeneous), hot strategy switching, packing/dynamic sequence lengths,
distributed checkpointing, and auto-parallel strategy search — expressed
TPU-first as `jax.sharding.Mesh` + `PartitionSpec` + `shard_map` collectives
instead of the reference's C++/CUDA graph executor + NCCL stack.

Layer map (mirrors SURVEY.md §1, re-architected for XLA):
  core/      dtype policies, mesh helpers, pytree path utilities
  nn/        Module system + layers (incl. tensor-parallel layers)
  ops/       numerics: attention (Pallas flash / ring-CP), norms, rotary,
             losses (vocab-parallel CE), MoE dispatch
  parallel/  strategy IR -> (Mesh, PartitionSpec) compiler, ZeRO, pipeline
             executor, hot-switch resharding
  optim/     optimizers with shardable state, schedules, grad scaler
  models/    GPT / Llama model families
  data/      datasets, packing buckets, loaders
  engine/    Trainer, planners, straggler monitor
  serving/   continuous-batching inference engine (slot-pooled KV cache)
  telemetry/ spans, metric registry, cross-rank aggregation, goodput
  utils/     checkpoint (safetensors-compat), logging, profiler
"""

from hetu_tpu.version import __version__

from hetu_tpu.core.dtypes import Policy, autocast, current_policy
from hetu_tpu.core.mesh import make_mesh, local_devices
from hetu_tpu import telemetry
from hetu_tpu import nn
from hetu_tpu import ops
from hetu_tpu import optim
from hetu_tpu import models
from hetu_tpu import engine
from hetu_tpu.parallel.strategy import Strategy
from hetu_tpu.parallel.sharding import (
    AxisRules,
    param_partition_specs,
    shard_params,
)

__all__ = [
    "__version__",
    "telemetry",
    "Policy",
    "autocast",
    "current_policy",
    "make_mesh",
    "local_devices",
    "nn",
    "ops",
    "optim",
    "models",
    "engine",
    "Strategy",
    "AxisRules",
    "param_partition_specs",
    "shard_params",
]
