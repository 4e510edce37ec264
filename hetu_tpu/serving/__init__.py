"""Serving plane: continuous-batching inference over a block-paged KV
cache with radix-tree prefix sharing.

- :mod:`~hetu_tpu.serving.kv_pool` — the paged KV arena
  (``(layers, n_blocks, block_size, hkv*d)``), the refcounting
  :class:`BlockManager`, and sizing from the memory-plane ledger;
- :mod:`~hetu_tpu.serving.prefix_cache` — the radix-tree prompt-prefix
  cache (whole-block sharing, CoW partial tails, LRU leaf eviction);
- :mod:`~hetu_tpu.serving.engine` — the jit-once fused step (packed
  multi-request prefill + all-slot decode through block tables,
  per-slot SamplingParams as traced operands) and the
  :class:`ServingEngine` host loop;
- :mod:`~hetu_tpu.serving.scheduler` — priority-class admission
  (deficit-weighted fairness; exact FCFS for single-class traffic),
  cache-aware free-block gating, completion/eviction, and resumable
  preemption planning;
- :mod:`~hetu_tpu.serving.speculative` — the draft plane for
  speculative decoding (self-drafting n-gram/prompt-lookup, optional
  small-model draftsman) behind ``ServingEngine(spec_depth=k)``;
- :mod:`~hetu_tpu.serving.server` — the line-protocol front end over
  ``rpc/py_server.py`` plus payload codecs;
- :mod:`~hetu_tpu.serving.router` — the FLEET plane: load-aware +
  prefix-sticky dispatch over N replicas, drain/death requeue, and the
  :class:`WeightPublisher` live train→serve weight push (rolling
  drain → swap → resume through the HotSPa reshard core, or — for
  multi-process fleets — the ``dist_ckpt`` sharded-checkpoint
  transport);
- :mod:`~hetu_tpu.serving.fleet` — the MULTI-PROCESS rung: remote
  replicas driven through coordinator verbs (heartbeat-staleness death
  detection, idempotency-keyed submission), prefill/decode
  disaggregation roles, the KV-block wire format, and the engine
  process entry point (``python -m hetu_tpu.serving.fleet``).

``docs/SERVING.md`` documents the architecture, block lifecycle, and
the fleet state machines.
"""

from hetu_tpu.serving.block_diffusion import BlockGenerationNotSupported
from hetu_tpu.serving.engine import ServingEngine
from hetu_tpu.serving.fleet import (
    RemoteEngineProxy, RemoteReplicaHandle, RemoteRequest,
    spill_from_wire, spill_to_wire,
)
from hetu_tpu.serving.kv_pool import (
    NULL_BLOCK, BlockManager, HostSpillArena, KVPool, SpillEntry,
    cache_dtype_name,
)
from hetu_tpu.serving.prefix_cache import PrefixCache
from hetu_tpu.serving.router import (
    ReplicaHandle, Router, RouterRequest, WeightPublisher,
    materialize_params,
)
from hetu_tpu.serving.scheduler import (
    PromptTooLongError, Request, SamplingParams, Scheduler,
)
from hetu_tpu.serving.speculative import (
    ModelDraftsman, NgramDraftsman, SpeculativeConfigError,
)

__all__ = [
    "ServingEngine", "BlockGenerationNotSupported",
    "KVPool", "BlockManager", "NULL_BLOCK", "cache_dtype_name",
    "HostSpillArena", "SpillEntry",
    "PrefixCache",
    "Request", "SamplingParams", "Scheduler", "PromptTooLongError",
    "NgramDraftsman", "ModelDraftsman", "SpeculativeConfigError",
    "Router", "RouterRequest", "ReplicaHandle", "WeightPublisher",
    "materialize_params",
    "RemoteEngineProxy", "RemoteReplicaHandle", "RemoteRequest",
    "spill_to_wire", "spill_from_wire",
]
