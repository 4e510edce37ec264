"""Memory-plane ledger + remat policy engine.

The step-time/HBM tradeoff has three knobs — parallel degrees, remat
policy, per-device batch — and until now only the planner's private
memory formula priced them. This module is the ONE analytic model both
sides consume:

- the **byte ledger** (:func:`estimate_breakdown`): per-device bytes by
  class (params / grads / optimizer / activations) for a (model,
  Strategy) pair, the same arithmetic ``tools.galvatron.cost_model``
  ranks candidates with (selective activation recomputation factors per
  Korthikanti et al.; ZeRO shard divisors per Rajbhandari et al. SC'20);
- the **runtime recorder** (:func:`record_model_memory_plane`): the
  train step seeds a process-global snapshot + ``mem_*`` telemetry
  gauges on its first call, so ``tools/trace_summary.py`` reports
  the memory plane next to the control/data planes — and the Perfetto
  counter tracks render it over time;
- the **remat policy engine** (:func:`derive_remat_mask`): given an HBM
  budget, derive the minimal per-layer recompute mask
  (``Strategy(remat_mask=...)`` → ``StackedBlocks``) instead of the
  all-or-nothing per-block switch.

Byte numbers here are ANALYTIC (model-shape arithmetic, optionally
scaled by the AOT-measured calibration) — the ground-truth companion is
``jax.local_devices()[0].memory_stats()`` where the backend exposes it
(the benchmark's ``memory_peak_bytes``).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Optional, Sequence

from hetu_tpu.parallel.strategy import Strategy

#: activation bytes per (token, hidden) as a multiple of bytes_per_el —
#: the standard transformer residual accounting by remat policy
#: (none = every matmul input + attention residuals live to bwd;
#: selective = flash outputs + checkpointed dots only; full = block
#: boundaries; offload = streamed to host)
REMAT_ACT_FACTORS = {"none": 14.0, "selective": 6.0, "full": 2.0,
                     "offload": 1.0}

#: step-compute multiplier: recompute replays (part of) the forward
#: during backward — fwd is 1/3 of the 6N fwd+bwd total, selective
#: replays only attention+norms
REMAT_COMPUTE_FACTORS = {"none": 1.0, "selective": 1.12,
                         "full": 4.0 / 3.0, "offload": 4.0 / 3.0}


def act_factor(remat: str) -> float:
    return REMAT_ACT_FACTORS.get(remat, 14.0)


def compute_factor(remat: str) -> float:
    return REMAT_COMPUTE_FACTORS.get(remat, 1.0)


@dataclasses.dataclass(frozen=True)
class MemoryBreakdown:
    """Per-device bytes by class for one (model dims, Strategy) pair.

    ``act_bytes_per_microbatch`` is UNSCALED (the per-live-microbatch
    residual footprint); ``act_bytes`` applies schedule liveness
    (``live_microbatches``, the scan-flush pipeline keeps nm+pp-1 alive)
    and the measured ``act_scale`` calibration.
    """

    params_bytes: float
    grads_bytes: float
    opt_bytes: float
    act_bytes_per_microbatch: float
    live_microbatches: int
    act_scale: float
    remat: str
    remat_recompute_flops: float

    @property
    def act_bytes(self) -> float:
        return self.act_bytes_per_microbatch * self.live_microbatches \
            * self.act_scale

    @property
    def peak_bytes(self) -> float:
        return self.params_bytes + self.grads_bytes + self.opt_bytes \
            + self.act_bytes

    def classes(self) -> dict[str, float]:
        return {"params": self.params_bytes, "grads": self.grads_bytes,
                "opt": self.opt_bytes, "act": self.act_bytes}

    def to_record(self) -> dict:
        return {"kind": "memory_plane", "remat": self.remat,
                "peak_bytes": self.peak_bytes,
                "act_bytes_per_microbatch": self.act_bytes_per_microbatch,
                "live_microbatches": self.live_microbatches,
                "remat_recompute_flops": self.remat_recompute_flops,
                **{f"{k}_bytes": v for k, v in self.classes().items()}}


def estimate_breakdown(dims, strategy: Strategy, *,
                       act_scale: float = 1.0) -> MemoryBreakdown:
    """Analytic per-device memory breakdown (the arithmetic
    ``cost_model.estimate`` ranks with, split by class).

    ``dims`` is duck-typed on the ``ModelDims`` fields (num_layers,
    hidden, total_params(), layer_params(), seq_len, global_batch,
    bytes_per_el, ...).
    """
    s = strategy
    # expert params (rule "expert" → "ep") shard over ep on top of
    # tp·pp; dense params do NOT — the historical formula divided the
    # whole model by ep, under-pricing dense weights on MoE strategies
    # exactly when the planner compares ep against tp/fsdp
    expert_fn = getattr(dims, "layer_expert_params", None)
    expert_total = dims.num_layers * expert_fn() if callable(expert_fn) \
        else 0.0
    dense_total = dims.total_params() - expert_total
    p_shard = dense_total / (s.tp * s.pp) \
        + expert_total / (s.tp * s.pp * max(s.ep, 1))
    dp_shard = s.dp if (s.fsdp or s.zero) else 1
    opt_div = s.dp if s.zero else 1
    # weights bf16 + fp32 grads; fsdp shards the grad copy over dp
    # (ZeRO-3 reduce-scattered grads), two fp32 Adam moments under zero
    params_bytes = p_shard * 2.0
    grads_bytes = p_shard * (4.0 / dp_shard if s.fsdp else 4.0)
    opt_bytes = p_shard * 8.0 / opt_div

    b_loc = dims.global_batch / max(s.dp * s.ep, 1)
    seq_loc = dims.seq_len / s.cp
    nm = max(s.num_microbatches, 1)
    layers_per_stage = dims.num_layers / s.pp
    act_mb = b_loc / nm * seq_loc * dims.hidden * act_factor(s.remat) \
        * layers_per_stage * dims.bytes_per_el / s.tp
    if getattr(dims, "num_experts", 0) > 0:
        # MoE dispatch liveness: the fp32 capacity buffers (pre- and
        # post-a2a views, capacity_factor·T_loc·k·d each) are saved
        # residuals of the dispatch einsums — not tp-sharded, scaled by
        # the residual-stream remat ratio like everything else the
        # policy can free
        cf = getattr(dims, "moe_capacity_factor", 1.25)
        k = max(getattr(dims, "moe_top_k", 2), 1)
        moe_buf = 2.0 * cf * (b_loc / nm) * seq_loc * k \
            * dims.hidden * 4.0
        act_mb += moe_buf * layers_per_stage \
            * act_factor(s.remat) / act_factor("none")
    # the scan-flush pipeline keeps every microbatch's residuals live
    # until its backward REGARDLESS of remat (validated against XLA
    # memory_analysis — see cost_model history); plain accumulation
    # keeps one
    live_mb = (nm + s.pp - 1) if s.pp > 1 else 1

    # recompute FLOPs/step/device: the fwd share replayed during bwd
    tokens_loc = b_loc * dims.seq_len
    flops_layer = 6.0 * tokens_loc * dims.layer_params()
    flops_attn = 6.0 * b_loc * dims.seq_len * dims.seq_len \
        * dims.hidden / 2
    base_flops = (flops_layer + flops_attn) * layers_per_stage \
        / (s.tp * s.cp)
    recompute = (compute_factor(s.remat) - 1.0) * base_flops

    return MemoryBreakdown(
        params_bytes=params_bytes, grads_bytes=grads_bytes,
        opt_bytes=opt_bytes, act_bytes_per_microbatch=act_mb,
        live_microbatches=live_mb, act_scale=act_scale, remat=s.remat,
        remat_recompute_flops=recompute)


def layer_act_weights(dims) -> tuple:
    """Per-layer relative activation-byte weights from the ledger's
    per-class split: a layer's residual footprint decomposes into an
    MLP share and an attention share (proxied by each side's width —
    ``ModelDims.attn_param_share``), and the attention share scales
    with the layer's attention intensity (``dims.layer_attn_scale``:
    1.0 = full causal attention, ``window/seq_len`` for sliding-window
    layers). Homogeneous stacks get uniform weights."""
    n = dims.num_layers
    scales = getattr(dims, "layer_attn_scale", None)
    if scales is None:
        return (1.0,) * n
    if len(scales) != n:
        raise ValueError(
            f"layer_attn_scale has {len(scales)} entries for {n} layers")
    attn = dims.attn_param_share() if hasattr(dims, "attn_param_share") \
        else 0.5
    return tuple((1.0 - attn) + attn * float(s) for s in scales)


def derive_remat_mask(dims, strategy: Strategy, *,
                      hbm_budget_bytes: float,
                      act_scale: float = 1.0,
                      weights: Optional[Sequence[float]] = None
                      ) -> Optional[tuple]:
    """Per-layer recompute mask fitting ``hbm_budget_bytes`` with the
    fewest rematted layers.

    Returns ``None`` when the strategy fits WITHOUT recompute (uniform
    ``remat="none"`` is optimal — recompute is never free), else a
    ``Strategy(remat_mask=...)``-shaped tuple selecting the smallest
    set of layers that brings the ledger peak under budget. Raises
    ``ValueError`` when even full recompute does not fit (the planner
    must change parallel degrees instead). The rematted layers use
    ``strategy.remat`` when it names a policy, else "full" (matching
    ``StackedBlocks``' mask semantics).

    Layer selection is GREEDY BY SAVINGS, not a fixed prefix: each
    layer's live-residual bytes are weighted by ``weights`` (default:
    :func:`layer_act_weights` — the ledger's attention/MLP byte split
    times the per-layer attention intensity), so ATTENTION-HEAVY layers
    are rematted first (Korthikanti et al.: attention residuals
    dominate and recompute cheapest). A homogeneous stack has uniform
    weights and degrades to the historical leading-prefix mask (greedy
    ties break on layer index)."""
    import dataclasses as _dc
    none_bd = estimate_breakdown(
        dims, _dc.replace(strategy, remat="none"), act_scale=act_scale)
    if none_bd.peak_bytes <= hbm_budget_bytes:
        return None
    policy = strategy.remat if strategy.remat != "none" else "full"
    remat_bd = estimate_breakdown(
        dims, _dc.replace(strategy, remat=policy), act_scale=act_scale)
    if remat_bd.peak_bytes > hbm_budget_bytes:
        raise ValueError(
            f"over HBM budget even with remat={policy!r} on every "
            f"layer ({remat_bd.peak_bytes / 1e9:.2f}GB > "
            f"{hbm_budget_bytes / 1e9:.2f}GB) — change parallel "
            f"degrees, not remat")
    n = dims.num_layers
    w = tuple(weights) if weights is not None else layer_act_weights(dims)
    if len(w) != n:
        raise ValueError(f"weights has {len(w)} entries for {n} layers")
    wsum = sum(w)
    # per-layer activation contribution (schedule-scaled): the uniform
    # ledger total split by weight for the "none" residuals; the remat
    # floor (saved block boundaries / flash residuals) is uniform
    layer_none = [none_bd.act_bytes * wi / wsum for wi in w]
    layer_remat = remat_bd.act_bytes / n
    fixed = none_bd.params_bytes + none_bd.grads_bytes \
        + none_bd.opt_bytes
    need = fixed + sum(layer_none) - hbm_budget_bytes
    # biggest savings first; stable sort keeps index order on ties, so
    # uniform stacks produce the historical leading prefix
    order = sorted(range(n),
                   key=lambda i: -(layer_none[i] - layer_remat))
    chosen: set[int] = set()
    saved = 0.0
    for i in order:
        if saved >= need and chosen:
            break
        chosen.add(i)
        saved += max(layer_none[i] - layer_remat, 0.0)
    return tuple(i in chosen for i in range(n))


# -- shape plane: per-bucket pricing -----------------------------------------
#
# The bucket planner (data/hydraulis.plan_buckets) and the trainer's
# bucketed dispatch feed DIFFERENT seq-lens through one strategy; these
# helpers price each bucket with the same estimate_breakdown arithmetic
# so the planner's HBM gate and the runtime gauges can never disagree
# about what a long bucket costs.


def bucket_act_bytes(dims_base, strategy: Strategy, bucket_len: int,
                     rows: int, *, act_scale: float = 1.0) -> float:
    """Live activation bytes of one (bucket_len, rows) dispatch under
    ``strategy`` — ``estimate_breakdown`` at the bucket's own seq-len."""
    dims = dataclasses.replace(dims_base, seq_len=int(bucket_len),
                               global_batch=max(int(rows), 1))
    return estimate_breakdown(dims, strategy,
                              act_scale=act_scale).act_bytes


def bucket_peak_bytes(dims_base, strategy: Strategy,
                      plans: dict) -> dict[int, float]:
    """Ledger peak per bucket for a ``plan_buckets`` output
    (``{bucket_len: BucketPlan}``) — each bucket priced under ITS OWN
    strategy and row count. The honest per-bucket view the shape-plane
    bench and trace_summary report."""
    out: dict[int, float] = {}
    for L, plan in plans.items():
        dims = dataclasses.replace(dims_base, seq_len=int(L),
                                   global_batch=max(plan.batch_rows, 1))
        out[int(L)] = estimate_breakdown(dims, plan.strategy).peak_bytes
    return out


def cp_prefill_act_bytes(cfg, *, seq_len: int, cp: int = 1) -> float:
    """Activation bytes of ONE cp-sharded long-prompt prefill forward
    (the serving CP lane, ``ServingEngine(long_max_len=)``): per-device
    residuals of a no-remat, batch-1 forward at ``seq_len``, divided
    over the cp axis. The serving admission gate uses this to refuse a
    ``long_max_len`` whose prefill could not fit next to the arena."""
    from hetu_tpu.tools.galvatron.cost_model import ModelDims
    dims = ModelDims.from_config(cfg, seq_len=int(seq_len),
                                 global_batch=1)
    bd = estimate_breakdown(dims, Strategy(cp=max(int(cp), 1)))
    return bd.act_bytes_per_microbatch


# -- serving plane: KV-pool sizing -------------------------------------------
#
# The serving engine's admission control is a BYTES question — how many
# fixed-shape KV slots fit next to the weights — and this ledger is the
# one place that arithmetic lives (the training planner and the serving
# scheduler must not disagree about what a layer weighs).

#: bytes per KV element by cache dtype: fp32/bf16 dense caches, int8 =
#: 1 byte/elem + per-(position, head) fp32 scales amortized over
#: head_dim (``generation.init_kv_caches`` quantized layout)
KV_CACHE_BYTES_PER_EL = {"fp32": 4.0, "bf16": 2.0, "int8": 1.0}


def kv_bytes_per_block(cfg, *, block_size: int,
                       cache_dtype: str = "fp32", tp: int = 1) -> float:
    """Bytes of one ``block_size``-token K+V page across every layer —
    the allocation unit of the PAGED serving pool (the scheduler's
    free-block admission gate prices requests in these)."""
    if cache_dtype not in KV_CACHE_BYTES_PER_EL:
        raise ValueError(f"cache_dtype must be one of "
                         f"{sorted(KV_CACHE_BYTES_PER_EL)}, "
                         f"got {cache_dtype!r}")
    hkv = getattr(cfg, "num_kv_heads", None) or cfg.num_heads
    d = getattr(cfg, "head_dim", None) or cfg.hidden_size // cfg.num_heads
    rows = cfg.num_layers * block_size * (hkv / max(tp, 1))
    per_el = KV_CACHE_BYTES_PER_EL[cache_dtype]
    bytes_kv = 2.0 * rows * d * per_el          # K and V
    if cache_dtype == "int8":
        bytes_kv += 2.0 * rows * 4.0            # fp32 row scales
    return bytes_kv


def kv_bytes_per_slot(cfg, *, max_len: int, cache_dtype: str = "fp32",
                      tp: int = 1) -> float:
    """Per-slot bytes of one request's worst-case K+V rows across every
    layer — a ``max_len``-token page (back-compat unit; the paged pool
    allocates :func:`kv_bytes_per_block` at a time)."""
    return kv_bytes_per_block(cfg, block_size=max_len,
                              cache_dtype=cache_dtype, tp=tp)


def size_kv_blocks(cfg, *, hbm_budget_bytes: float, block_size: int,
                   cache_dtype: str = "fp32", tp: int = 1,
                   param_bytes_per_el: float = 4.0,
                   headroom: float = 0.1) -> int:
    """How many KV blocks fit in ``hbm_budget_bytes`` next to the
    weights (``param_bytes_per_el`` per parameter, sharded over tp).

    Raises ``ValueError`` when not even one block fits — the caller
    must shrink ``block_size``, quantize the cache, or raise tp."""
    from hetu_tpu.tools.galvatron.cost_model import ModelDims
    dims = ModelDims.from_config(cfg, seq_len=block_size, global_batch=1)
    weights = dims.total_params() * param_bytes_per_el / max(tp, 1)
    avail = hbm_budget_bytes * (1.0 - headroom) - weights
    per_block = kv_bytes_per_block(cfg, block_size=block_size,
                                   cache_dtype=cache_dtype, tp=tp)
    blocks = int(avail // per_block)
    if blocks < 1:
        raise ValueError(
            f"KV pool does not fit: weights {weights / 1e9:.2f}GB + one "
            f"{per_block / 1e6:.1f}MB block exceed the "
            f"{hbm_budget_bytes / 1e9:.2f}GB budget — shrink the "
            f"block/slot size, use an int8 cache, or raise tp")
    return blocks


def decode_attn_read_bytes(cfg, *, context_len: int, table_len: int,
                           block_size: int, rows: int = 1,
                           cache_dtype: str = "fp32", tp: int = 1,
                           kernel: str = "paged") -> float:
    """HBM bytes ONE slot's decode attention reads per fused step —
    the gather-tax arithmetic the kernel plane exists to kill.

    ``kernel="reference"`` prices the XLA-gather path: every layer
    MATERIALIZES the slot's full ``table_len``-row KV view
    (``gather_block_rows`` — written once by the gather, read back by
    the attention contraction, and on int8 arenas dequantized to the
    compute dtype first), so bytes scale with the TABLE WIDTH the
    long-prompt lane widened, not the live context. ``kernel="paged"``
    prices the Pallas kernel: only the ``ceil(context/block_size)``
    live pages stream HBM→VMEM, once, in the arena's own dtype (int8
    pages + their fp32 scales — the dequant happens in VMEM). ``rows``
    (1 classic decode, k+1 verify-lane, C packed-prefill) does not
    change the KV read — the q tile rides VMEM — so it is accepted and
    ignored; it documents the call shape."""
    del rows
    if kernel not in ("paged", "reference"):
        raise ValueError(f"kernel must be paged|reference, "
                         f"got {kernel!r}")
    if kernel == "paged":
        live = -(-int(context_len) // int(block_size))
        return live * kv_bytes_per_block(
            cfg, block_size=block_size, cache_dtype=cache_dtype, tp=tp)
    gathered = kv_bytes_per_block(cfg, block_size=table_len,
                                  cache_dtype=cache_dtype, tp=tp)
    if cache_dtype == "int8":
        # the reference path dequantizes the gathered view to fp32
        # scratch before the einsum reads it — a second, 4x-wide pass
        gathered += kv_bytes_per_block(cfg, block_size=table_len,
                                       cache_dtype="fp32", tp=tp)
    # written by the gather + read back by the attention contraction
    return 2.0 * gathered


def size_spill_arena(cfg, *, host_budget_bytes: float, block_size: int,
                     cache_dtype: str = "fp32", tp: int = 1) -> int:
    """How many KV blocks the host spill arena may park in
    ``host_budget_bytes`` of host memory.

    The resumable-preemption path (``serving/kv_pool.HostSpillArena``)
    evicts a running request by copying its blocks device→host; this is
    the pricing that gates those copies, and it is the SAME
    :func:`kv_bytes_per_block` arithmetic the device pool allocates
    with — a spilled block costs on the host exactly what it freed on
    the device (no weights term: the host side holds only KV). Raises
    when not even one block fits."""
    per_block = kv_bytes_per_block(cfg, block_size=block_size,
                                   cache_dtype=cache_dtype, tp=tp)
    blocks = int(float(host_budget_bytes) // per_block)
    if blocks < 1:
        raise ValueError(
            f"spill arena does not fit: one {per_block / 1e6:.1f}MB "
            f"block exceeds the {host_budget_bytes / 1e6:.1f}MB host "
            f"budget — raise the budget or shrink block_size")
    return blocks


def size_spill_tiers(cfg, *, host_budget_bytes: float,
                     peer_budget_bytes: float = 0.0, block_size: int,
                     cache_dtype: str = "fp32", tp: int = 1) -> dict:
    """Per-tier block capacities for a chained spill store
    (device→host→peer, ISSUE 18): ``{"host": n, "peer": m}``.

    Both tiers are priced with the SAME :func:`kv_bytes_per_block`
    arithmetic as :func:`size_spill_arena`, so demotion accounting
    stays in arena blocks end to end — a block demoted to the peer
    tier frees on the host exactly what it costs the peer. The host
    tier must fit at least one block (same contract as
    :func:`size_spill_arena`); a zero peer budget prices an unchained
    arena (``peer: 0``)."""
    host = size_spill_arena(cfg, host_budget_bytes=host_budget_bytes,
                            block_size=block_size,
                            cache_dtype=cache_dtype, tp=tp)
    per_block = kv_bytes_per_block(cfg, block_size=block_size,
                                   cache_dtype=cache_dtype, tp=tp)
    peer = int(float(peer_budget_bytes) // per_block) \
        if peer_budget_bytes else 0
    return {"host": host, "peer": peer}


def size_adapter_arena(cfg, *, r: int, max_adapters: int,
                       dtype_bytes: float = 4.0) -> int:
    """Device bytes of the multi-tenant LoRA adapter arena
    (``serving/tenancy.py``): per layer and per arena page, an
    ``(in, r)`` A plus an ``(r, out)`` B for every adapter-targetable
    projection — q/k/v/out always, plus the dense FFN matrices
    (gated gate/up/down when the config carries ``intermediate_size``,
    GPT fc_in/fc_out otherwise; MoE expert weights are not adapter
    targets, so MoE FFNs price zero). This is what the serving
    engine's admission gate subtracts from ``hbm_budget_bytes`` before
    sizing the KV pool — adapter pages are HBM the KV arena can no
    longer have."""
    L = int(cfg.num_layers)
    E = int(cfg.hidden_size)
    heads = int(cfg.num_heads)
    hd = int(getattr(cfg, "head_dim", None) or E // heads)
    kvh = int(getattr(cfg, "num_kv_heads", None) or heads)
    q_out, kv_out = heads * hd, kvh * hd
    dims = [(E, q_out), (E, kv_out), (E, kv_out), (q_out, E)]
    if getattr(cfg, "num_experts", 0) <= 0:
        inter = getattr(cfg, "intermediate_size", None)
        if inter is not None:
            dims += [(E, int(inter)), (E, int(inter)), (int(inter), E)]
        else:
            hidden = int(getattr(cfg, "mlp_ratio", 4)) * E
            dims += [(E, hidden), (hidden, E)]
    per_page = sum((i + o) * int(r) for i, o in dims) * L
    return int(per_page * int(max_adapters) * float(dtype_bytes))


def size_kv_pool(cfg, *, hbm_budget_bytes: float, max_len: int,
                 cache_dtype: str = "fp32", tp: int = 1,
                 param_bytes_per_el: float = 4.0,
                 headroom: float = 0.1) -> int:
    """How many serving slots fit in ``hbm_budget_bytes`` next to the
    weights — :func:`size_kv_blocks` with one ``max_len``-token block
    per slot (back-compat wrapper; the paged pool sizes in blocks)."""
    return size_kv_blocks(cfg, hbm_budget_bytes=hbm_budget_bytes,
                          block_size=max_len, cache_dtype=cache_dtype,
                          tp=tp, param_bytes_per_el=param_bytes_per_el,
                          headroom=headroom)


# -- runtime ledger ----------------------------------------------------------
#
# Mirrors parallel.overlap's pattern: a module-level snapshot tests
# read without enabling telemetry, plus mem_* gauges in the
# registry when it is on. Last-write-wins per class (gauge semantics —
# the memory plane is a state, not a flow).

_LOCK = threading.Lock()
_LEDGER: dict[str, float] = {}


def record_memory_plane(bd: MemoryBreakdown,
                        strategy: Optional[Strategy] = None) -> None:
    """Install ``bd`` as the process's current memory-plane snapshot and
    mirror it into the ``mem_*`` telemetry gauges."""
    vals = {f"{k}_bytes": float(v) for k, v in bd.classes().items()}
    vals["peak_bytes"] = float(bd.peak_bytes)
    vals["remat_recompute_flops"] = float(bd.remat_recompute_flops)
    with _LOCK:
        _LEDGER.update(vals)
        _LEDGER["remat"] = bd.remat
        if strategy is not None:
            _LEDGER["strategy"] = strategy.to_json()
    from hetu_tpu import telemetry
    if telemetry.enabled():
        reg = telemetry.get_registry()
        for name, help_ in (
                ("mem_params_bytes", "ledger: param bytes per device"),
                ("mem_grads_bytes", "ledger: gradient bytes per device"),
                ("mem_opt_bytes", "ledger: optimizer-state bytes"),
                ("mem_act_bytes", "ledger: live activation bytes"),
                ("mem_peak_bytes", "ledger: peak HBM estimate"),
                ("mem_remat_recompute_flops",
                 "ledger: recompute FLOPs/step the remat policy costs")):
            key = name[len("mem_"):]
            reg.gauge(name, help_).set(vals[key])


def record_model_memory_plane(model, strategy: Strategy,
                              batch: dict) -> Optional[MemoryBreakdown]:
    """Derive dims from the model config + batch shape and record the
    breakdown (called once per compiled step, on its first invocation).
    Returns None for model families without transformer dims."""
    cfg = getattr(model, "cfg", None)
    if cfg is None or not hasattr(cfg, "num_layers") \
            or not hasattr(cfg, "hidden_size"):
        return None
    ids = batch.get("input_ids") if hasattr(batch, "get") else None
    if ids is None or getattr(ids, "ndim", 0) < 2:
        return None
    from hetu_tpu.tools.galvatron.cost_model import ModelDims
    dims = ModelDims.from_config(cfg, seq_len=int(ids.shape[1]),
                                 global_batch=int(ids.shape[0]))
    bd = estimate_breakdown(dims, strategy)
    record_memory_plane(bd, strategy)
    return bd


def memory_stats() -> dict:
    """Snapshot of the last recorded memory plane ({} before any step)."""
    with _LOCK:
        return dict(_LEDGER)


def reset_memory_stats() -> None:
    with _LOCK:
        _LEDGER.clear()


def device_peak_bytes() -> Optional[int]:
    """Ground truth where available: the backend's own peak allocation
    (``memory_stats()["peak_bytes_in_use"]`` on TPU; None on CPU)."""
    import jax
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    peak = stats.get("peak_bytes_in_use")
    return int(peak) if peak is not None else None
