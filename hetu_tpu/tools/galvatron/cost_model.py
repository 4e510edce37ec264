"""Analytic cost model for hybrid-parallel transformer training on TPU.

Galvatron-equivalent (reference ``tools/Galvatron``: hardware profiler →
cost estimator → DP search), re-derived for TPU systems: MXU peak FLOPs,
HBM capacity, and ICI ring bandwidth replace the NVLink/IB tables. The
model follows the standard scaling-book accounting:

- compute: fwd FLOPs/layer = 2·tokens·(attn+mlp params) + attention
  O(s²); bwd = 2× fwd; divided across dp·tp·cp.
- tp comm: 2 allreduces per layer fwd (+2 bwd) of the activation block,
  ring cost 2·(n-1)/n · bytes / bw.
- cp comm: (cp-1) ring hops of local KV per layer, fwd + bwd.
- dp comm: one grad allreduce (or reduce-scatter+allgather under ZeRO)
  per step, overlappable fraction configurable.
- pp: bubble multiplier (nm + pp - 1)/nm on the per-stage time.
- memory: params·(weights+grads+Adam moments)/shards + activation
  checkpointing policy factor.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional

from hetu_tpu.engine.memory import compute_factor, estimate_breakdown
from hetu_tpu.parallel.strategy import Strategy

# Default location of the measured calibration written by
# workloads/calibrate_run.py during a TPU window.
CALIBRATION_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), "workloads", "out",
    "calibration.json")
# Memory-model correction measured against AOT compiler ground truth
# (workloads/mem_calibrate.py — needs no TPU window: libtpu is local).
MEM_CALIBRATION_PATH = os.path.join(
    os.path.dirname(CALIBRATION_PATH), "mem_calibration.json")


#: Per-chip facts keyed by ``device_kind``: bf16 peak FLOP/s and HBM
#: bytes. The ONE table, and it holds only kinds this tree has run on —
#: ``"TPU v5 lite"`` is what the v5e reports (chip run, PR 21); 197e12
#: and 16 GB are its published figures (Google Cloud TPU documentation,
#: "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e per chip). A device that is
#: not here is an error, never a default: add its row, with the source
#: of its bf16 figure, when the program first runs on it.
DEVICE_SPECS = {
    "TPU v5 lite": {"peak_flops": 197e12, "hbm_bytes": 16e9},
}


def device_spec(device) -> dict:
    """``DEVICE_SPECS`` row of a live device; where the runtime reports
    its memory limit (``memory_stats()["bytes_limit"]``) that replaces
    the published size. An unknown ``device_kind`` raises."""
    kind = device.device_kind
    if kind not in DEVICE_SPECS:
        raise KeyError(f"no peak/memory on record for device kind "
                       f"{kind!r} (tools/galvatron/cost_model.py "
                       f"DEVICE_SPECS)")
    spec = dict(DEVICE_SPECS[kind])
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if limit:
        spec["hbm_bytes"] = float(limit)
    return spec


@dataclasses.dataclass(frozen=True)
class TPUTopology:
    """One slice. The field defaults ≈ TPU v5p are for planning and
    simulation off the chip; on a live TPU :meth:`calibrated` takes peak
    and memory from the device (:func:`device_spec`)."""

    num_devices: int
    peak_flops: float = 459e12        # bf16 per chip
    ici_bw: float = 9e10              # bytes/s per direction, ring
    dcn_bw: float = 2.5e9             # bytes/s per host pair (multi-slice)
    hbm_bytes: float = 95e9
    mxu_efficiency: float = 0.5       # achievable fraction of peak
    dp_overlap: float = 0.7           # grad-allreduce overlap with bwd
    # activation-memory correction vs the analytic model, measured by
    # AOT-compiling real train steps and reading XLA's memory analysis
    # (workloads/mem_calibrate.py → mem_calibration.json); 1.0 = trust
    # the analytic act model. Applied multiplicatively to mem_act.
    # ``mem_scale_remat``: per-remat refinements as (remat, scale)
    # pairs — the analytic act_factor RATIOS between remat modes are
    # also off, so one global scale cannot match all three.
    mem_scale: float = 1.0
    mem_scale_remat: tuple = ()

    def act_scale(self, remat: str) -> float:
        for r, s in self.mem_scale_remat:
            if r == remat:
                return s
        if self.mem_scale_remat:
            # a remat mode the calibration never measured (e.g.
            # offload) must not inherit the global max — that would
            # reject candidates on a correction with no measurement
            # behind it; analytic (1.0) is the honest default there
            return 1.0
        return self.mem_scale

    @classmethod
    def calibrated(cls, num_devices: int,
                   path: Optional[str] = None, **overrides
                   ) -> "TPUTopology":
        """Topology seeded from the MEASURED calibration when one exists
        (profile-first, like the reference's ``profile_hardware`` flow —
        ``tools/Galvatron/galvatron/profile_hardware/``). On a live TPU
        peak and memory come from the device (:func:`device_spec`; an
        unknown kind raises) and a calibration recorded on another
        ``device_kind`` is not applied; off the chip the spec defaults
        stand in. A missing file is no calibration; an unreadable one
        raises. Explicit ``overrides`` always win."""
        import jax
        dev = jax.devices()[0]
        on_tpu = dev.platform == "tpu"
        fields = device_spec(dev) if on_tpu else {}

        def load(p):
            if not os.path.exists(p):
                return None
            with open(p) as f:
                return json.load(f)

        cal = load(path or CALIBRATION_PATH)
        if cal is not None and (not on_tpu or cal.get(
                "device_kind", dev.device_kind) == dev.device_kind):
            for k in ("peak_flops", "ici_bw", "dcn_bw", "hbm_bytes",
                      "mxu_efficiency", "dp_overlap"):
                if k in cal:    # the live device's own facts stay
                    fields.setdefault(k, float(cal[k]))
        mc = load(MEM_CALIBRATION_PATH)
        if mc is not None:
            fields["mem_scale"] = float(mc["mem_scale"])
            fields["mem_scale_remat"] = tuple(
                (str(r), float(s))
                for r, s in mc.get("remat_scales", {}).items())
        fields.update(overrides)
        return cls(num_devices=num_devices, **fields)


@dataclasses.dataclass(frozen=True)
class ModelDims:
    """Shapes that drive cost (from a GPTConfig/LlamaConfig + run shape)."""

    num_layers: int
    hidden: int
    intermediate: int
    num_heads: int
    num_kv_heads: int
    vocab: int
    seq_len: int
    global_batch: int
    bytes_per_el: int = 2             # bf16 activations/weights on the wire
    num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # per-layer relative attention intensity (len = num_layers), e.g.
    # 1.0 for full attention, window/seq_len for sliding-window layers.
    # None = homogeneous stack. Consumed by the memory-plane remat
    # policy engine (engine.memory.derive_remat_mask) to remat the
    # attention-heavy layers FIRST instead of an arbitrary prefix.
    layer_attn_scale: Optional[tuple] = None

    @classmethod
    def from_config(cls, cfg, *, seq_len: int, global_batch: int):
        inter = getattr(cfg, "intermediate_size",
                        getattr(cfg, "mlp_ratio", 4) * cfg.hidden_size)
        return cls(
            num_layers=cfg.num_layers, hidden=cfg.hidden_size,
            intermediate=inter, num_heads=cfg.num_heads,
            num_kv_heads=getattr(cfg, "num_kv_heads", None)
            or cfg.num_heads,
            vocab=cfg.vocab_size, seq_len=seq_len,
            global_batch=global_batch,
            num_experts=getattr(cfg, "num_experts", 0),
            moe_top_k=getattr(cfg, "moe_top_k", 2),
            moe_capacity_factor=getattr(cfg, "moe_capacity_factor", 1.25))

    # params of one block (attention + dense or expert MLP)
    def layer_params(self) -> float:
        h, hd = self.hidden, self.hidden // self.num_heads
        attn = h * (self.num_heads * hd + 2 * self.num_kv_heads * hd) \
            + self.num_heads * hd * h
        mlp_dense = 3 * h * self.intermediate if self.intermediate \
            != 4 * h else 2 * h * self.intermediate
        if self.num_experts > 0:
            mlp_dense *= self.num_experts
        return attn + mlp_dense

    def layer_expert_params(self) -> float:
        """Params of one layer's EXPERT MLP stack (0 for dense models)
        — the share the ``"expert" → "ep"`` rule shards over ep, which
        the memory ledger must divide by ep where everything else
        divides by tp·pp alone."""
        if self.num_experts <= 0:
            return 0.0
        h = self.hidden
        mlp_one = 3 * h * self.intermediate if self.intermediate \
            != 4 * h else 2 * h * self.intermediate
        return mlp_one * self.num_experts

    def attn_param_share(self) -> float:
        """Attention's fraction of one block's params — the proxy the
        memory ledger uses to split a layer's residual bytes into
        attention vs MLP classes (widths drive residual sizes)."""
        h, hd = self.hidden, self.hidden // self.num_heads
        attn = h * (self.num_heads * hd + 2 * self.num_kv_heads * hd) \
            + self.num_heads * hd * h
        return attn / self.layer_params()

    def total_params(self) -> float:
        return self.num_layers * self.layer_params() \
            + self.vocab * self.hidden


@dataclasses.dataclass
class CostBreakdown:
    step_time: float
    compute: float
    tp_comm: float
    cp_comm: float
    dp_comm: float
    pp_bubble_factor: float
    mem_per_device: float
    # per-micro-batch accounting (reference MicroBatchMemoryInfo,
    # graph/profiler.h:31-38): the activation term is per LIVE microbatch
    mem_params: float = 0.0
    mem_opt: float = 0.0
    mem_act_per_microbatch: float = 0.0
    # MoE dispatch/combine all_to_all time (0 for dense models or
    # ep=1); priced serialized — Strategy(ep_overlap="chunk") hides a
    # large share of it behind the expert matmuls at runtime
    ep_comm: float = 0.0

    def fits(self, topo: TPUTopology) -> bool:
        return self.mem_per_device <= topo.hbm_bytes


def _ring_allreduce_time(bytes_: float, n: int, bw: float) -> float:
    if n <= 1:
        return 0.0
    return 2.0 * (n - 1) / n * bytes_ / bw


def estimate(dims: ModelDims, strategy: Strategy,
             topo: TPUTopology) -> CostBreakdown:
    """Estimated step time (seconds) and per-device memory for one
    strategy."""
    s = strategy
    b_loc = dims.global_batch / max(s.dp * s.ep, 1)      # per dp×ep shard
    seq_loc = dims.seq_len / s.cp
    h = dims.hidden
    tokens_loc = b_loc * dims.seq_len                    # per dp replica

    # ---- compute ----------------------------------------------------------
    # matmul flops per token per layer = 6 * layer_params (fwd+bwd), but
    # MoE only computes top_k experts' worth
    lp = dims.layer_params()
    if dims.num_experts > 0:
        mlp_all = lp - (h * (dims.num_heads + 2 * dims.num_kv_heads)
                        * (h // dims.num_heads)
                        + h * dims.num_heads * (h // dims.num_heads))
        lp_active = lp - mlp_all + mlp_all * dims.moe_top_k \
            / dims.num_experts
    else:
        lp_active = lp
    flops_layer = 6.0 * tokens_loc * lp_active
    # causal attention scores+pv: fwd 2·b·s²·h ≈, ×3 for bwd
    flops_attn = 6.0 * b_loc * dims.seq_len * dims.seq_len * h / 2
    layers_per_stage = dims.num_layers / s.pp
    flops_dev = (flops_layer + flops_attn) * layers_per_stage \
        / (s.tp * s.cp)
    # remat recomputes forward work during bwd: fwd share is 1/3 of 6N
    # (full = whole block fwd again; selective ≈ attention+norms only) —
    # factors shared with the runtime ledger (engine.memory)
    flops_dev *= compute_factor(s.remat)
    # embedding + lm head on the last/first stage
    flops_head = 6.0 * tokens_loc * dims.vocab * h / (s.tp * s.cp)
    t_compute = (flops_dev + flops_head) \
        / (topo.mxu_efficiency * topo.peak_flops)

    # ---- tp comm ----------------------------------------------------------
    act_bytes = b_loc * seq_loc * h * dims.bytes_per_el
    t_tp = 4.0 * _ring_allreduce_time(act_bytes, s.tp, topo.ici_bw) \
        * layers_per_stage if s.tp > 1 else 0.0

    # ---- cp ring comm -----------------------------------------------------
    kv_bytes = 2.0 * b_loc * seq_loc * \
        (dims.num_kv_heads * (h / dims.num_heads)) * dims.bytes_per_el
    # fwd ring + bwd ring with dkv piggyback (~2x)
    t_cp = 3.0 * (s.cp - 1) * kv_bytes / topo.ici_bw * layers_per_stage \
        if s.cp > 1 else 0.0

    # ---- ep a2a (MoE dispatch + combine) ----------------------------------
    # two fp32 capacity-buffer exchanges forward + the mirrored pair in
    # backward (a2a transposes to a2a), each moving the (ep-1)/ep
    # remote share of capacity_factor·tokens·k·h per device per layer
    t_ep = 0.0
    if s.ep > 1 and dims.num_experts > 0:
        buf_bytes = dims.moe_capacity_factor * tokens_loc \
            * max(dims.moe_top_k, 1) * h * 4.0
        t_ep = 4.0 * (s.ep - 1) / s.ep * buf_bytes / topo.ici_bw \
            * layers_per_stage

    # ---- dp grad sync -----------------------------------------------------
    # expert params are ep-sharded (rule "expert" → "ep"): their grads
    # reduce over dp from a 1/ep shard per device; dense params carry
    # the full tp·pp shard
    expert_bytes = dims.num_layers * dims.layer_expert_params() \
        * dims.bytes_per_el
    dense_bytes = dims.total_params() * dims.bytes_per_el - expert_bytes
    param_bytes_dev = dense_bytes / (s.tp * s.pp) \
        + expert_bytes / (s.tp * s.pp * max(s.ep, 1))
    t_dp = _ring_allreduce_time(param_bytes_dev, s.dp, topo.ici_bw) \
        * (1.0 - topo.dp_overlap) if s.dp > 1 else 0.0

    # ---- pp bubble --------------------------------------------------------
    nm = max(s.num_microbatches, 1)
    bubble = (nm + s.pp - 1) / nm if s.pp > 1 else 1.0

    step = (t_compute + t_tp + t_cp + t_ep) * bubble + t_dp

    # ---- memory -----------------------------------------------------------
    # one formula for planner and runtime: the memory-plane ledger
    # (engine.memory.estimate_breakdown) — weights + (ZeRO-sharded)
    # grads/moments, per-remat activation factors, scan-flush liveness
    # (nm+pp-1 live microbatches under pp — validated against XLA
    # memory_analysis), scaled by the AOT-measured calibration.
    bd = estimate_breakdown(dims, s, act_scale=topo.act_scale(s.remat))

    return CostBreakdown(step, t_compute * bubble, t_tp * bubble,
                         t_cp * bubble, t_dp, bubble, bd.peak_bytes,
                         mem_params=bd.params_bytes + bd.grads_bytes,
                         mem_opt=bd.opt_bytes,
                         mem_act_per_microbatch=bd.act_bytes_per_microbatch,
                         ep_comm=t_ep * bubble)
