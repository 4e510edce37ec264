"""Strategy IR — the TPU-native equivalent of Hetu's ds-parallel JSON.

The reference drives all parallelism from a JSON strategy file (per-module
``{split, dup, device_group_union, zero, recompute}`` — SURVEY §2.5,
``generate_llama_4d_config.py``) which a C++ pass propagates through the graph
as ``DistributedStates``. Here a :class:`Strategy` compiles directly to
``(jax.sharding.Mesh, AxisRules)``: the mesh axes carry the dp/pp/cp/tp/ep
degrees and the rules map each parameter's *logical* axes onto mesh axes.
GSPMD then does what ``SubstituteCommOp`` did — inserting the collectives
implied by producer/consumer shardings.

Strategies serialize to/from JSON so external planners (Galvatron-style
search, Malleus replanning) can emit them, and so hot switching is a matter
of re-sharding the train state under a new Strategy.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, PartitionSpec as P

# Canonical mesh axis order lives in core.mesh (single source of truth).
from hetu_tpu.core.mesh import MESH_AXES


@dataclasses.dataclass(frozen=True)
class Strategy:
    """One hybrid-parallel configuration (reference: one entry of
    ``DistributedStatesHierarchy``)."""

    dp: int = 1          # data parallel
    tp: int = 1          # tensor parallel (Megatron-style)
    pp: int = 1          # pipeline stages
    cp: int = 1          # context parallel (ring attention)
    ep: int = 1          # expert parallel (MoE)
    zero: bool = False   # ZeRO-1: shard optimizer state over dp
    fsdp: bool = False   # ZeRO-3-style param sharding over dp
    num_microbatches: int = 1   # pipeline / grad-accumulation microbatches
    remat: str = "none"          # "none" | "full" | "selective"
    offload: bool = False        # host offload of remat'd activations
    cp_layout: str = "zigzag"    # "zigzag" (load-balanced causal ring — the
                                 # reference's SYM split) | "contiguous"
    cp_impl: str = "ring"        # "ring" (KV ppermute ring, reference
                                 # AttnCommRing) | "ulysses" (all_to_all
                                 # head scatter — beyond-reference)
    sp: bool = False             # Megatron-SP: norms/residuals shard seq
                                 # over tp (activation memory / tp)
    remat_mask: Optional[tuple] = None   # per-layer recompute flags
                                 # (search_layerwise output; None = uniform)
    unroll: bool = False         # unroll the layer scan (straight-line
                                 # code: faster per stage, compile time
                                 # grows with layers; under pp>1 the
                                 # PER-STAGE scan unrolls)
    tp_overlap: str = "off"      # "ring": decompose the Megatron-SP
                                 # all-gather→matmul / matmul→reduce-
                                 # scatter pairs into ppermute rings of
                                 # chunk matmuls so each comm hop hides
                                 # behind partial compute
                                 # (parallel.overlap, ASPLOS'23-style);
                                 # "off": GSPMD collectives
    pp_overlap: bool = False     # double-buffer the pipeline ring: the
                                 # ppermute of tick t's activations is
                                 # issued alongside tick t+1's stage
                                 # compute (one extra in-flight buffer
                                 # and pp-1 extra ticks buy comm that
                                 # fully hides behind the stage body)
    fsdp_overlap: str = "off"    # "ring": reformulate the ZeRO-3 param
                                 # all-gather as PER-BLOCK ppermute-ring
                                 # gathers driven from the model's block
                                 # structure — block k+1's gather
                                 # overlaps block k's compute
                                 # (parallel.overlap.ring_gather_block_
                                 # params); "off": one monolithic GSPMD
                                 # all-gather (always the fallback for
                                 # models without a stacked block list)
    delay_grad_sync: bool = False  # in-jit grad accumulation
                                 # (num_microbatches>1, pp=1): keep
                                 # per-microbatch grads group-local
                                 # in the lax.scan (leading group-
                                 # sharded accumulator dim) and reduce
                                 # ONCE per optimizer update instead of
                                 # once per microbatch — the scan-path
                                 # twin of build_grad_accum_steps(
                                 # delay_grad_sync=True). With ep > 1
                                 # the group is dp×ep: dense grads
                                 # reduce over dp×ep lanes, expert
                                 # grads over dp lanes only (their ep
                                 # sum already happened through the
                                 # backward all_to_all)
    ep_overlap: str = "off"      # "chunk": decompose the MoE
                                 # dispatch-a2a → expert FFN →
                                 # combine-a2a into ep_chunks capacity
                                 # slices inside the manual shard_map,
                                 # so chunk i's combine-a2a (and chunk
                                 # i+1's dispatch-a2a) hide behind
                                 # chunk i's expert matmul (the EP twin
                                 # of tp_overlap/fsdp_overlap;
                                 # bitwise-identical to "off")
    ep_chunks: int = 2           # capacity slices for ep_overlap=
                                 # "chunk" (clamped to the capacity)

    # -- derived -----------------------------------------------------------
    @property
    def num_devices(self) -> int:
        return self.dp * self.tp * self.pp * self.cp * self.ep

    @property
    def effective_cp_layout(self) -> str:
        """The layout actually in force. The ring path (cp_impl="ring")
        honors ``cp_layout`` both standalone and inside the pipeline
        region (pp>1 binds cp as a manual shard_map axis and runs the
        ring core per stage — reference composes AttnCommRing with any
        pipeline, ``ParallelAttention.h:391-470`` +
        ``generate_llama_4d_config.py:11-51``). Ulysses reassembles
        global order, so it is always contiguous. Both ``shard_batch``
        and ``make_plan`` consult this single source of truth."""
        if self.cp == 1 or self.cp_impl == "ulysses":
            return "contiguous"
        return self.cp_layout

    def mesh_shape(self) -> dict[str, int]:
        return {"pp": self.pp, "dp": self.dp, "ep": self.ep,
                "cp": self.cp, "tp": self.tp}

    def build_mesh(self, devices=None) -> Mesh:
        from hetu_tpu.core.mesh import make_mesh
        return make_mesh(self.mesh_shape(), devices=devices)

    def axis_rules(self) -> "AxisRules":
        from hetu_tpu.parallel.sharding import AxisRules
        rules = {
            "vocab": "tp",
            "mlp": "tp",
            "heads": "tp",
            "kv_heads": "tp",
            "expert": "ep",
            "layers": "pp",
            "embed": "dp" if self.fsdp else None,
        }
        return AxisRules(rules)

    def data_spec(self, ndim: int = 2) -> P:
        """PartitionSpec for a (batch, seq, ...) input batch: batch over
        dp×ep, seq over cp."""
        batch_axes = ("dp", "ep") if self.ep > 1 else "dp"
        parts = [batch_axes, "cp"] + [None] * (ndim - 2)
        return P(*parts[:ndim])

    # -- serialization (planner interface) ---------------------------------
    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, s: str) -> "Strategy":
        return cls(**json.loads(s))

    def validate(self, n_devices: Optional[int] = None):
        if self.num_microbatches < 1:
            raise ValueError("num_microbatches must be >= 1")
        if self.cp_layout not in ("zigzag", "contiguous"):
            raise ValueError(f"unknown cp_layout {self.cp_layout!r}")
        if self.cp_impl not in ("ring", "ulysses"):
            raise ValueError(f"unknown cp_impl {self.cp_impl!r}")
        if self.tp_overlap not in ("off", "ring"):
            raise ValueError(f"unknown tp_overlap {self.tp_overlap!r}")
        if self.fsdp_overlap not in ("off", "ring"):
            raise ValueError(f"unknown fsdp_overlap {self.fsdp_overlap!r}")
        if self.delay_grad_sync and self.fsdp:
            raise ValueError(
                "delay_grad_sync=True is incompatible with fsdp: params "
                "are dp-sharded, so group-local gradients would require "
                "the param all-gather the delay is meant to avoid")
        if self.ep_overlap not in ("off", "chunk"):
            raise ValueError(f"unknown ep_overlap {self.ep_overlap!r}")
        if self.ep_chunks < 1:
            raise ValueError("ep_chunks must be >= 1")
        if self.pp > 1 and self.num_microbatches % self.pp != 0:
            raise ValueError(
                f"num_microbatches ({self.num_microbatches}) must be a "
                f"multiple of pp ({self.pp}) for the pipeline schedule")
        if n_devices is not None and self.num_devices > n_devices:
            raise ValueError(
                f"strategy needs {self.num_devices} devices, have {n_devices}")
        return self
