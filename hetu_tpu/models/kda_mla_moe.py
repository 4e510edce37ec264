"""Decoders that mix Kimi-Delta-Attention layers with latent-attention
(MLA) layers over group-limited routed experts (the Ling-3.0 / Kimi
Linear family).

The config dataclass keeps the published key names. Per layer (``x`` is
``(T, hidden)``, no biases, ``n`` = RMSNorm):
``h = x + Mixer(n1(x))``, ``y = h + FFN(n2(h))``.

- mixer of layer ``i``: latent attention where ``(i + 1) %
  layer_group_size == 0``
  (:class:`~hetu_tpu.nn.parallel.LatentAttention`, RMSNorm on q and the
  RoPE key where ``use_qk_norm``: a token's cache is ONE latent row),
  else Kimi Delta Attention
  (:class:`~hetu_tpu.nn.parallel.KimiDeltaAttention`: a slot's cache is
  a float32 state and the convolution's tail, whatever the context);
- FFN of the first ``first_k_dense_replace`` layers: SwiGLU of width
  ``intermediate_size``; they run one by one OUTSIDE the layer scans;
- FFN of every other layer: a sigmoid router ``n_routed_experts`` wide
  with a selection bias, GROUP-LIMITED (``n_group`` groups of
  consecutive experts, the ``topk_group`` best stay), top
  ``num_experts_per_tok``, weights ``routed_scaling_factor * s_e / sum
  s`` (:class:`~hetu_tpu.nn.moe.ExpertShareMoE`; ``local_experts`` is
  the share held here — one group a chip in the deployment), beside
  ONE shared SwiGLU expert.

The two mixers differ in parameter shapes, so RUNS of like layers are
scanned (:class:`~hetu_tpu.nn.parallel.StackedBlocks`) and
:class:`HybridBlocks` strings the runs; each kind counts ITS OWN layers
in its cache leaves. The caches are ``(latent rows, states, tails)``:
one paged leaf over the MLA layers and two slot leaves over the KDA
layers (:meth:`HybridBlocks.init_paged_caches`).

A final RMSNorm, then an UNTIED head. Operands: ``compute_dtype``
("bfloat16" to serve: bf16 operands, float32 accumulation) is what the
projections, the latent products, the shared and expert matmuls take;
the residual stream, the norms, the router, the convolution, the state
with its decays, ``beta``, the triangular solve and the L2 norms, and
the logits stay float32.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from hetu_tpu.core.dtypes import autocast
from hetu_tpu.nn.layers import RMSNorm
from hetu_tpu.nn.module import Module, normal_init
from hetu_tpu.nn.moe import ExpertShareMoE, count_group_held
from hetu_tpu.nn.parallel import (
    KimiDeltaAttention, LatentAttention, LayerKV, ParallelMLP,
    SlotStateNotSupported, StackedBlocks, VocabParallelEmbedding,
)
from hetu_tpu.parallel.sharding import act_constrain

KDA, MLA = "kda", "mla"


@dataclasses.dataclass(frozen=True)
class KDAMLAMoEConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144            # the dense layers' FFN
    moe_intermediate_size: int = 768         # ONE routed expert
    moe_shared_expert_intermediate_size: int = 768
    num_hidden_layers: int = 42
    num_attention_heads: int = 32
    head_dim: int = 128                      # the KDA heads' dk = dv
    layer_group_size: int = 6
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    use_qk_norm: bool = True
    #: the router's width (the experts of the whole deployment)
    n_routed_experts: int = 512
    #: ``(first, count)`` of them held here (None: all)
    local_experts: Optional[tuple] = None
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    first_k_dense_replace: int = 2
    rms_norm_eps: float = 1e-6
    rope_theta: float = 6000000.0
    max_position_embeddings: int = 131072
    #: the gain the MLA q and RoPE-key norms are drawn at (1: a
    #: checkpoint's would be learned)
    qk_norm_gain: float = 1.0
    #: the arena row's width (None: ``kv_lora_rank + qk_rope_head_dim``)
    stored_row: Optional[int] = None
    #: positions the RoPE table covers (None = all the model declares)
    rope_positions: Optional[int] = None
    compute_dtype: str = "float32"
    init_std: float = 0.02

    def __post_init__(self):
        if self.q_lora_rank is not None:
            raise NotImplementedError("query compression (q_lora_rank)")
        if self.local_experts is not None:
            object.__setattr__(self, "local_experts",
                               tuple(self.local_experts))
        kinds = self.mixer_types
        if MLA not in kinds[self.first_k_dense_replace:] \
                or KDA not in kinds:
            raise ValueError(
                f"{self.num_hidden_layers} layers in groups of "
                f"{self.layer_group_size} behind "
                f"{self.first_k_dense_replace} dense ones: at least one "
                f"scanned latent layer (its attention speaks for the "
                f"arena) and one delta-rule layer")

    @property
    def mixer_types(self) -> tuple:
        return tuple(MLA if (i + 1) % self.layer_group_size == 0 else KDA
                     for i in range(self.num_hidden_layers))

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def max_positions(self) -> int:
        return self.rope_positions or self.max_position_embeddings

    @classmethod
    def tiny(cls, **kw):
        """Test size: 7 layers in groups of 3 (KDA, KDA, MLA, ...), the
        first a dense one; 4 heads of 16; latent 32, nope 16 / rope 8 /
        v 16; a router 16 wide in 4 groups of which 2 stay, top-3,
        all held."""
        return cls(**{**dict(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=32, num_hidden_layers=7,
            num_attention_heads=4, head_dim=16, layer_group_size=3,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=16, num_experts_per_tok=3,
            n_group=4, topk_group=2, first_k_dense_replace=1,
            max_position_embeddings=256, qk_norm_gain=2.0), **kw})


class HybridBlock(Module):
    """One layer: a mixer of ``kind``, then a dense SwiGLU (``dense``)
    or the routed experts beside the shared one."""
    returns_aux = False

    def __init__(self, cfg: KDAMLAMoEConfig, kind: str, *, dense: bool):
        super().__init__()
        init = normal_init(cfg.init_std)
        self.kind = kind
        self.norm1 = RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps)
        self.norm2 = RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps)
        if kind == MLA:
            self.attn = LatentAttention(
                cfg.hidden_size, cfg.num_attention_heads,
                kv_rank=cfg.kv_lora_rank, nope_dim=cfg.qk_nope_head_dim,
                rope_dim=cfg.qk_rope_head_dim, v_dim=cfg.v_head_dim,
                stored_row=cfg.stored_row, rope_theta=cfg.rope_theta,
                norm_eps=cfg.rms_norm_eps,
                max_positions=cfg.max_positions,
                qk_norm=cfg.use_qk_norm, qk_gain=cfg.qk_norm_gain,
                init=init)
        else:
            self.attn = KimiDeltaAttention(
                cfg.hidden_size, cfg.num_attention_heads,
                head_dim=cfg.head_dim,
                conv_size=cfg.short_conv_kernel_size,
                lower_bound=cfg.kda_lower_bound,
                norm_eps=cfg.rms_norm_eps, init=init)
        self._dense = dense
        if dense:
            self.mlp = ParallelMLP(cfg.hidden_size, cfg.intermediate_size,
                                   bias=False, gated=True)
        else:
            self.shared = ParallelMLP(
                cfg.hidden_size, cfg.moe_shared_expert_intermediate_size,
                bias=False, gated=True)
            self.moe = ExpertShareMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, k=cfg.num_experts_per_tok,
                local_experts=cfg.local_experts, select_bias=True,
                scale=cfg.routed_scaling_factor, n_group=cfg.n_group,
                topk_group=cfg.topk_group, init=init)
            #: the grouped expert matmul cannot read through the layer
            #: scan's slice (``StackedBlocks.decode``)
            self.unsliced = (("moe", "wg"), ("moe", "wi"), ("moe", "wo"))
            held = self.moe.local_experts[1]
            self.layer_stats = {
                "moe_local_sizes": ((held,), jnp.int32,
                                    self.moe.count_share),
                "moe_group_held": ((2,), jnp.int32, count_group_held)}
        self._policy = {"float32": "fp32",
                        "bfloat16": "bf16"}[cfg.compute_dtype]

    def _ffn(self, params, u):
        """``(FFN(u), the routed experts' stats or None)``."""
        if self._dense:
            return self.mlp(params["mlp"], u), None
        with jax.named_scope("hetu.moe_shared"):
            shared = self.shared(params["shared"], u)
        routed, st = self.moe(params["moe"], u, return_stats=True)
        return shared.astype(jnp.float32) + routed.astype(jnp.float32), \
            {"moe_local_sizes": st["sizes"],
             "moe_group_held": st["group_held"]}

    def __call__(self, params, x, *, positions=None, segment_ids=None,
                 attn_impl="auto", kv_cache=None, slot_mask=None,
                 block_tables=None, row_mask=None,
                 attn_kernel="reference", pack=None, w8a8=None,
                 w8a8_wq=None, lora=None, dropout_key=None,
                 return_kv=False):
        if w8a8 is not None or lora or dropout_key is not None:
            raise NotImplementedError(
                "kda_mla_moe has no W8A8, LoRA or dropout lane")
        new_cache = None
        u = self.norm1(params["norm1"], x)              # float32
        with autocast(self._policy):
            if kv_cache is not None:
                a, new_cache = self.attn(
                    params["attn"], u, positions=positions,
                    kv_cache=kv_cache, slot_mask=slot_mask,
                    block_tables=block_tables, row_mask=row_mask,
                    attn_kernel=attn_kernel, pack=pack)
            else:
                a = self.attn(params["attn"], u, positions=positions,
                              segment_ids=segment_ids, attn_impl=attn_impl,
                              return_kv=return_kv)
        h = x + a.astype(x.dtype)
        u = self.norm2(params["norm2"], h)              # float32
        with autocast(self._policy):
            f, stats = self._ffn(params, u)
        y = h + f.astype(x.dtype)
        if kv_cache is None:
            return act_constrain(y, "tokens")
        return (y, new_cache) if self._dense else (y, new_cache, stats)


class HybridBlocks(Module):
    """The layers: the leading dense ones one by one, then runs of like
    expert blocks, each run ONE scan; the interface is
    ``StackedBlocks``'s as the serving engine uses it. ``block`` is the
    latent expert block: its attention speaks for the arena (heads, row
    width) and its ``layer_stats`` for every expert layer."""

    def __init__(self, cfg: KDAMLAMoEConfig):
        super().__init__()
        self.num_layers = cfg.num_hidden_layers
        kinds = cfg.mixer_types
        k = cfg.first_k_dense_replace
        count = {KDA: 0, MLA: 0}
        self.dense, self.dense_at = [], []
        for kind in kinds[:k]:
            self.dense.append(HybridBlock(cfg, kind, dense=True))
            self.dense_at.append((kind, count[kind]))
            count[kind] += 1
        self.runs, self.run_kinds = [], []
        i = k
        while i < len(kinds):
            kind, n = kinds[i], 1
            while i + n < len(kinds) and kinds[i + n] == kind:
                n += 1
            self.runs.append(StackedBlocks(
                lambda kind=kind: HybridBlock(cfg, kind, dense=False), n,
                first_layer=count[kind]))
            self.run_kinds.append(kind)
            count[kind] += n
            i += n
        self.n_kda, self.n_mla = count[KDA], count[MLA]
        self.n_expert_layers = len(kinds) - k
        self._mla = next(r for r, kd in zip(self.runs, self.run_kinds)
                         if kd == MLA)
        self._kda = next(
            b for b in self.dense + [r.block for r in self.runs]
            if b.kind == KDA)

    @property
    def block(self) -> Module:
        return self._mla.block

    def __call__(self, params, x, **kwargs):
        for i, blk in enumerate(self.dense):
            x = blk(params["dense"][str(i)], x, **kwargs)
        for i, run in enumerate(self.runs):
            x = run(params["runs"][str(i)], x, **kwargs)
        return x

    # -- the caches ----------------------------------------------------------
    def init_paged_caches(self, n_blocks: int, block_size: int, dtype,
                          slots: int, sharding=None) -> tuple:
        """``(latent rows)`` over the MLA layers' pages, and ``(states,
        tails)`` of the KDA layers over the slots."""
        (shape,) = self.block.attn.kv_leaf_shapes()
        latent = jnp.zeros(
            (self.n_mla, n_blocks, block_size, math.prod(shape)), dtype,
            device=sharding)
        return (latent,) + self._kda.attn.init_leaves(
            self.n_kda, slots, sharding)

    def cache_bytes(self, itemsize: int) -> dict:
        """``kv_row_bytes{kind}`` / ``kv_state_bytes{kind}``: a token's
        bytes over all latent layers (as stored, and as needed), a
        slot's state and tail over all delta-rule layers."""
        attn = self.block.attn
        return {"row": {"stored": attn.head_dim * itemsize * self.n_mla,
                        "needed": attn.kv_needed_elements() * itemsize
                        * self.n_mla},
                "state": {"slot": self._kda.attn.state_bytes()
                          * self.n_kda}}

    def refuse_serving(self, **asked) -> None:
        """An honest refusal, by name, of what assumes a cache of token
        rows in pages alone (``asked``: feature -> whether it is on)."""
        for what, on in asked.items():
            if on:
                raise SlotStateNotSupported(
                    f"{what} is not available over a per-slot recurrent "
                    f"state and convolution tail: it would need them "
                    f"snapshotted (or rolled back) with the pages")

    def decode(self, params, x, caches, *, with_stats=False,
               w8a8_mask=None, w8a8_wq=None, lora=None, **kwargs):
        if w8a8_mask is not None or w8a8_wq is not None or lora:
            raise NotImplementedError(
                "kda_mla_moe has no W8A8 or LoRA lane")
        caches = tuple(caches)
        leaves = {MLA: caches[:1], KDA: caches[1:]}
        for i, (blk, (kind, at)) in enumerate(
                zip(self.dense, self.dense_at)):
            x, leaves[kind] = blk(
                params["dense"][str(i)], x,
                kv_cache=LayerKV(leaves[kind], jnp.asarray(at, jnp.int32)),
                **kwargs)
        stats = []
        for i, (run, kind) in enumerate(zip(self.runs, self.run_kinds)):
            x, leaves[kind], st = run.decode(
                params["runs"][str(i)], x, leaves[kind], with_stats=True,
                **kwargs)
            stats.append(st)
        caches = tuple(leaves[MLA]) + tuple(leaves[KDA])
        if not with_stats:
            return x, caches
        return x, caches, {name: jnp.concatenate([s[name] for s in stats])
                           for name in stats[0]}

    def layer_stats_zeros(self) -> dict:
        return {name: jnp.zeros((self.n_expert_layers,) + tuple(shape),
                                dtype)
                for name, (shape, dtype, _) in
                self.block.layer_stats.items()}

    def prefill(self, *args, **kwargs):
        raise SlotStateNotSupported(
            "StackedBlocks.prefill (the CP-prefill lane) returns "
            "per-head (k, v) of every layer; the delta-rule layers have "
            "none and the latent layers cache one row a token")


class KDAMLAMoEForCausalLM(Module):
    def __init__(self, cfg: KDAMLAMoEConfig):
        super().__init__()
        self.cfg = cfg
        init = normal_init(cfg.init_std)
        self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size,
                                          init=init)
        self.blocks = HybridBlocks(cfg)
        self.final_norm = RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps)
        # untied: the same (V, E) layout the tied models' head has
        self.lm_head = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size, init=init)

    def _head_weight(self, params):
        return params["lm_head"]["weight"]

    def embed(self, params, input_ids, *, positions=None):
        del positions          # rotary positions are applied per layer
        return act_constrain(
            self.wte(params["wte"], input_ids).astype(jnp.float32),
            "tokens")

    def hidden_norm(self, params, h):
        return self.final_norm(params["final_norm"], h)

    def hidden_states(self, params, input_ids, *, positions=None,
                      segment_ids=None, attn_impl="auto"):
        h = self.embed(params, input_ids)
        h = self.blocks(params["blocks"], h, positions=positions,
                        segment_ids=segment_ids, attn_impl=attn_impl)
        return self.hidden_norm(params, h)

    def __call__(self, params, input_ids, **kwargs):
        h = self.hidden_states(params, input_ids, **kwargs)
        logits = jnp.einsum(
            "bse,ve->bsv", h.astype(jnp.float32),
            self._head_weight(params).astype(jnp.float32))
        return act_constrain(logits, "logits")
