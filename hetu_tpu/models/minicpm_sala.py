"""MiniCPM-SALA (``model_type: minicpm_sala``): a dense decoder whose
layers mix two kinds of sequence mixer, ``mixer_types[l]``:

- ``"minicpm4"`` — block-sparse attention, NoPE, 32 query heads over 2
  kv heads, ``qk_norm``, an output gate
  (:class:`~hetu_tpu.nn.parallel.BlockSparseAttention`): a token's cache
  is K, V and a share of a compressed key; a query reads the ``topk``
  pages it chooses;
- ``"lightning-attn"`` — linear attention with a per-head decay, RoPE,
  an output norm and gate
  (:class:`~hetu_tpu.nn.parallel.LightningAttention`): the cache is a
  float32 state per SLOT, whatever the context.

Frame (MiniCPM's μP scalings; ``u`` the RMS-normed input, no biases):
``x0 = scale_emb * E[ids]``; ``h = x + a * Mixer(n1(x))``, ``y = h + a *
MLP(n2(h))`` with ``a = scale_depth / sqrt(published_depth)`` — the
PUBLISHED depth, a constant of the model whatever depth is held — and a
SwiGLU MLP; a final RMSNorm; logits ``(h / (hidden_size /
dim_model_base)) W_head^T``, untied.

The two kinds differ in parameter shapes, so RUNS of like layers are
scanned (:class:`~hetu_tpu.nn.parallel.StackedBlocks`) and
:class:`SalaBlocks` strings the runs; each kind counts ITS OWN layers in
its cache leaves — no page for a layer that has no keys. The caches are
``(K, V, stride means, states)``: three paged leaves over the sparse
layers and one slot leaf over the lightning layers
(:meth:`SalaBlocks.init_paged_caches`).

Operands: ``compute_dtype`` ("bfloat16" to serve: bf16 operands,
float32 accumulation) is what the projections, scores, values and the
MLP take; the residual stream, the norms, the selection, the lightning
state and its decay, and the logits stay float32.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp

from hetu_tpu.core.dtypes import autocast
from hetu_tpu.nn.layers import RMSNorm
from hetu_tpu.nn.module import Module, normal_init
from hetu_tpu.nn.parallel import (
    BlockSparseAttention, LightningAttention, ParallelMLP,
    SlotStateNotSupported, StackedBlocks, VocabParallelEmbedding,
)
from hetu_tpu.parallel.sharding import act_constrain

SPARSE, LINEAR = "minicpm4", "lightning-attn"


@dataclasses.dataclass(frozen=True)
class MiniCPMSALAConfig:
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_head_dim: int = 128
    #: the mixers of the layers HELD, in order
    mixer_types: tuple = (SPARSE,) + (LINEAR,) * 3
    #: the depth ``scale_depth`` is divided by the root of: the
    #: published model's, whatever is held
    published_depth: int = 32
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    max_position_embeddings: int = 524288
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    # the sparse layers' sizes (the family's ``sparse_config``)
    block_size: int = 64
    kernel_size: int = 32
    kernel_stride: int = 16
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    #: the gain the q and k norms are drawn at (1: a checkpoint's
    #: would be learned)
    qk_norm_gain: float = 1.0
    #: positions the RoPE table covers (None = all the model declares)
    rope_positions: Optional[int] = None
    compute_dtype: str = "float32"
    init_std: float = 0.02

    def __post_init__(self):
        object.__setattr__(self, "mixer_types", tuple(self.mixer_types))
        bad = set(self.mixer_types) - {SPARSE, LINEAR}
        if bad or SPARSE not in self.mixer_types:
            raise ValueError(
                f"mixer_types holds {sorted(bad) or 'no sparse layer'}: "
                f"each is {SPARSE!r} or {LINEAR!r}, at least one the "
                f"former (its attention speaks for the arena)")

    @property
    def num_layers(self) -> int:
        return len(self.mixer_types)

    num_hidden_layers = num_layers

    @property
    def max_positions(self) -> int:
        return self.rope_positions or self.max_position_embeddings

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / self.published_depth ** 0.5

    def runs(self) -> list:
        """``[(kind, layers)]``: the runs of like layers, in order."""
        out = []
        for kind in self.mixer_types:
            if out and out[-1][0] == kind:
                out[-1][1] += 1
            else:
                out.append([kind, 1])
        return [tuple(r) for r in out]

    @classmethod
    def tiny(cls, **kw):
        """Test size: 2 sparse + 3 lightning layers, 4 query heads over
        2 kv heads of 16, 4 lightning heads of 16, pages of 4 tokens,
        top-4 (first block, the query's own and the one before forced:
        one place competed for), compressed keys of 2 tokens every 1."""
        return cls(**{**dict(
            vocab_size=128, hidden_size=64, intermediate_size=96,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            lightning_nh=4, lightning_head_dim=16,
            mixer_types=(SPARSE, LINEAR, LINEAR, SPARSE, LINEAR),
            published_depth=8, max_position_embeddings=256,
            block_size=4, kernel_size=2, kernel_stride=1, topk=4,
            init_blocks=1, window_size=4, qk_norm_gain=2.0), **kw})


class SalaBlock(Module):
    """One layer: a mixer of ``kind``, then the SwiGLU MLP, each behind
    its RMSNorm and weighted ``residual_scale`` into the stream."""
    returns_aux = False

    def __init__(self, cfg: MiniCPMSALAConfig, kind: str):
        super().__init__()
        init = normal_init(cfg.init_std)
        self.kind = kind
        self.norm1 = RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps)
        self.norm2 = RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps)
        if kind == SPARSE:
            self.attn = BlockSparseAttention(
                cfg.hidden_size, cfg.num_attention_heads,
                num_kv_heads=cfg.num_key_value_heads,
                head_dim=cfg.head_dim, block_size=cfg.block_size,
                kernel_size=cfg.kernel_size,
                kernel_stride=cfg.kernel_stride, topk=cfg.topk,
                init_blocks=cfg.init_blocks, window_size=cfg.window_size,
                norm_eps=cfg.rms_norm_eps, qk_gain=cfg.qk_norm_gain,
                init=init)
            #: what the engine's commit hands the host (the block's own
            #: result is the lane's pair; ``SalaBlocks.decode`` places it)
            self.layer_stats = {"sparse_pages": (
                (4,), jnp.int32, count_sparse_pages)}
        else:
            self.attn = LightningAttention(
                cfg.hidden_size, cfg.lightning_nh,
                head_dim=cfg.lightning_head_dim,
                rope_theta=cfg.rope_theta,
                max_positions=cfg.max_positions,
                norm_eps=cfg.rms_norm_eps, qk_gain=cfg.qk_norm_gain,
                init=init)
        self.mlp = ParallelMLP(cfg.hidden_size, cfg.intermediate_size,
                               bias=False, gated=True)
        self._alpha = cfg.residual_scale
        self._policy = {"float32": "fp32",
                        "bfloat16": "bf16"}[cfg.compute_dtype]

    def __call__(self, params, x, *, positions=None, segment_ids=None,
                 attn_impl="auto", kv_cache=None, slot_mask=None,
                 block_tables=None, row_mask=None,
                 attn_kernel="reference", pack=None, w8a8=None,
                 w8a8_wq=None, lora=None, dropout_key=None,
                 return_kv=False):
        if w8a8 is not None or lora or dropout_key is not None:
            raise NotImplementedError(
                "minicpm_sala has no W8A8, LoRA or dropout lane")
        stats = None
        u = self.norm1(params["norm1"], x)              # float32
        with autocast(self._policy):
            if kv_cache is not None:
                a, new_cache, *stats = self.attn(
                    params["attn"], u, positions=positions,
                    kv_cache=kv_cache, slot_mask=slot_mask,
                    block_tables=block_tables, row_mask=row_mask,
                    attn_kernel=attn_kernel, pack=pack)
            else:
                a = self.attn(params["attn"], u, positions=positions,
                              segment_ids=segment_ids, attn_impl=attn_impl,
                              return_kv=return_kv)
        h = x + self._alpha * a.astype(x.dtype)
        u = self.norm2(params["norm2"], h)              # float32
        with autocast(self._policy):
            f = self.mlp(params["mlp"], u)
        y = h + self._alpha * f.astype(x.dtype)
        if kv_cache is None:
            return act_constrain(y, "tokens")
        if stats:
            return y, new_cache, {"sparse_pages": stats[0]}
        return y, new_cache


def count_sparse_pages(values, tokens=None) -> None:
    """``layer_stats`` on the host: ``values (sparse layers, 4)`` — the
    pages the rows of a lane chose and could see, summed over its live
    rows and kv heads, as ``[chosen, visible]`` of the decode rows then
    of the prefill pack (a lane fills its own pair:
    :meth:`SalaBlocks.decode`) — into
    ``serving_sparse_pages_total{state, lane}``."""
    import numpy as np
    from hetu_tpu import telemetry
    v = np.asarray(values, np.int64).sum(axis=0)
    c = telemetry.get_registry().counter(
        "serving_sparse_pages_total",
        "pages the block-sparse attention's rows chose / could see")
    for i, lane in enumerate(("decode", "prefill")):
        if v[2 * i + 1]:
            c.inc(int(v[2 * i]), state="chosen", lane=lane)
            c.inc(int(v[2 * i + 1]), state="visible", lane=lane)


class SalaBlocks(Module):
    """The layers as runs of like blocks, each run ONE scan; the
    interface is ``StackedBlocks``'s as the serving engine uses it.
    ``block`` is the sparse block: its attention speaks for the arena
    (heads, head size, page size)."""

    def __init__(self, cfg: MiniCPMSALAConfig):
        super().__init__()
        self.num_layers = cfg.num_layers
        self.run_kinds = [k for k, _ in cfg.runs()]
        first = {SPARSE: 0, LINEAR: 0}
        self.runs = []
        for kind, n in cfg.runs():
            self.runs.append(StackedBlocks(
                lambda kind=kind: SalaBlock(cfg, kind), n,
                first_layer=first[kind]))
            first[kind] += n
        self.n_sparse, self.n_linear = first[SPARSE], first[LINEAR]
        self._sparse = next(r for r, k in zip(self.runs, self.run_kinds)
                            if k == SPARSE)
        self._linear = next((r for r, k in zip(self.runs, self.run_kinds)
                             if k == LINEAR), None)

    @property
    def block(self) -> Module:
        return self._sparse.block

    def __call__(self, params, x, **kwargs):
        for i, run in enumerate(self.runs):
            x = run(params["runs"][str(i)], x, **kwargs)
        return x

    # -- the caches ----------------------------------------------------------
    def init_paged_caches(self, n_blocks: int, block_size: int, dtype,
                          slots: int, sharding=None) -> tuple:
        """``(K, V, stride means)`` over the sparse layers' pages and
        the lightning layers' states over the slots."""
        leaves = self.block.attn.init_leaves(
            self.n_sparse, n_blocks, block_size, dtype, sharding)
        if self._linear is not None:
            leaves += self._linear.block.attn.init_leaves(
                self.n_linear, slots, sharding)
        return leaves

    def cache_bytes(self, itemsize: int) -> dict:
        """``kv_row_bytes{kind}`` / ``kv_state_bytes{kind}``: a token's
        bytes over all sparse layers by leaf, a slot's state over all
        lightning layers."""
        rows = {k: v * self.n_sparse for k, v in
                self.block.attn.row_bytes(itemsize).items()}
        state = 0 if self._linear is None else \
            self._linear.block.attn.state_bytes() * self.n_linear
        return {"row": rows, "state": {"slot": state}}

    def refuse_serving(self, **asked) -> None:
        """An honest refusal, by name, of what assumes a cache of token
        rows in pages alone (``asked``: feature -> whether it is on)."""
        for what, on in asked.items():
            if on:
                raise SlotStateNotSupported(
                    f"{what} is not available over a per-slot recurrent "
                    f"state and a compressed-key leaf: it would need "
                    f"the state snapshotted (or rolled back) with the "
                    f"pages")

    def decode(self, params, x, caches, *, with_stats=False,
               w8a8_mask=None, w8a8_wq=None, lora=None, **kwargs):
        if w8a8_mask is not None or w8a8_wq is not None or lora:
            raise NotImplementedError(
                "minicpm_sala has no W8A8 or LoRA lane")
        caches = tuple(caches)
        paged, state = caches[:3], caches[3:]
        stats = []
        for i, (run, kind) in enumerate(zip(self.runs, self.run_kinds)):
            p = params["runs"][str(i)]
            if kind == SPARSE:
                x, paged, st = run.decode(p, x, paged, with_stats=True,
                                          **kwargs)
                stats.append(st["sparse_pages"])
            else:
                x, state = run.decode(p, x, state, **kwargs)
        if not with_stats:
            return x, paged + state
        pages = jnp.concatenate(stats, axis=0)
        zeros = jnp.zeros_like(pages)
        # [chosen, visible] of the decode rows, then of the pack
        pages = jnp.concatenate(
            [zeros, pages] if kwargs.get("pack") is not None
            else [pages, zeros], axis=1)
        return x, paged + state, {"sparse_pages": pages}

    def layer_stats_zeros(self) -> dict:
        return {"sparse_pages": jnp.zeros((self.n_sparse, 4), jnp.int32)}

    def prefill(self, *args, **kwargs):
        raise SlotStateNotSupported(
            "StackedBlocks.prefill (the CP-prefill lane) returns "
            "per-head (k, v) of every layer; the lightning layers have "
            "none")


class MiniCPMSALAForCausalLM(Module):
    def __init__(self, cfg: MiniCPMSALAConfig):
        super().__init__()
        self.cfg = cfg
        init = normal_init(cfg.init_std)
        self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size,
                                          init=init)
        self.blocks = SalaBlocks(cfg)
        self.final_norm = RMSNorm(cfg.hidden_size, eps=cfg.rms_norm_eps)
        # untied: the same (V, E) layout the tied models' head has
        self.lm_head = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size, init=init)

    def _head_weight(self, params):
        return params["lm_head"]["weight"]

    def embed(self, params, input_ids, *, positions=None):
        del positions          # rotary positions are applied per layer
        h = self.wte(params["wte"], input_ids).astype(jnp.float32)
        return act_constrain(h * self.cfg.scale_emb, "tokens")

    def hidden_norm(self, params, h):
        """The final norm with the head's ``1 / (hidden_size /
        dim_model_base)`` folded in: what the head multiplies."""
        h = self.final_norm(params["final_norm"], h)
        return h * (self.cfg.dim_model_base / self.cfg.hidden_size)

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
