"""``arch: sdar_moe`` for the ``serve_arch_blocks`` runner: the
published ``config.json`` keys of SDAR-30B-A3B-Chat and the
configuration's ``serve.generation`` onto the program's model
(``hetu_tpu/models/sdar_moe.py``), and the plain reference's entry
point (``benchmark/reference/sdar_moe.py``).

A configuration that holds a chip's share of an expert-parallel
deployment gives the experts held under ``num_experts`` and the
published count under ``published.num_experts``: the router keeps the
published width, and the held experts are the first ones.

The comparison (``runners/serve_arch_blocks.py``) rebuilds, from a
finished request's tokens and its recorded unmask passes, the block
states the program's passes saw; ``reference_rows`` gives the float32
reference's logits for them: one clean stream and one noised stream a
pass. Its limits are below, each with its two readings.
"""

from __future__ import annotations

from benchmark.reference import sdar_moe as reference

#: What the comparison of ``runners/serve_arch_blocks.py`` holds the
#: program to, each from two readings on the chip (PERF.md section 6,
#: PR 45; ~6,100 compared tokens and as many passes a run): what the
#: program gives over its seeds, and what the seven computations that
#: must be refused give on the same finished requests (seed 2147490201:
#: the reference with a causal mask inside a block, without the commit
#: pass, without the block's own keys, unmasking left to right, with a
#: sigmoid router, without the q/k norms, with ``float8_e4m3fn``
#: operands). The reference with ``bfloat16`` operands — the stated
#: precision — reads as the program does (2.1 % over): what the program
#: lies from the float32 reference is its operands' rounding through 48
#: layers, not a fault.
#: A token is OVER when it lies more than this below the float32
#: reference's top logit at the pass that unmasked it (a token drawn at
#: random lies 3.7 below; the program's median gap is 0, its 90th
#: percentile 0.28, its 99th 0.58)
LOGIT_TOL = 0.5
#: every compared position counts as a routing near-tie (48 layers x
#: top-8 of 128 with 16 held: the median routing margin is 0.002, the
#: 99th percentile 0.015, and a position's keys carry its context's
#: flips), so no position is held to ``LOGIT_TOL`` alone ...
ROUTE_TOL = 1.0
#: ... and at most this share of them may be over: the program 1.2-2.1 %
#: over sixteen runs' seeds; a sigmoid router 8.3 %, no commit pass 22.8 %,
#: ``float8_e4m3fn`` operands 35.9 %, no q/k norm 64.1 %, a causal mask
#: inside a block 76.5 %, the block's own keys left out 81.0 %
NEAR_TIE_OVER_MAX = 0.05
ROUTE_SHARE_MAX = 1.0
#: a pass whose unmasked positions are not the reference rule's is a
#: SWAP when the confidences of the two choices (the reference's own,
#: at those positions) differ by more than this share of the larger.
#: With random weights a block's four confidences lie within tens of
#: per cent of each other and bf16 moves each by about as much, so the
#: program's choice is another position in 30-32 % of the passes — a
#: near-equal one (a difference over 0.1 in 15-17 %, over 0.2 in 5-7 %)
CONF_TOL = 0.3
#: ... and at most this share of the compared passes may be such: the
#: program 1.2-2.1 % over sixteen runs' seeds; positions unmasked left to
#: right 7.5 % (seed 2147490202; 17.3 % over 0.2, 2.4 % over 0.4 — its
#: logits are the program's own: only this limit refuses it)
CONF_OVER_MAX = 0.04


def _share(config: dict):
    """(router width, (first, count) held here or None for all)."""
    width = config.get("published", {}).get("num_experts",
                                            config["num_experts"])
    held = config["num_experts"]
    return width, (None if held == width else (0, held))


def generation(config: dict) -> dict:
    """The configuration's generation settings (``serve.generation``),
    the mask id the last of the vocabulary held where none is given."""
    g = dict(config["serve"]["generation"])
    g.setdefault("mask_token_id", config["vocab_size"] - 1)
    return g


def reference_config(config: dict) -> dict:
    """``config`` as the reference reads it: the router's published
    width and the generation settings at the top level."""
    return {**config, **generation(config), "num_experts": _share(config)[0]}


def build(config: dict):
    """The program's model for ``config`` (weights come from
    ``model.init``)."""
    from hetu_tpu.models.sdar_moe import SDARMoEConfig, SDARMoEForCausalLM
    width, local = _share(config)
    serve, g = config.get("serve", {}), generation(config)
    return SDARMoEForCausalLM(SDARMoEConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"],
        num_experts=width,
        num_experts_per_tok=config["num_experts_per_tok"],
        max_position_embeddings=config["max_position_embeddings"],
        block_length=g["block_length"],
        denoising_steps=g["denoising_steps"],
        remasking=g["remasking"],
        confidence_threshold=g["confidence_threshold"],
        mask_token_id=g["mask_token_id"],
        qk_norm_gain=config["assumed"]["qk_norm_gain"],
        init_std=config["assumed"].get("init_std", 0.02),
        local_experts=local,
        rope_positions=serve.get("max_len"),
        compute_dtype=serve.get("compute_dtype", "float32")))


def arena_row_elements(config: dict) -> int:
    """Elements of one token's K (or V) row in one layer of the arena."""
    return config["num_key_value_heads"] * config["head_dim"]


def window(config: dict):
    return None


def reference_rows(config: dict, params, clean, noised, start: int):
    """The float32 reference on ONE finished request: ``clean (S,)``
    its final tokens, ``noised (N, S - start)`` its rows from ``start``
    on as they stood at each of N passes -> ``(logits (N, S - start,
    vocab), margin (N, S - start))`` of the noised streams (the margin
    the routing margin of ``reference.route``, the smallest over the
    layers). ``reference.CONTROL`` is the ONE change of a negative
    control (its ``order`` is the comparison's, not the streams')."""
    import jax
    import jax.numpy as jnp
    _, h, low = reference.streams(
        params, clean, noised, reference_config(config), start=start,
        local_experts=_share(config)[1],
        **{k: v for k, v in reference.CONTROL.items() if k != "order"})
    # (the head is upcast once the layers are done, not beside them)
    head, h = jax.lax.optimization_barrier(
        (params["lm_head"]["weight"], h))
    with jax.default_matmul_precision("highest"):
        return h @ jnp.asarray(head, jnp.float32).T, low
