"""``arch: mla_moe`` for the ``serve_arch`` runner: the published
``config.json`` keys of a latent-attention (MLA) decoder with
bias-corrected routed experts behind leading dense layers onto the
program's model (``hetu_tpu/models/mla_moe.py``), and the plain
reference's entry point (``benchmark/reference/mla_moe.py``).

The arena holds ONE leaf: a token's row in a layer is ``[c ‖ k_r]``,
``kv_lora_rank + qk_rope_head_dim`` numbers, stored ``stored_row`` wide
(the configuration says which, and why)."""

from __future__ import annotations

from benchmark.reference import mla_moe as reference


#: The reference comparison's limits, for the ``serve_arch_ties`` runner
#: (``benchmark/runners/serve_arch_ties.py`` says why this architecture
#: is judged by the SHARE of its near-ties that moved). Each from two
#: readings on the chip (PERF.md section 6, PR 30): what the program
#: gives over its seeds, and what a computation below the stated
#: precision gives, which has to be refused.
#: An emitted token off every routing near-tie lies within this of the
#: float32 reference's top logit: the program 0.027 at most (its
#: runs), the reference with ``float8_e4m3fn`` operands 1.15 / 1.23, one
#: whose router leaves the bias out 1.33 / 1.75
LOGIT_TOL = 0.1
#: An expert whose selection score ``s + b`` lies closer to the top-6's
#: cut than this share of the token's spread of scores may be chosen or
#: not on rounding: with bf16 operands no token moved by more than 0.017
#: at a margin over 0.008 (0.19 in 0.006-0.008, up to 2.2 below 0.004);
#: twice that
ROUTE_TOL = 0.015
#: At most this share of the near-tie positions may lie more than
#: ``LOGIT_TOL`` below the top: the program 6.5-9.5 % (bf16 operands in
#: the reference 4.4 / 8.4 %), ``float8_e4m3fn`` operands 55 / 64 %, no
#: bias 57 / 58 %
NEAR_TIE_OVER_MAX = 0.25
#: At most this share of the compared positions may be near-ties: a
#: property of 6 layers x 64 experts x top-6 (87 % by order statistics),
#: not of the program: 88.4-90.3 % read
ROUTE_SHARE_MAX = 0.95


def build(config: dict):
    """The program's model for ``config`` (weights come from
    ``model.init``)."""
    from hetu_tpu.models.mla_moe import MLAMoEConfig, MLAMoEForCausalLM
    serve = config.get("serve", {})
    return MLAMoEForCausalLM(MLAMoEConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        kv_lora_rank=config["kv_lora_rank"],
        q_lora_rank=config["q_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        n_routed_experts=config["n_routed_experts"],
        n_shared_experts=config["n_shared_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        routed_scaling_factor=config["routed_scaling_factor"],
        first_k_dense_replace=config["first_k_dense_replace"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"],
        max_position_embeddings=config["max_position_embeddings"],
        stored_row=config["stored_row"],
        rope_positions=serve.get("max_len"),
        compute_dtype=serve.get("compute_dtype", "float32")))


def arena_row_elements(config: dict) -> int:
    """Elements of one token's row in one layer of the arena's one
    leaf, as stored."""
    return config["stored_row"]


def window(config: dict):
    return None


def reference_rows(config: dict, params, ids, start, n_rows: int):
    """The float32 reference on ONE row ``ids`` ``(seq,)``:
    ``(logits (n_rows, vocab), margin (n_rows,))`` at positions
    ``start .. start + n_rows - 1`` — the margin is the routing margin
    of ``reference.hidden_states(with_margins=True)``: how close, in
    some expert layer, an expert's selection score ``s + b`` lies to
    the top-k's cut."""
    import jax
    import jax.numpy as jnp
    h, low = reference.hidden_states(params, ids[None], config,
                                     with_margins=True)
    h = jnp.pad(h[0], ((0, n_rows), (0, 0)))
    low = jnp.pad(low[0], (0, n_rows), constant_values=jnp.inf)
    rows = jax.lax.dynamic_slice_in_dim(h, start, n_rows)
    with jax.default_matmul_precision("highest"):
        lg = rows @ jnp.asarray(params["lm_head"]["weight"],
                                jnp.float32).T
    return lg, jax.lax.dynamic_slice_in_dim(low, start, n_rows)
