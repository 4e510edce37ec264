"""``arch: kda_mla_moe`` for the ``serve_arch_ties`` runner: the
published ``config.json`` keys of a decoder of Kimi-Delta-Attention
layers beside latent-attention layers over group-limited routed experts
(Ling-3.0-flash) onto the program's model
(``hetu_tpu/models/kda_mla_moe.py``), and the plain reference's entry
point (``benchmark/reference/kda_mla_moe.py``).

The cache is two kinds of state: ONE latent row a token over the MLA
layers (``stored_row`` wide) and, a SLOT, a float32 state and a
convolution tail over the KDA layers.

The comparison is ``serve_arch_ties``'s, read here as MiniCPM-SALA's
arch file reads it: NO position is compared alone. The routing margin
is the reference's (``reference.route``): how close the choice of an
expert HELD ON THIS CHIP is to flipping — the held group's score against
the cut between kept and dropped groups, or a held expert's selection
score against the top-8's cut inside the kept groups — as a share of
the token's spread of selection scores, the smallest over the ten expert
layers. At 64 held experts and two cuts a layer a margin under 0.015 is
the rule (90-92 % of the positions), and a position's own margin does
not bound its gap: its delta-rule states and latent rows carry its
CONTEXT's flips (a 2,048-token chunk's worth of them), so gaps over 0.1
were read at margins of 0.016 and of 0.05-0.1 (4,096 positions dumped,
seeds 2151410501 / ...502; one run of sixteen had such a position above
a first ``ROUTE_TOL`` of 0.015 and came out ``correct: false`` for a
flip, not for a fault). So ``ROUTE_TOL`` takes in every position and the
comparison is the SHARE of positions that lie more than ``LOGIT_TOL``
below the reference's top logit, which the six computations that must
be refused move from 1-2 % to 12-100 %. Each comes out ``correct:
false`` THROUGH the runner — ``serve_arch_ties.reference_check`` with
the control planted in ``reference.CONTROL``, on the requests a chip
run finished (PERF.md section 6, PR 41), and at tiny size through
``harness.run_cell`` (``tests/benchmark/test_serve_arch_kda.py``)."""

from __future__ import annotations

from benchmark.reference import kda_mla_moe as reference

#: The limits, each from two readings on the chip (PERF.md section 6,
#: PR 41; 2,048 compared positions a run): what the program gives over
#: its seeds, and what the six computations that must be refused give
#: on the same finished requests (seed 2151410003) — the reference with
#: ``float8_e4m3fn`` operands, without the erase term, without the
#: convolution, with a decay a head, with no group limit, with no
#: selection bias. The reference with ``bfloat16`` operands — the stated
#: precision — passes (0.6 %).
#: A position is OVER when its token lies more than this below the
#: float32 reference's top logit (a token drawn at random lies 4.0
#: below): the program's median gap is 0, its 99th percentile 0.11; the
#: controls' largest gaps are 0.24 (no group limit) to 6.2 (no
#: convolution)
LOGIT_TOL = 0.1
#: every compared position has a routing margin under this (the
#: largest read: under 0.1; see the module docstring): none is held to
#: ``LOGIT_TOL`` alone
ROUTE_TOL = 1.0
#: at most this share of the positions may be over: the program 1.2 %,
#: 1.2 %, 1.5 %, 1.7 % over four seeds at 0.1 (0.8-2.3 % a request; 0.3-
#: 0.8 % over thirteen more seeds read at 0.15), no group limit 11.9 %,
#: no selection bias 12.1 %, ``float8_e4m3fn`` operands 37.6 %, no erase
#: 80.3 %, a decay a head 97.2 %, no convolution 100 %
NEAR_TIE_OVER_MAX = 0.05
#: all positions are near-ties here, by the choice of ``ROUTE_TOL``
#: (1.0: the share of near-ties cannot fail the run; what it guards in
#: ``serve_arch_ties`` — that enough is compared closely — the six
#: negative controls show of the share above instead)
ROUTE_SHARE_MAX = 1.0


def build(config: dict):
    """The program's model for ``config`` (weights come from
    ``model.init``)."""
    from hetu_tpu.models.kda_mla_moe import (
        KDAMLAMoEConfig, KDAMLAMoEForCausalLM,
    )
    serve = config.get("serve", {})
    first, count = reference.held_experts(config)
    return KDAMLAMoEForCausalLM(KDAMLAMoEConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=config[
            "moe_shared_expert_intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        head_dim=config["head_dim"],
        layer_group_size=config["layer_group_size"],
        short_conv_kernel_size=config["short_conv_kernel_size"],
        kda_lower_bound=config["kda_lower_bound"],
        kv_lora_rank=config["kv_lora_rank"],
        q_lora_rank=config["q_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        use_qk_norm=config["use_qk_norm"],
        n_routed_experts=config["published"]["num_experts"],
        local_experts=(first, count),
        num_experts_per_tok=config["num_experts_per_tok"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        routed_scaling_factor=config["routed_scaling_factor"],
        first_k_dense_replace=config["first_k_dense_replace"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"],
        max_position_embeddings=config["max_position_embeddings"],
        qk_norm_gain=config["assumed"]["qk_norm_gain"],
        stored_row=config["stored_row"],
        init_std=config["assumed"].get("init_std", 0.02),
        rope_positions=serve.get("max_len"),
        compute_dtype=serve.get("compute_dtype", "float32")))


def arena_row_elements(config: dict) -> int:
    """Elements of one token's row in one MLA layer of the arena's one
    paged leaf, as stored."""
    return config["stored_row"]


def window(config: dict):
    return None


def reference_rows(config: dict, params, ids, start, n_rows: int):
    """The float32 reference on ONE row ``ids`` ``(seq,)``: ``(logits
    (n_rows, vocab), margin (n_rows,))`` at positions ``start .. start
    + n_rows - 1`` — the margin is the routing margin of
    ``reference.hidden_states(with_margins=True)``."""
    import jax
    import jax.numpy as jnp
    h, low = reference.hidden_states(params, ids, config,
                                     with_margins=True,
                                     **reference.CONTROL)
    h = jnp.pad(h, ((0, n_rows), (0, 0)))
    low = jnp.pad(low, (0, n_rows), constant_values=jnp.inf)
    rows = jax.lax.dynamic_slice_in_dim(h, start, n_rows)
    # (the head is upcast once the layers are done, not beside them)
    head, rows = jax.lax.optimization_barrier(
        (params["lm_head"]["weight"], rows))
    with jax.default_matmul_precision("highest"):
        lg = rows @ jnp.asarray(head, jnp.float32).T
    return lg, jax.lax.dynamic_slice_in_dim(low, start, n_rows)
