"""``arch: qwen3_next`` for the ``serve_arch_ssm`` runner: the published
``config.json`` keys of Qwen3-Next-80B-A3B-Instruct (Gated DeltaNet
layers beside gated softmax attention layers over a 512-wide softmax
router and a gated shared expert) onto the program's model
(``hetu_tpu/models/qwen3_next.py``), and the plain reference's entry
point (``benchmark/reference/qwen3_next.py``: the delta rule a token at
a time over the whole row).

The cache is two kinds of state: a k and a v row of 2 x 256 a token
over the THREE gated attention layers (in pages) and, a SLOT, a float32
state of 32 value heads x 128 x 128 and a convolution tail over the
nine Gated DeltaNet layers.

The comparison is ``serve_arch_ssm``'s: ``serve_arch_ties``' on the
emitted tokens, read as Ling's arch file reads it — NO position is
compared alone — and then the slot's STATE against the reference's.
The routing margin is the reference's (``reference.route``): how far
the nearest expert HELD ON THIS CHIP lies from the cut between the 10th
and the 11th of 512 router logits, as a share of the token's spread of
logits, the smallest over the twelve layers. With 64 held experts
around a cut a layer and twelve layers a margin under 0.015 is the rule,
and a position's own margin does not bound its gap (its delta-rule
state and its keys carry its CONTEXT's flips, 32k tokens of them). So
``ROUTE_TOL`` takes in every position and the comparison is the SHARE
of positions that lie more than ``LOGIT_TOL`` below the reference's top
logit — a top-10 of 512 flips on rounding; near-ties are a share under
a limit, never waved through. What it compares: chunked prefill of
32,768 tokens (16 chunks; the state and the tail carried across every
boundary, the keys read back from pages) and then 256 tokens decoded
THROUGH the state and the arena, against the reference's one forward
over the 33,024 positions.

The computations that must be refused come out ``correct: false``
THROUGH the runner — ``serve_arch_ssm``'s two comparisons with the
control planted in ``reference.CONTROL``, on the requests a chip run
finished (``workloads/qwen3_next_controls.py``; PERF.md section 6, PR
59), and at tiny size through ``harness.run_cell``
(``tests/benchmark/test_serve_arch_gdn.py``)."""

from __future__ import annotations

from benchmark.reference import qwen3_next as reference

#: The limits, each from two readings on the chip (PERF.md section 6,
#: PR 59; 256 compared positions a run: ONE request's 256 tokens
#: predicted from positions 32,767-33,022; a token drawn at random lies
#: 3.6 below the top): what the program gives over its seeds, and what
#: the computations that must be refused give on a finished request
#: (``workloads/qwen3_next_controls.py``, seeds 2159590301 | ...302).
#: A position is OVER when its token lies more than this below the
#: float32 reference's top logit: the program's 99th percentile gap is
#: 0.04-0.07 and its largest over seventeen runs 0.129
LOGIT_TOL = 0.05
#: every compared position has a routing margin under this: none is
#: held to ``LOGIT_TOL`` alone (the module docstring)
ROUTE_TOL = 1.0
#: at most this share of the positions may be over. At 0.05 the program
#: reads 0.4-2.7 % over seventeen runs (1 to 7 of 256; the reference in bf16
#: operands, the stated precision, 0 %); the controls: ``sigmoid_router``
#: **11.7 | 14.1 %** (the nearest: the same ten experts under flatter
#: weights, an eighth of them held), the state in ``bfloat16`` 4.3 | 4.3
#: % (``STATE_TOL`` is what refuses it), ``float8_e4m3fn`` operands 75.0
#: | 74.2 %, ``full_rotary`` 75.4 | 80.9 %, ``no_out_gate`` 73 %,
#: ``no_shared_gate`` 90 %, ``plain_gain`` 89.8 | 88.3 %, ``no_erase``
#: 99 %, ``tile_key_heads`` 99.6 %, ``no_conv`` 100 %. At a tolerance of
#: 0.1 the nearest control reads 3.9 | 6.6 % against the program's 0-0.4
#: %: too few positions of 256 to stand between (and Ling's 0.1 / 5 %
#: PASSED it). 5 % (12 positions) lies a factor of 1.8 above the
#: program's largest and of 2.3 below the nearest control's smallest
NEAR_TIE_OVER_MAX = 0.05
#: all positions are near-ties here, by the choice of ``ROUTE_TOL``
ROUTE_SHARE_MAX = 1.0

#: The slot's STATE (``state_gap``): how far the FIRST Gated DeltaNet
#: layer's slowest heads may lie from the float32 recurrence's where
#: the last chunk and where the last decoded token leave them, by the
#: operand precision the configuration states
#: (``serve.compute_dtype``). Why the first layer: its inputs are an
#: embedding row, one norm and two projections, so nothing but those
#: operands' rounding has reached its state — behind it every earlier
#: layer's rounding and routing flips have (the program reads 0.009 in
#: the second layer and 0.05 in the ninth, the reference in bf16
#: operands 0.003 and 0.03) —, and all nine layers are runs of ONE scan
#: body over one leaf through the same two kernels. Why the slowest
#: heads: a head that forgets in a few tokens holds the last few
#: tokens' writes whatever the precision of what came before.
#: ``bfloat16`` operands, on the chip, one 32,768 + 256 request a
#: reading: the PROGRAM **0.0026-0.0029** over seventeen seeds (the operands'
#: rounding is a statistical constant over 33k tokens: against the
#: reference in bf16 operands it reads 0.0002); the reference with its
#: state in ``bfloat16`` **0.0163 | 0.0162**, ``float8_e4m3fn`` operands
#: 0.070 | 0.071,
#: ``no_erase`` 0.97, ``no_conv`` / ``tile_key_heads`` 1.35, ``plain_gain``
#: 54 (the attention layers', the router's and the shared gate's
#: controls do not reach this layer: the tokens refuse them). 0.007 lies
#: a factor of 2.4 above the one and of 2.3 below the nearest other.
#: ``float32`` operands (the CPU rehearsal's tiny model): the program
#: 2e-7 .. 2e-5, the state in ``bfloat16`` 0.003-0.005; 3e-4 lies a
#: factor of 15 above and of 10 below.
STATE_TOL = {"bfloat16": 0.007, "float32": 3e-4}
#: ... read on the heads that keep their past longest: this share of a
#: layer's value heads (8 of 32 at the published widths)
SLOW_SHARE = 0.25


def state_tol(config: dict) -> float:
    return STATE_TOL[config.get("serve", {}).get("compute_dtype",
                                                 "float32")]


def build(config: dict):
    """The program's model for ``config`` (weights come from
    ``model.init``)."""
    from hetu_tpu.models.qwen3_next import (
        Qwen3NextConfig, Qwen3NextForCausalLM,
    )
    serve = config.get("serve", {})
    a = config["assumed"]
    return Qwen3NextForCausalLM(Qwen3NextConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        full_attention_interval=config["full_attention_interval"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        partial_rotary_factor=config["partial_rotary_factor"],
        rope_theta=config["rope_theta"],
        linear_num_key_heads=config["linear_num_key_heads"],
        linear_num_value_heads=config["linear_num_value_heads"],
        linear_key_head_dim=config["linear_key_head_dim"],
        linear_value_head_dim=config["linear_value_head_dim"],
        linear_conv_kernel_dim=config["linear_conv_kernel_dim"],
        num_experts=config["published"]["num_experts"],
        local_experts=reference.held_experts(config),
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        shared_expert_intermediate_size=config[
            "shared_expert_intermediate_size"],
        norm_topk_prob=config["norm_topk_prob"],
        rms_norm_eps=config["rms_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"],
        tie_word_embeddings=config["tie_word_embeddings"],
        qk_norm_gain=a["qk_norm_gain"], norm_w_std=a["norm_w_std"],
        a_range=tuple(a["a_range"]), dt_range=tuple(a["dt_range"]),
        init_std=a.get("init_std", 0.02),
        rope_positions=serve.get("max_len"),
        compute_dtype=serve.get("compute_dtype", "float32")))


def arena_row_elements(config: dict) -> int:
    """Elements of one token's k (or v) row in ONE attention layer of
    the arena: the kv heads' ``head_dim`` each."""
    return config["num_key_value_heads"] * config["head_dim"]


def window(config: dict):
    return None


def reference_rows(config: dict, params, ids, start, n_rows: int):
    """The float32 reference on ONE row ``ids`` ``(seq,)``: ``(logits
    (n_rows, vocab), margin (n_rows,), states)`` at positions ``start ..
    start + n_rows - 1`` — the margin the routing margin of
    ``reference.hidden_states(with_margins=True)``; ``states (GDN
    layers, 2, H, d, d)`` every Gated DeltaNet layer's state after
    position ``start`` (the prompt's last: what the last chunk leaves)
    and after ``start + n_rows - 1`` (what ``n_rows`` decoded tokens
    leave: the last of them is emitted and never read)."""
    import jax
    import jax.numpy as jnp
    h, low, states = reference.hidden_states(
        params, ids, config, with_margins=True,
        stops=jnp.stack([start, start + n_rows - 1]), **reference.CONTROL)
    h = jnp.pad(h, ((0, n_rows), (0, 0)))
    low = jnp.pad(low, (0, n_rows), constant_values=jnp.inf)
    rows = jax.lax.dynamic_slice_in_dim(h, start, n_rows)
    # (the head is upcast once the layers are done, not beside them)
    head, rows = jax.lax.optimization_barrier(
        (params["lm_head"]["weight"], rows))
    with jax.default_matmul_precision("highest"):
        lg = rows @ jnp.asarray(head, jnp.float32).T
    return lg, jax.lax.dynamic_slice_in_dim(low, start, n_rows), states


def program_states(caches, slot: int):
    """``slot``'s state in the engine's caches (``eng.pool.caches``),
    ``(GDN layers, H, d, d)`` float32 on the host: the ONE leaf of five
    axes, ``(layers, slots, H, d, d)``
    (``DeltaRuleMixer.init_leaves``)."""
    import numpy as np
    leaf, = [x for x in caches if x.ndim == 5]
    return np.asarray(leaf[:, slot], np.float32)


def state_gap(config: dict, params, got, want) -> dict:
    """How far a slot's state ``got (GDN layers, 2, H, d, d)``
    (:func:`program_states` after the prompt's last chunk and after the
    last decoded token) lies from the reference's ``want``: the norm of
    the difference over the norm of ``want``, a layer and a reading at
    a time, over the layer's SLOWEST heads (``SLOW_SHARE`` of them by
    ``reference.horizons``) and over all of them. ``gap``, which
    ``STATE_TOL`` judges, is the FIRST layer's over its slowest heads,
    the larger of its two readings (the note at ``STATE_TOL``); the
    other layers' are reported beside it."""
    import numpy as np
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    H = np.asarray(reference.horizons(params, config))       # (L, heads)
    cut = np.quantile(H, 1 - SLOW_SHARE, axis=1)
    slow = (H >= cut[:, None])[:, None, :, None, None]

    def rel(mask):
        return np.sqrt(((got - want) ** 2 * mask).sum((2, 3, 4))
                       / np.maximum((want ** 2 * mask).sum((2, 3, 4)),
                                    1e-300))
    slowest, whole = rel(slow), rel(np.ones_like(slow))
    return {"gap": float(slowest[0].max()),
            "first_layer_after_prompt": float(slowest[0, 0]),
            "first_layer_after_decode": float(slowest[0, 1]),
            "slowest_by_layer": slowest.max(1).tolist(),
            "whole_by_layer": whole.max(1).tolist()}
