"""``arch: jamba`` for the ``serve_arch_ssm`` runner: the published
``config.json`` keys of AI21-Jamba2-3B (Mamba-1 selective-scan layers
beside multi-query attention layers with no position encoding) onto the
program's model (``hetu_tpu/models/jamba.py``), and the plain
reference's entry point (``benchmark/reference/jamba.py``: the
recurrence a token at a time over the whole row).

The cache is two kinds of state: one k and one v row of 128 a token
over the TWO attention layers (in pages) and, a SLOT, a float32 state
and a convolution tail over the 26 Mamba layers.

The comparison is the plain one: a dense model has no routing and no
selection whose near-tie would make another computation equally valid,
so ``reference_rows`` gives every position a margin of ``+inf``: under
``serve_arch_ssm`` (``serve_arch``'s load, window, judging and audits,
``serve_arch_ties``' comparison with its limits taken from this file,
and the reading of the slot's state against ``STATE_TOL``) no position
is a near-tie and each compared token is held to ``LOGIT_TOL`` alone.
(``serve_arch`` itself holds every arch to ITS constant, 0.1, read on
four expert layers of 16 experts: the first chip run of this model read
0.133 at a near-tie of two tokens and came out ``correct: false`` for
the runner's constant, not for a fault.) What it compares:
chunked prefill of 32,768 tokens (16 chunks; the state and the tail
carried across every boundary) and then 256 tokens decoded THROUGH the
state and the arena, against the reference's one forward over the
33,024 positions — logits at the emitted tokens.

The computations that must be refused come out ``correct: false``
THROUGH the runner — ``serve_arch_ssm``'s two comparisons with the control
planted in ``reference.CONTROL``, on the requests a chip run finished
(``workloads/jamba_controls.py``; PERF.md section 6, PR 55), and at tiny
size through ``harness.run_cell``
(``tests/benchmark/test_serve_arch_ssm.py``)."""

from __future__ import annotations

from benchmark.reference import jamba as reference

#: The limit, from two readings on the chip (PERF.md section 6, PR 55;
#: 512 compared positions a run: 2 requests x 256 tokens predicted from
#: positions 32,767-33,022; a token drawn at random lies 4.3 below the
#: top): a compared token may lie at most this far below the float32
#: reference's top logit. The PROGRAM's largest gap over its seeds is
#: 0.181 (0.077-0.181 a run over twenty-two runs, mean 0.124; greedy
#: tokens, so a gap is a near-tie that bf16 operands flipped); the
#: reference in ``bfloat16`` operands — the stated precision — reads
#: 0.089 / 0.086 on two seeds, and with its STATE in ``bfloat16`` 0.26 /
#: 1.14 (NOT always refused here: ``STATE_TOL`` is what refuses it; the
#: first round's 0.113 / 0.147 was the reference itself — the TPU
#: compiler had taken the control's convert there and back out); in
#: ``float8_e4m3fn`` operands, the
#: nearest precision below, 2.40 / 3.12; without the inner norms 5.31 /
#: 2.50, with the tail dropped at every chunk 4.79 / 4.84, with the
#: state reset at every chunk 5.45 / 5.43, without ``D x`` 6.97 / 7.31.
#: 0.4 lies a factor of 2.2 above the one and of six below the nearest
#: other.
LOGIT_TOL = 0.4
#: a dense model: no position has a routing margin (``+inf`` everywhere),
#: none is a near-tie, and the two shares of ``serve_arch_ties`` never
#: bind
ROUTE_TOL = 0.0
NEAR_TIE_OVER_MAX = 0.0
ROUTE_SHARE_MAX = 0.0


#: The slot's STATE (``state_gap``): how far the FIRST Mamba layer's
#: slowest pairs may lie from the float32 recurrence's where the last
#: chunk and where the last decoded token leave them, by the operand
#: precision the configuration states (``serve.compute_dtype``). Why the
#: first layer: its inputs are an embedding row, one norm and three
#: projections, so nothing but those operands' rounding has reached its
#: state — in the layers behind it every earlier layer's rounding has
#: (the program reads 0.03-0.05 there over ALL pairs and 0.03-0.04 over
#: the slowest, the float32 reference in bf16 operands the same), and
#: all 26 layers are ONE scan over one leaf through the same two
#: kernels. Why the slowest pairs: the operands' rounding partly
#: averages out over a pair's horizon, a state kept one precision below
#: does the opposite — a decay of 0.001-0.004 a token is under half a
#: ``bfloat16`` step, so rounding after a token takes the decay away.
#: Two readings each (PERF.md section 6, PR 55, calls 8-12):
#: ``bfloat16`` operands, on the chip, one 32,768 + 256 request a
#: reading — the PROGRAM 0.0005-0.0043 over twenty seeds (twelve of
#: them 0.0005-0.0018, eight 0.0026-0.0043; the reference in bf16
#: operands 0.0013, 0.0016, 0.0027); the reference with its state in
#: ``bfloat16`` **0.033-0.63** over ten seeds (four of them
#: 0.033-0.049, the seeds on which the program reads 0.003-0.004: where
#: a layer's slow pairs hold mostly recent inputs the decay matters
#: less and the operands' rounding averages out less); reset at every
#: chunk 0.72-0.86, no inner norms 0.13-0.20, ``float8_e4m3fn``
#: operands 0.042-0.064, the tail dropped 0.019-0.026 (``D x`` does
#: not touch the state: the tokens refuse it). 0.012 lies a factor of
#: 2.8 above the one's largest and of 2.7 below the other's smallest.
#: ``float32`` operands (the CPU rehearsal's tiny model, 40 + 12
#: tokens): the program 2e-7 .. 1.7e-5, the state in ``bfloat16``
#: 0.0040-0.0096 (no decay there is under a ``bfloat16`` step: the
#: rounding alone); 3e-4 lies a factor of 18 above and of 13 below.
STATE_TOL = {"bfloat16": 0.012, "float32": 3e-4}
#: ... read on the pairs that keep their past longest: this share of a
#: layer's (state, channel) pairs (at the published widths and steps:
#: horizons of 256 tokens and more)
SLOW_SHARE = 0.03


def state_tol(config: dict) -> float:
    return STATE_TOL[config.get("serve", {}).get("compute_dtype",
                                                 "float32")]


def build(config: dict):
    """The program's model for ``config`` (weights come from
    ``model.init``)."""
    from hetu_tpu.models.jamba import JambaConfig, JambaForCausalLM
    serve = config.get("serve", {})
    a = config["assumed"]
    return JambaForCausalLM(JambaConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        attn_layer_period=config["attn_layer_period"],
        attn_layer_offset=config["attn_layer_offset"],
        num_experts=config["num_experts"],
        mamba_d_state=config["mamba_d_state"],
        mamba_d_conv=config["mamba_d_conv"],
        mamba_expand=config["mamba_expand"],
        mamba_dt_rank=config["mamba_dt_rank"],
        mamba_conv_bias=config["mamba_conv_bias"],
        mamba_proj_bias=config["mamba_proj_bias"],
        rms_norm_eps=config["rms_norm_eps"],
        max_position_embeddings=config["max_position_embeddings"],
        tie_word_embeddings=config["tie_word_embeddings"],
        dt_range=tuple(a["dt_range"]),
        init_std=a.get("init_std", 0.02),
        compute_dtype=serve.get("compute_dtype", "float32")))


def arena_row_elements(config: dict) -> int:
    """Elements of one token's k (or v) row in ONE attention layer of
    the arena: the kv heads' ``head_dim`` each."""
    return config["num_key_value_heads"] * (
        config["hidden_size"] // config["num_attention_heads"])


def window(config: dict):
    return None


def reference_rows(config: dict, params, ids, start, n_rows: int):
    """The float32 reference on ONE row ``ids`` ``(seq,)``: ``(logits
    (n_rows, vocab), margin (n_rows,), states)`` at positions ``start ..
    start + n_rows - 1`` — the margin ``+inf`` everywhere (the module's
    note); ``states (Mamba layers, 2, N, D)`` every Mamba layer's state
    after position ``start`` (the prompt's last: what the last chunk
    leaves) and after ``start + n_rows - 1`` (what ``n_rows`` decoded
    tokens leave: the last of them is emitted and never read)."""
    import jax
    import jax.numpy as jnp
    h, states = reference.hidden_states(
        params, ids, config, stops=jnp.stack([start, start + n_rows - 1]),
        **reference.CONTROL)
    h = jnp.pad(h, ((0, n_rows), (0, 0)))
    rows = jax.lax.dynamic_slice_in_dim(h, start, n_rows)
    # (the head is upcast once the layers are done, not beside them)
    head, rows = jax.lax.optimization_barrier(
        (params["wte"]["weight"], rows))
    with jax.default_matmul_precision("highest"):
        lg = rows @ jnp.asarray(head, jnp.float32).T
    return lg, jnp.full((n_rows,), jnp.inf, jnp.float32), states


def program_states(caches, slot: int):
    """``slot``'s state in the engine's caches (``eng.pool.caches``),
    ``(Mamba layers, N, D)`` float32 on the host: the ONE leaf of five
    axes, ``(layers, slots, N, D / 128, 128)`` (``MambaMixer.
    init_leaves``), its channels put back in one axis."""
    import numpy as np
    leaf, = [x for x in caches if x.ndim == 5]
    got = np.asarray(leaf[:, slot], np.float32)
    return got.reshape(got.shape[:2] + (-1,))


def state_gap(config: dict, params, got, want) -> dict:
    """How far a slot's state ``got (Mamba layers, 2, N, D)``
    (:func:`program_states` after the prompt's last chunk and after the
    last decoded token) lies from the reference's ``want``: the norm of
    the difference over the norm of ``want``, a layer and a reading at
    a time, over the layer's SLOWEST pairs (``SLOW_SHARE`` of them by
    ``reference.horizons``) and over all of them. ``gap``, which
    ``STATE_TOL`` judges, is the FIRST Mamba layer's over its slowest
    pairs, the larger of its two readings (the note at ``STATE_TOL``);
    the other layers' are reported beside it."""
    import numpy as np
    got, want = (np.asarray(x, np.float64) for x in (got, want))
    H = np.asarray(reference.horizons(params, config))
    cut = np.quantile(H.reshape(len(H), -1), 1 - SLOW_SHARE, axis=1)
    slow = (H >= cut[:, None, None])[:, None]

    def rel(mask):
        return np.sqrt(((got - want) ** 2 * mask).sum((2, 3))
                       / np.maximum((want ** 2 * mask).sum((2, 3)), 1e-300))
    slowest, whole = rel(slow), rel(np.ones_like(slow))
    return {"gap": float(slowest[0].max()),
            "first_layer_after_prompt": float(slowest[0, 0]),
            "first_layer_after_decode": float(slowest[0, 1]),
            "slowest_by_layer": slowest.max(1).tolist(),
            "whole_by_layer": whole.max(1).tolist()}
