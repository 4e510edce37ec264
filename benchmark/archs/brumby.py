"""``arch: brumby`` for the ``serve_arch_state`` runner: the published
``config.json`` keys of Brumby-14B-Base (a Qwen3-shaped dense decoder
whose every layer is power retention) onto the program's model
(``hetu_tpu/models/brumby.py``), and the plain reference's entry point
(``benchmark/reference/brumby.py``: the quadratic form, no state).

The cache is ONE kind of state: a float32 state a kv head, layer and
SLOT; no layer keeps a token row, so the engine holds no arena
(``arena_row_elements`` 0, no window).

The comparison is the plain one: a dense model has no routing and no
selection whose near-tie would make another computation equally valid,
so ``reference_rows`` gives every position a margin of ``+inf`` and
each compared token is held to ``LOGIT_TOL`` alone. What it compares:
chunked prefill of 16,384 tokens (8 chunks; 64 pieces of the scan
kernel a kv head and layer) and then 96 tokens decoded THROUGH THE
STATE, against the reference's one quadratic forward over the 16,480
positions — logits at the emitted tokens.

Four computations that must be refused come out ``correct: false``
THROUGH the runner — ``serve_arch_state.reference_check`` with the
control planted in ``reference.CONTROL``, on the requests a chip run
finished (``workloads/brumby_controls.py``; PERF.md section 6, PR 51),
and at tiny size through ``harness.run_cell``
(``tests/benchmark/test_serve_arch_retention.py``)."""

from __future__ import annotations

from benchmark.reference import brumby as reference

#: The limit, from two readings on the chip (PERF.md section 6, PR 51;
#: 192 compared positions a run: 2 requests x 96 tokens predicted from
#: positions 16,383-16,478; a token drawn at random lies 6.3 below the
#: top): a compared token may lie at most this far below the float32
#: reference's top logit. The PROGRAM's largest gap over its seeds is
#: 0.112 (sixteen seeds: 0.042-0.112 a run; 99th percentile 0.03, median
#: 0: greedy tokens, so a gap is a near-tie that bf16 operands flipped); the reference in ``bfloat16``
#: operands — the stated precision — reads 0.100; in ``float8_e4m3fn``
#: operands, the nearest precision below, 2.10 (median 0.44, 61 % of
#: the positions over 0.2); without the gate 11.1, with the state reset
#: at every chunk 10.2, with ``phi``'s diagonal alone 10.3 (every
#: position over 1). 0.4 lies a factor of 3.6 above the one and of five
#: below the other.
LOGIT_TOL = 0.4
#: queries a block of the reference's quadratic form (memory only)
Q_BLOCK = 64


def build(config: dict):
    """The program's model for ``config`` (weights come from
    ``model.init``)."""
    from hetu_tpu.models.brumby import BrumbyConfig, BrumbyForCausalLM
    serve = config.get("serve", {})
    a = config["assumed"]
    return BrumbyForCausalLM(BrumbyConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=config["num_hidden_layers"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"],
        max_position_embeddings=config["max_position_embeddings"],
        retention_eps=a["retention_eps"],
        gate_means=tuple(a["gate_means"]),
        qk_norm_gain=a["qk_norm_gain"],
        init_std=a.get("init_std", 0.02),
        rope_positions=serve.get("max_len"),
        compute_dtype=serve.get("compute_dtype", "float32")))


def arena_row_elements(config: dict) -> int:
    """No layer keeps a token row."""
    return 0


def window(config: dict):
    return None


def reference_rows(config: dict, params, ids, start, n_rows: int):
    """The float32 reference on ONE row ``ids`` ``(seq,)``: ``(logits
    (n_rows, vocab), margin (n_rows,))`` at positions ``start .. start
    + n_rows - 1`` — the margin ``+inf`` everywhere (the module's
    note)."""
    import jax
    import jax.numpy as jnp
    h = reference.hidden_states(params, ids, config, q_block=Q_BLOCK,
                                **reference.CONTROL)
    h = jnp.pad(h, ((0, n_rows), (0, 0)))
    rows = jax.lax.dynamic_slice_in_dim(h, start, n_rows)
    # (the head is upcast once the layers are done, not beside them)
    head, rows = jax.lax.optimization_barrier(
        (params["lm_head"]["weight"], rows))
    with jax.default_matmul_precision("highest"):
        lg = rows @ jnp.asarray(head, jnp.float32).T
    return lg, jnp.full((n_rows,), jnp.inf, jnp.float32)
