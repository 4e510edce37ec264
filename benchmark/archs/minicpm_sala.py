"""``arch: minicpm_sala`` for the ``serve_arch_ties`` runner: the
published ``config.json`` keys of MiniCPM-SALA (block-sparse attention
layers over a compressed-key cache beside lightning linear-attention
layers) onto the program's model (``hetu_tpu/models/minicpm_sala.py``),
and the plain reference's entry point
(``benchmark/reference/minicpm_sala.py``).

The cache is two kinds of state: pages over the sparse layers (K, V a
kv head and a share of a stride mean a token) and one float32 state a
slot over the lightning layers.

The comparison is ``serve_arch_ties``'s, read here as: the "routing
margin" is the SELECTION margin — how close, in some sparse layer and kv
group, the 64th block score lies to the 65th, as a share of the spread
of the competing blocks' scores (``reference.sparse_attention``). At
these lengths a near-tie in SOME of a token's eight selections (4 layers
x 2 kv groups, 466 blocks competing for 30 places) is the rule, and an
exact tie is common: a compressed key's window straddles two blocks
and, where it is the best of both, gives them the same score to the
bit — 83 % of the compared positions have a margin under 1e-4, all of
them one under 0.01 (my chip run, PR 39). Rounding then picks another
block, a different and equally valid computation, and because the
attention is peaked (``assumed.qk_norm_gain``) one block can carry a
key that moves the top logit. So NO position is compared alone here;
the comparison is the SHARE of positions that lie more than
``LOGIT_TOL`` below the reference's top logit, which the three
computations that must be refused move from 6-9 % to 69-99 %.

Each of the three comes out ``correct: false`` THROUGH the runner —
``serve_arch_ties.reference_check`` with the control planted in
``reference.CONTROL``, on the requests a chip run finished (PERF.md
section 6, PR 39), and at tiny size through ``harness.run_cell``
(``tests/benchmark/test_serve_arch_sala.py``). The margin is not
redefined to leave exact ties out, which would put a few positions
under ``LOGIT_TOL`` alone: the positions without an exact tie read
margins of 1e-4 to 3e-3 of the spread, which bf16 operands flip as
surely, and a position's keys and values carry its CONTEXT's flips
whatever its own margin."""

from __future__ import annotations

from benchmark.reference import minicpm_sala as reference

#: The limits, each from two readings on the chip (PERF.md section 6,
#: PR 39; 1,792 compared positions a run): what the program gives over
#: its seeds, and what the three computations that must be refused
#: give on the same finished requests — the reference with
#: ``float8_e4m3fn`` operands, the reference that attends the forced
#: blocks alone (no top-k), the reference without the lightning decay.
#: A position is OVER when its token lies more than this below the
#: float32 reference's top logit (a token drawn at random lies 0.34
#: below): the program's median gap is 0, its 90th percentile 0.017;
#: the controls' MEDIANS are 0.048 / 0.187 / 0.178
LOGIT_TOL = 0.02
#: every compared position has a selection margin under this (see the
#: module docstring): none is held to ``LOGIT_TOL`` alone
ROUTE_TOL = 0.01
#: at most this share of the positions may be over: the program 6.3 %
#: and 8.5 % (7.8-9.4 % a request), ``float8_e4m3fn`` operands 68.9 %,
#: forced blocks only 98.7 %, no decay 98.8 %
NEAR_TIE_OVER_MAX = 0.25
#: all positions are near-ties here, by construction of the selection
#: (1.0: the share of near-ties cannot fail the run; what it guards in
#: ``serve_arch_ties`` — that enough is compared closely — the three
#: negative controls show of the share above instead)
ROUTE_SHARE_MAX = 1.0
#: queries a block of the reference's sparse layers (memory only)
Q_BLOCK = 64


def build(config: dict):
    """The program's model for ``config`` (weights come from
    ``model.init``)."""
    from hetu_tpu.models.minicpm_sala import (
        MiniCPMSALAConfig, MiniCPMSALAForCausalLM,
    )
    serve = config.get("serve", {})
    a = config["assumed"]
    return MiniCPMSALAForCausalLM(MiniCPMSALAConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        lightning_nh=config["lightning_nh"],
        lightning_head_dim=config["lightning_head_dim"],
        mixer_types=tuple(config["mixer_types"]),
        published_depth=config["published"]["num_hidden_layers"],
        rms_norm_eps=config["rms_norm_eps"],
        rope_theta=config["rope_theta"],
        max_position_embeddings=config["max_position_embeddings"],
        scale_emb=config["scale_emb"], scale_depth=config["scale_depth"],
        dim_model_base=config["dim_model_base"],
        block_size=a["block_size"], kernel_size=a["kernel_size"],
        kernel_stride=a["kernel_stride"], topk=a["topk"],
        init_blocks=a["init_blocks"], window_size=a["window_size"],
        qk_norm_gain=a["qk_norm_gain"],
        init_std=a.get("init_std", 0.02),
        rope_positions=serve.get("max_len"),
        compute_dtype=serve.get("compute_dtype", "float32")))


def arena_row_elements(config: dict) -> int:
    """Elements a token holds in one sparse layer of the arena: K and V
    of every kv head and its share of a stride mean."""
    row = config["num_key_value_heads"] * config["head_dim"]
    return 2 * row + row // config["assumed"]["kernel_stride"]


def window(config: dict):
    return None


def reference_rows(config: dict, params, ids, start, n_rows: int):
    """The float32 reference on ONE row ``ids`` ``(seq,)``: ``(logits
    (n_rows, vocab), margin (n_rows,))`` at positions ``start .. start
    + n_rows - 1`` — the margin is the selection margin of
    ``reference.hidden_states(with_margins=True)``."""
    import jax
    import jax.numpy as jnp
    h, low = reference.hidden_states(
        params, ids, config, with_margins=True, q_block=Q_BLOCK,
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
