"""``arch: cohere2_moe`` for the ``serve_arch`` runner: the published
``config.json`` keys onto the program's model, and the plain reference's
entry point. The next architecture adds one such file and no runner.

A configuration that holds a chip's share of an expert-parallel
deployment gives the experts held under ``num_experts`` and the
published count under ``published.num_experts``: the router keeps the
published width, and the held experts are the first ones.
"""

from __future__ import annotations

from benchmark.reference import cohere2_moe as reference


def _share(config: dict):
    """(router width, (first, count) held here or None for all)."""
    width = config.get("published", {}).get("num_experts",
                                            config["num_experts"])
    held = config["num_experts"]
    return width, (None if held == width else (0, held))


def build(config: dict):
    """The program's model for ``config`` (weights come from
    ``model.init``)."""
    from hetu_tpu.models import Cohere2MoEConfig, Cohere2MoEForCausalLM
    n = config["num_hidden_layers"]
    width, local = _share(config)
    serve = config.get("serve", {})
    return Cohere2MoEForCausalLM(Cohere2MoEConfig(
        vocab_size=config["vocab_size"],
        hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        num_hidden_layers=n,
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        layer_norm_eps=config["layer_norm_eps"],
        logit_scale=config["logit_scale"],
        sliding_window=config["sliding_window"],
        layer_types=tuple(config["layer_types"][:n]),
        rope_theta=config["rope_theta"],
        num_experts=width,
        num_experts_per_tok=config["num_experts_per_tok"],
        num_shared_experts=config["num_shared_experts"],
        max_position_embeddings=config["max_position_embeddings"],
        local_experts=local,
        rope_positions=serve.get("max_len"),
        compute_dtype=serve.get("compute_dtype", "float32")))


def arena_row_elements(config: dict) -> int:
    """Elements of one token's K (or V) row in one layer of the arena."""
    return config["num_key_value_heads"] * config["head_dim"]


def window(config: dict) -> int:
    return config["sliding_window"]


def reference_rows(config: dict, params, ids, start, n_rows: int):
    """The float32 reference on ONE row ``ids`` ``(seq,)``:
    ``(logits (n_rows, vocab), margin (n_rows,))`` at positions
    ``start .. start + n_rows - 1`` — the margin is the routing margin
    of ``reference.hidden_states(with_margins=True)``: how close, in
    some layer, an expert held here lies to the top-k's cut."""
    import jax
    import jax.numpy as jnp
    width, local = _share(config)
    h, low = reference.hidden_states(
        params, ids[None], {**config, "num_experts": width},
        local_experts=local, with_margins=True)
    h = jnp.pad(h[0], ((0, n_rows), (0, 0)))
    low = jnp.pad(low[0], (0, n_rows), constant_values=jnp.inf)
    rows = jax.lax.dynamic_slice_in_dim(h, start, n_rows)
    with jax.default_matmul_precision("highest"):
        lg = rows @ jnp.asarray(params["wte"]["weight"], jnp.float32).T
    return lg, jax.lax.dynamic_slice_in_dim(low, start, n_rows)
