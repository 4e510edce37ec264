"""The published ``config.json`` keys onto the program's model."""

from __future__ import annotations


def gpt_config(config: dict):
    """A configuration file's keys onto ``GPTConfig`` fields."""
    from hetu_tpu.models import GPTConfig
    return GPTConfig(
        vocab_size=config["vocab_size"],
        max_positions=config["n_positions"],
        hidden_size=config["n_embd"], num_layers=config["n_layer"],
        num_heads=config["n_head"],
        layer_norm_eps=config["layer_norm_epsilon"],
        init_std=config["initializer_range"],
        embd_pdrop=0.0, resid_pdrop=0.0, attn_pdrop=0.0)


def dtype(name: str):
    import jax.numpy as jnp
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "int8": jnp.int8}[name]
