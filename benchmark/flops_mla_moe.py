"""Operations and bytes the ``mla_moe`` layers NEED, from shapes
(``config`` is the configuration file: the published ``config.json``
keys). Never what a particular implementation spends."""

from __future__ import annotations


def latent_row(config: dict) -> int:
    """Numbers a token's cached row NEEDS in one layer: the compressed
    keys-and-values and the one RoPE key (padding is not needed)."""
    return config["kv_lora_rank"] + config["qk_rope_head_dim"]


def expert_bytes(config: dict, weight_bytes: int = 2) -> int:
    """One SwiGLU expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] \
        * weight_bytes


def moe_experts_call(config: dict, assignments: float,
                     experts_touched: float) -> dict:
    """One call of the routed-expert layer: it has to read each expert
    that got a token ONCE (whatever the number of its tokens) and spends
    2 operations per weight per (token, choice) pair — 6 x hidden x
    width. The activations' bytes are left out (under 3 % of the 64
    experts' weights at a chunk of 1024)."""
    return {"bytes": experts_touched * expert_bytes(config),
            "flops": 6.0 * config["hidden_size"]
            * config["moe_intermediate_size"] * assignments}


def mla_decode_call(config: dict, pages: float, block_size: int,
                    kv_bytes_per_elem: int = 2) -> dict:
    """One layer's decode attention in the latent space: every live
    token's row is read ONCE (key and value are the same bytes), and
    each of the heads spends 2 x row operations on its score and 2 x
    kv_lora_rank on its value a cached token."""
    tokens = pages * block_size
    return {"bytes": float(tokens * latent_row(config)
                           * kv_bytes_per_elem),
            "flops": float(tokens * config["num_attention_heads"]
                           * (2 * latent_row(config)
                              + 2 * config["kv_lora_rank"]))}
