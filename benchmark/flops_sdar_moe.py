"""Operations and bytes the ``sdar_moe`` layers NEED, from shapes
(``config`` is the configuration file: the published ``config.json``
keys). Never what a particular implementation spends."""

from __future__ import annotations


def expert_bytes(config: dict, weight_bytes: int = 2) -> int:
    """One SwiGLU expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"] \
        * weight_bytes


def moe_experts_call(config: dict, assignments: float,
                     experts_touched: float) -> dict:
    """One call of the routed-expert layer on one chip's share
    (``flops_cohere2_moe.moe_experts_call``'s rule at this model's
    widths): each expert that got a token read ONCE, 6 x hidden x width
    operations per (token, choice) pair routed to an expert held here.
    The activations' bytes are left out (under 1 % of one expert's
    weights at the cell's sizes)."""
    return {"bytes": experts_touched * expert_bytes(config),
            "flops": 6.0 * config["hidden_size"]
            * config["moe_intermediate_size"] * assignments}


def paged_block_call(config: dict, pages: float, block_size: int,
                     kv_bytes_per_elem: int = 2) -> dict:
    """One layer's paged call of the BLOCK lane: every live slot's
    ``block_length`` query rows against its context and its own block.
    It has to read each live page's K and V ONCE a slot (the rows of a
    block share them), and spends 2 x 2 x heads x head_dim operations
    per cached token and query row (QK^T and PV for every query head of
    the group)."""
    tokens = pages * block_size
    row = config["num_key_value_heads"] * config["head_dim"]
    rows = config["serve"]["generation"]["block_length"]
    return {"bytes": 2.0 * tokens * row * kv_bytes_per_elem,
            "flops": 4.0 * tokens * rows * config["num_attention_heads"]
            * config["head_dim"]}
