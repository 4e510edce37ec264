"""Operations and bytes the ``cohere2_moe`` layers NEED, from shapes
(``config`` is the configuration file: the published ``config.json``
keys). Never what a particular implementation spends."""

from __future__ import annotations


def expert_bytes(config: dict, weight_bytes: int = 2) -> int:
    """One SwiGLU expert's three matrices."""
    return 3 * config["hidden_size"] * config["intermediate_size"] \
        * weight_bytes


def moe_experts_call(config: dict, assignments: float,
                     experts_touched: float) -> dict:
    """One call of the routed-expert layer on one chip's share: it has
    to read each expert that got a token ONCE (whatever the number of
    its tokens) and spends 2 operations per weight per (token, choice)
    pair routed to an expert held here — 6 x hidden x width. The
    activations' bytes (pairs x hidden, three times) are left out: they
    are under 1 % of one expert's weights at the cell's sizes."""
    return {"bytes": experts_touched * expert_bytes(config),
            "flops": 6.0 * config["hidden_size"]
            * config["intermediate_size"] * assignments}


def paged_decode_call(config: dict, pages_full: float,
                      pages_window: float, block_size: int,
                      kv_bytes_per_elem: int = 2) -> dict:
    """One layer's paged decode attention, the MEAN over a model's
    layers: a full layer has to read every live page's K and V once, a
    window layer at most ``sliding_window / block_size + 1`` pages a
    slot (``pages_window`` is summed over the slots with that cap);
    2 x 2 x heads x head_dim operations per cached token read (QK^T and
    PV for every query head of the group)."""
    types = config["layer_types"][:config["num_hidden_layers"]]
    n_win = sum(t == "sliding_attention" for t in types)
    pages = (n_win * pages_window + (len(types) - n_win) * pages_full) \
        / len(types)
    tokens = pages * block_size
    row = config["num_key_value_heads"] * config["head_dim"]
    return {"bytes": 2.0 * tokens * row * kv_bytes_per_elem,
            "flops": 4.0 * tokens * config["num_attention_heads"]
            * config["head_dim"]}
