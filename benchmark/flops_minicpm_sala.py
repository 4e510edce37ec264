"""Operations and bytes the ``minicpm_sala`` layers NEED, from shapes
(``config`` is the configuration file: the published ``config.json``
keys and the sparse sizes under ``assumed``). Never what a particular
implementation spends."""

from __future__ import annotations


def page_bytes(config: dict, kv_bytes_per_elem: int = 2) -> int:
    """One chosen page of ONE kv head: its keys and its values."""
    return 2 * config["assumed"]["block_size"] * config["head_dim"] \
        * kv_bytes_per_elem


def sparse_attn_call(config: dict, chosen_pages: float) -> dict:
    """The block-sparse read: every chosen page of a (row, kv head) is
    read ONCE, K and V of that head alone (64 x 512 B), and each of the
    group's query heads spends 2 x head_dim operations on a key's score
    and as many on its value."""
    group = config["num_attention_heads"] // config["num_key_value_heads"]
    keys = chosen_pages * config["assumed"]["block_size"]
    return {"bytes": float(chosen_pages * page_bytes(config)),
            "flops": float(keys * group * 4 * config["head_dim"])}


def state_bytes(config: dict) -> int:
    """One slot's float32 state in ONE lightning layer."""
    return config["lightning_nh"] * config["lightning_head_dim"] ** 2 * 4


def linear_scan_call(config: dict, tokens: float, runs: float = 1.0,
                     operand_bytes: int = 2) -> dict:
    """One lightning layer over a pack of ``tokens`` in ``runs`` runs:
    q, k, v are read (bf16) and o written (float32) once a token, a
    run's state read and written once; a token and head spends 2 d^2
    operations on ``k v^T`` into the state and 2 d^2 on ``S^T q``."""
    h, d = config["lightning_nh"], config["lightning_head_dim"]
    return {"bytes": float(tokens * h * d * (3 * operand_bytes + 4)
                           + 2 * runs * state_bytes(config)),
            "flops": float(tokens * h * 4 * d * d)}


def linear_update_call(config: dict, slots: float) -> dict:
    """One lightning layer's decode rows: each live slot's state is
    read and written once, with the same 4 d^2 operations a head."""
    h, d = config["lightning_nh"], config["lightning_head_dim"]
    return {"bytes": float(2 * slots * state_bytes(config)),
            "flops": float(slots * h * 4 * d * d)}


def layers(config: dict) -> tuple[int, int]:
    """``(sparse, lightning)`` layers held."""
    kinds = config["mixer_types"]
    n = kinds.count("minicpm4")
    return n, len(kinds) - n
