"""Operations and bytes the power-retention layers NEED, counted from
the EQUATIONS (``config`` is the configuration file: the published
``config.json`` keys) — the state as the model defines it, ``D = d (d +
1) / 2`` features by ``d + 1`` float32 a kv head, not whatever layout a
kernel chose — so that a later kernel is judged on the same work."""

from __future__ import annotations

#: tokens a chunk of the chunk form the scan's quadratic part is counted
#: at (``hetu_tpu/ops/retention_pallas.py`` states the same)
CHUNK = 256


def features(config: dict) -> int:
    """``D``: the symmetric second tensor power of a head."""
    d = config["head_dim"]
    return d * (d + 1) // 2


def state_bytes(config: dict) -> int:
    """One slot's float32 state in ONE layer: ``D x (d + 1)`` a kv
    head."""
    return config["num_key_value_heads"] * features(config) \
        * (config["head_dim"] + 1) * 4


def retention_scan_call(config: dict, tokens: float, runs: float = 1.0,
                        operand_bytes: int = 2) -> dict:
    """One layer over a pack of ``tokens`` in ``runs`` runs. Bytes: q,
    k, v read (bf16), the gate read and y written (float32) once a
    token; a run's state read and written once. Operations a token:
    ``phi(q)^T S`` a QUERY head (``2 D (d + 1)``), the update's ``phi(k)
    [v, 1]^T`` a KV head (``2 D (d + 1)``), and the quadratic form
    inside the chunk a query head — ``q k^T`` and the weights against
    ``[v, 1]``, half of ``2 C d`` and of ``2 C (d + 1)`` (causal)."""
    h, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    d, D = config["head_dim"], features(config)
    per_token = (h + hkv) * 2 * D * (d + 1) \
        + h * CHUNK * (2 * d + 1)
    return {"bytes": float(
        tokens * ((h + 2 * hkv) * d * operand_bytes + hkv * 4 + h * d * 4)
        + 2 * runs * state_bytes(config)),
        "flops": float(tokens * per_token)}


def retention_update_call(config: dict, rows: float) -> dict:
    """One layer's decode rows: each live row's state read and written
    once; a row spends ``3 D (d + 1)`` a kv head on the gate and the
    rank-one write and ``2 D (d + 1)`` a query head on the read."""
    h, hkv = config["num_attention_heads"], config["num_key_value_heads"]
    d, D = config["head_dim"], features(config)
    return {"bytes": float(2 * rows * state_bytes(config)),
            "flops": float(rows * (3 * hkv + 2 * h) * D * (d + 1))}
