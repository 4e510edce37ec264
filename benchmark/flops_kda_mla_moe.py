"""Operations and bytes the ``kda_mla_moe`` layers NEED, from shapes
(``config`` is the configuration file: the published ``config.json``
keys). The latent decode call and the expert layer are
``flops_mla_moe``'s (the same keys); here are the two KDA scopes."""

from __future__ import annotations

#: pack rows a chunk of the chunk form the scan's operations are
#: counted by (``hetu_tpu/ops/kda.py`` states the same form)
CHUNK = 64


def kda_layers(config: dict) -> int:
    """KDA layers held: all but every ``layer_group_size``-th."""
    n = config["num_hidden_layers"]
    return n - n // config["layer_group_size"]


def state_bytes(config: dict) -> int:
    """One slot's float32 state in ONE KDA layer."""
    return config["num_attention_heads"] * config["head_dim"] ** 2 * 4


def tail_bytes(config: dict) -> int:
    """One slot's convolution tail in ONE KDA layer: the last ``taps -
    1`` float32 input rows of q, k and v."""
    return (config["short_conv_kernel_size"] - 1) * 3 \
        * config["num_attention_heads"] * config["head_dim"] * 4


def kda_scan_call(config: dict, tokens: float, runs: float = 1.0,
                  operand_bytes: int = 2) -> dict:
    """One KDA layer over a pack of ``tokens`` in ``runs`` runs, by the
    chunk form at ``CHUNK`` rows. Bytes: q, k, v read (bf16), the
    log-decay read and o written (float32) once a token; a run's state
    read and written once. Operations a token and head: the two
    lower-triangular products ``(K e^G)(K e^-G)^T`` and ``(Q e^G)(K
    e^-G)^T`` (half of ``2 C d`` each), the forward substitution on
    ``[K e^G | V]`` (``C d`` each half), ``P U`` (``C d``), and the
    three products with the state ``W S``, ``(Q e^G) S``, ``K^T U``
    (``2 d^2`` each)."""
    h, d = config["num_attention_heads"], config["head_dim"]
    per_token = 2 * CHUNK * d + 2 * CHUNK * d + CHUNK * d + 6 * d * d
    return {"bytes": float(tokens * h * d * (3 * operand_bytes + 4 + 4)
                           + 2 * runs * state_bytes(config)),
            "flops": float(tokens * h * per_token)}


def kda_update_call(config: dict, slots: float) -> dict:
    """One KDA layer's decode rows: each live slot's state is read and
    written once; a slot and head spends ``d^2`` on the decay and ``2
    d^2`` each on ``k^T S``, the rank-one write and ``S^T q``."""
    h, d = config["num_attention_heads"], config["head_dim"]
    return {"bytes": float(2 * slots * state_bytes(config)),
            "flops": float(slots * h * 7 * d * d)}
