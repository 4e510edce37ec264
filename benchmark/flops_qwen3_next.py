"""Operations and bytes the ``qwen3_next`` layers NEED, counted from the
EQUATIONS by the MODEL's sizes (``config`` is the configuration file:
the published ``config.json`` keys) — ``H = linear_num_value_heads``
value heads over ``Hk = linear_num_key_heads`` key heads of ``d``, ONE
decay a value head and token — whatever implements them: the program's
first form broadcasts the decay over a head's ``d`` channels and the
key heads over their value heads into the per-channel kernel, and that
shows here as a lower share, not as more work. The routed experts are
``flops_mla_moe``'s (the same keys)."""

from __future__ import annotations

#: pack rows a chunk of the chunk form the scan's operations are
#: counted by (``hetu_tpu/ops/kda.py`` states the same form)
CHUNK = 64


def gdn_layers(config: dict) -> int:
    """Gated DeltaNet layers held: all but every
    ``full_attention_interval``-th."""
    n = config["num_hidden_layers"]
    return n - n // config["full_attention_interval"]


def attention_layers(config: dict) -> int:
    return config["num_hidden_layers"] - gdn_layers(config)


def state_bytes(config: dict) -> int:
    """One slot's float32 state in ONE Gated DeltaNet layer."""
    return config["linear_num_value_heads"] \
        * config["linear_key_head_dim"] \
        * config["linear_value_head_dim"] * 4


def conv_channels(config: dict) -> int:
    """Channels the short convolution runs over: q and k a key head, v
    a value head."""
    return 2 * config["linear_num_key_heads"] \
        * config["linear_key_head_dim"] \
        + config["linear_num_value_heads"] * config["linear_value_head_dim"]


def tail_bytes(config: dict) -> int:
    """One slot's convolution tail in ONE Gated DeltaNet layer: the last
    ``taps - 1`` float32 input rows of q, k and v."""
    return (config["linear_conv_kernel_dim"] - 1) * conv_channels(config) \
        * 4


def gdn_scan_call(config: dict, tokens: float, runs: float = 1.0,
                  operand_bytes: int = 2) -> dict:
    """One Gated DeltaNet layer over a pack of ``tokens`` in ``runs``
    runs, by the chunk form at ``CHUNK`` rows. Bytes: q and k a key
    head and v a value head read (bf16), ``g`` and ``beta`` (one
    float32 each a value head) read and o written (float32) once a
    token; a run's state read and written once. Operations a token: a
    KEY head's two lower-triangular products ``K K^T`` and ``Q K^T``
    (half of ``2 C d`` each; the decay is a mask a value head on top),
    then a VALUE head's forward substitution on ``[K e^G | V]`` (``C
    d`` each half), ``P U`` (``C d``), and the three products with the
    state ``W S``, ``(Q e^G) S``, ``K^T U`` (``2 d^2`` each)."""
    hk, h = config["linear_num_key_heads"], config["linear_num_value_heads"]
    d = config["linear_key_head_dim"]
    per_token = hk * 2 * CHUNK * d + h * (3 * CHUNK * d + 6 * d * d)
    return {"bytes": float(tokens * ((2 * hk + h) * d * operand_bytes
                                     + h * d * 4 + 2 * h * 4)
                           + 2 * runs * state_bytes(config)),
            "flops": float(tokens * per_token)}


def gdn_update_call(config: dict, slots: float) -> dict:
    """One Gated DeltaNet layer's decode rows: each live slot's state is
    read and written once; a slot and value head spends ``d^2`` on the
    decay and ``2 d^2`` each on ``k^T S``, the rank-one write and ``S^T
    q``."""
    h, d = config["linear_num_value_heads"], config["linear_key_head_dim"]
    return {"bytes": float(2 * slots * state_bytes(config)),
            "flops": float(slots * h * 7 * d * d)}


def paged_decode_call(config: dict, pages: float, block_size: int,
                      kv_bytes_per_elem: int = 2) -> dict:
    """ONE gated attention layer's paged decode call: every live page's
    k and v rows (``num_key_value_heads x head_dim`` each) read once; 2
    x 2 x query heads x head_dim operations a cached token read (``q
    k^T`` and ``p v`` for every query head of the group)."""
    tokens = pages * block_size
    row = config["num_key_value_heads"] * config["head_dim"]
    return {"bytes": 2.0 * tokens * row * kv_bytes_per_elem,
            "flops": 4.0 * tokens * config["num_attention_heads"]
            * config["head_dim"]}
