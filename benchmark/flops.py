"""Operations and bytes computed from shapes: what the model and its
kernels NEED, never what a particular implementation spends.

All counts are for GPT-2's block (``config.json`` keys ``n_layer``,
``n_embd``, ``n_head``, ``vocab_size``): per layer 4 H^2 of attention
projections and 8 H^2 of MLP, and the tied V x H head.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matrix multiplication for every
    token: the blocks' projections and the tied LM head. The position
    and token embeddings are lookups and count nothing."""
    h, n_layer, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    return 12 * n_layer * h * h + v * h


def train_flops_per_token(cfg: dict, attended_per_token: float) -> float:
    """Forward + backward operations one trained token requires:
    6 per matmul parameter, plus attention's two matmuls (QK^T and PV)
    at 2 * H operations per attended key each, three times over for
    forward and backward. ``attended_per_token`` is the mean number of
    keys a token attends to (S/2 for one causal document of length S).
    Recomputation (remat, the flash backward's second QK^T) is not
    counted."""
    h, n_layer = cfg["n_embd"], cfg["n_layer"]
    attn = 3 * 2 * 2 * h * attended_per_token * n_layer
    return 6.0 * matmul_params(cfg) + attn


def flash_train_call(cfg: dict, rows: int, seq_len: int,
                     pairs_per_row: float) -> dict:
    """One layer's flash forward plus backward over a batch: the
    operations and HBM bytes the algorithm needs.

    ``pairs_per_row`` is the mean number of (query, key) pairs a row
    really has under its causal and document masks (S^2/2 for one full
    document). Forward: QK^T and PV, 2 * d operations per pair per head
    each. Backward: dV, dP, dQ, dK — four such matmuls (the recomputed
    QK^T is the implementation's, not the algorithm's). Bytes: forward
    reads Q, K, V and writes O; backward reads Q, K, V, O, dO and writes
    dQ, dK, dV; each is rows x S x H in bf16."""
    h = cfg["n_embd"]
    per_matmul = 2.0 * h * pairs_per_row * rows
    tensor = 2.0 * rows * seq_len * h
    return {"fwd_flops": 2 * per_matmul, "bwd_flops": 4 * per_matmul,
            "fwd_bytes": 4 * tensor, "bwd_bytes": 8 * tensor}


def paged_decode_call(cfg: dict, pages: float, block_size: int,
                      kv_bytes_per_elem: int = 2) -> dict:
    """One layer's paged decode attention: it has to read every live
    page's K and V once (``pages`` x block_size x H elements each) and
    spends 2 * 2 * H operations per cached token."""
    h = cfg["n_embd"]
    tokens = pages * block_size
    return {"bytes": 2.0 * tokens * h * kv_bytes_per_elem,
            "flops": 4.0 * tokens * h}


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations
    over peak FLOP/s and bytes over peak bytes/s."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
