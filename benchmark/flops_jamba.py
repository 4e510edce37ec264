"""Operations and bytes the selective-scan layers NEED, counted from
the EQUATIONS by the MODEL's sizes (``config`` is the configuration
file: the published ``config.json`` keys) — ``N = mamba_d_state``
states by ``D = mamba_expand x hidden_size`` channels of float32 a
layer and slot, whatever layout a kernel chose — so that a later kernel
is judged on the same work.

The scan's operations are VECTOR operations (a multiply, an ``exp``, a
multiply-add and a product into the sum over the states a (channel,
state) pair and token): no step of it is a matrix product, and they
are counted as operations all the same, against the one peak
``benchmark/peaks.py`` has — the MATRIX unit's. A share of the roofline
made from these counts therefore reads LOW by construction wherever the
operations and not the bytes bound the kernel; a vector peak is a
``benchmark`` issue's to add."""

from __future__ import annotations

#: operations a (channel, state) pair and token: ``dt A`` (1), ``exp``
#: (1), the decay times the state (1), ``(dt x) B`` (1), the add (1),
#: ``h C`` and its add into ``y`` (2)
PAIR_OPS = 7


def channels(config: dict) -> int:
    """``D``: the mixer's inner channels."""
    return config["mamba_expand"] * config["hidden_size"]


def mamba_layers(config: dict) -> int:
    """Mamba layers: all but every ``attn_layer_period``-th from
    ``attn_layer_offset``."""
    n, p = config["num_hidden_layers"], config["attn_layer_period"]
    return n - len(range(config["attn_layer_offset"], n, p))


def state_bytes(config: dict) -> int:
    """One slot's float32 state in ONE Mamba layer: ``N x D``."""
    return config["mamba_d_state"] * channels(config) * 4


def tail_bytes(config: dict) -> int:
    """One slot's convolution tail in ONE Mamba layer: the last ``taps
    - 1`` float32 inputs a channel."""
    return (config["mamba_d_conv"] - 1) * channels(config) * 4


def selective_scan_call(config: dict, tokens: float, runs: float = 1.0
                        ) -> dict:
    """One Mamba layer over a pack of ``tokens`` in ``runs`` runs.
    Bytes: ``x`` and ``dt`` read and ``y`` written (float32, ``D`` each)
    and ``B``, ``C`` read (``N`` each) once a token; ``A`` read once; a
    run's state read and written once. Operations: ``PAIR_OPS`` a
    (channel, state) pair and token, and ``dt x`` a channel."""
    D, N = channels(config), config["mamba_d_state"]
    return {"bytes": float(tokens * (3 * D + 2 * N) * 4 + N * D * 4
                           + 2 * runs * state_bytes(config)),
            "flops": float(tokens * D * (PAIR_OPS * N + 1))}


def selective_update_call(config: dict, slots: float) -> dict:
    """One Mamba layer's decode rows: each live slot's state is read
    and written once beside its ``x``, ``dt``, ``y`` and ``B``, ``C``;
    ``A`` once; the same operations a pair as the scan's."""
    D, N = channels(config), config["mamba_d_state"]
    return {"bytes": float(slots * (2 * state_bytes(config)
                                    + (3 * D + 2 * N) * 4) + N * D * 4),
            "flops": float(slots * D * (PAIR_OPS * N + 1))}
