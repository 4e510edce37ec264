"""The negative controls of the ``qwen3-next-80b-a3b-ep8`` cell, on the
chip: ``workloads/jamba_controls.py``'s run — the PROGRAM once (the
engine offline at the cell's configuration, a request of the cell's
lengths, the slot's state read where the last chunk and where the last
decoded token leave it), then ``serve_arch_ssm``'s two comparisons
against the float32 token recurrence as it is and changed in ONE way
each (``benchmark/reference/qwen3_next.py::CONTROL``) — with this
cell's controls and what its rule on a SHARE needs beside them (PERF.md
section 6, PR 59).

    chiprun -- python3 workloads/qwen3_next_controls.py [--seed N] [--only ...]

Prints one JSON line a reading: ``correct`` as the runner's comparisons
decide it, the share of compared positions more than ``LOGIT_TOL`` below
the reference's top logit beside its limit (and at other tolerances, for
setting it), the largest gap, and the state's gap beside its limit.
(``--config tests/benchmark/configs/qwen3-next-tiny.json --requests 4
--prompt 40 --outputs 12`` rehearses it on the CPU in a minute.)
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jamba_controls  # noqa: E402

CONTROLS = {
    "none": {},
    "bfloat16_operands": {"operands": "bfloat16"},
    "float8_e4m3fn_operands": {"operands": "float8_e4m3fn"},
    "bfloat16_state": {"state_dtype": "bfloat16"},
    **{name: {name: True} for name in (
        "no_erase", "no_conv", "tile_key_heads", "full_rotary",
        "no_out_gate", "no_shared_gate", "plain_gain", "sigmoid_router")},
}
#: tolerances the share of positions over is also read at
TOLS = (0.05, 0.1, 0.15, 0.2, 0.3, 0.5)


def shares(seen, recs, logits, limits) -> dict:
    """What a rule on a share is set from: the compared positions' gaps
    below the reference's top logit, as shares over several tolerances
    and as quantiles."""
    gap = np.concatenate([
        lg[:len(r["tokens"])].max(-1) - lg[
            np.arange(len(r["tokens"])), np.asarray(r["tokens"])]
        for lg, r in zip(logits, recs)])
    return {"over_share": seen["near_ties_over_share"],
            "over_share_limit": limits["NEAR_TIE_OVER_MAX"],
            "over_share_at": {str(t): float((gap > t).mean())
                              for t in TOLS},
            "max_gap": float(gap.max()),
            "gap_p50_p90_p99": np.quantile(gap, (0.5, 0.9, 0.99)).tolist()}


if __name__ == "__main__":
    jamba_controls.main(
        CONTROLS, reference="qwen3_next", seed=2159590251,
        config="benchmark/configs/qwen3-next-80b-a3b-ep8.json", extra=shares)
