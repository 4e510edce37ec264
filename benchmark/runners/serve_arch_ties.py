"""Runner of the ``serve_arch_ties`` kind: ``serve_arch`` — its load,
window, judging and audits, line for line (``runners/serve_arch.py``
runs; nothing of it is copied but the loop of the comparison) — for an
architecture whose routing near-ties are the RULE, with the
comparison's limits taken from the arch file.

Why a kind of its own. ``serve_arch`` holds an emitted token to
``LOGIT_TOL`` of the float32 reference's top logit, except where the
reference's own top-k routing is a near-tie (rounding picks another set
of experts, a different and equally valid computation): there it
allows ``NEAR_TIE_TOL`` per token and at most ``ROUTE_SHARE_MAX`` of the
positions. Those constants were read on 4 expert layers of 16 held
experts. With 64 experts chosen 6 at a time in each of 6 layers, the
gap between the 6th and 7th of 64 selection scores is a tenth of their
spread, and a position has a near-tie in SOME layer nine times in ten
(by order statistics 87 %; read 89 %); and because each chosen expert
carries ``routed_scaling_factor`` / 6 = 0.41 of the routed sum, one
flipped expert moves the top logit by up to ~2, half of what a random
token lies below it. A per-token allowance at near-ties would have to
be that wide and would refuse nothing. So this kind judges near-ties by
their SHARE instead:

* off near-ties (no expert of any layer within ``ROUTE_TOL`` of the
  cut): every token within ``LOGIT_TOL`` of the reference's top logit,
  as in ``serve_arch``;
* at near-ties: most do not flip — the program's routing input differs
  from the reference's by bf16 rounding, far less than ``ROUTE_TOL`` —
  so at most ``NEAR_TIE_OVER_MAX`` of them may lie more than
  ``LOGIT_TOL`` below the top. A computation below the stated
  precision, or a router that leaves the selection bias out, flips
  nearly all of them;
* at most ``ROUTE_SHARE_MAX`` of the compared positions may be
  near-ties (a property of the weights and the tokens, not of the
  program: it guards how much is compared closely).

The arch file gives the four limits, each with its two readings
(``LOGIT_TOL``, ``ROUTE_TOL``, ``NEAR_TIE_OVER_MAX``,
``ROUTE_SHARE_MAX``). The comparison is handed to ``serve_arch.run``
in place of ``serve_arch._reference_check``; ``serve_arch.run`` does
everything else, unchanged, the compile of the reference's rows
included.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark.runners import serve_arch

LIMITS = ("LOGIT_TOL", "ROUTE_TOL", "NEAR_TIE_OVER_MAX", "ROUTE_SHARE_MAX")


def reference_check(limits: dict, arch, config, rows, params, recs,
                    max_len: int) -> tuple[list[str], dict]:
    """``serve_arch._reference_check`` under the rule of this kind (see
    the module docstring): the same ``rows``, one call a request."""
    logit_tol, route_tol = limits["LOGIT_TOL"], limits["ROUTE_TOL"]
    why, gaps, margins, below, largest = [], [], [], [], []
    for r in recs:
        toks = np.asarray(r["tokens"], np.int64)
        n, p_len = len(toks), len(r["prompt"])
        ids = np.zeros(max_len, np.int32)
        seq = np.concatenate([r["prompt"], toks])[:max_len]
        ids[:len(seq)] = seq
        lg, margin = rows(params, ids, np.int32(p_len - 1))
        lg, margin = np.asarray(lg)[:n], np.asarray(margin)[:n]
        gap = lg.max(-1) - lg[np.arange(n), toks]
        gaps.append(gap)
        margins.append(margin)
        below.append(lg.max(-1) - np.median(lg, -1))
        largest += [(float(gap[j]), float(margin[j]), p_len, int(j))
                    for j in np.argsort(-gap)[:3]]
        off = margin >= route_tol
        if (gap[off] > logit_tol).any():
            j = int(np.argmax(np.where(off, gap, -1.0)))
            why.append(
                f"request of {p_len} prompt tokens: output token {j} is "
                f"{float(gap[j])} below the float32 reference's top "
                f"logit (tolerance {logit_tol}; routing margin "
                f"{float(margin[j])}, no near-tie)")
    gap = np.concatenate(gaps) if gaps else np.zeros(0)
    margin = np.concatenate(margins) if margins else np.zeros(0)
    tie = margin < route_tol
    compared, near = len(gap), int(tie.sum())
    over = int((gap[tie] > logit_tol).sum())
    if near and over > limits["NEAR_TIE_OVER_MAX"] * near:
        why.append(f"{over} of {near} routing near-ties lie more than "
                   f"{logit_tol} below the reference's top logit (at "
                   f"most {limits['NEAR_TIE_OVER_MAX']:.0%})")
    if compared and near > limits["ROUTE_SHARE_MAX"] * compared:
        why.append(f"{near} of {compared} compared positions are "
                   f"routing near-ties (at most "
                   f"{limits['ROUTE_SHARE_MAX']:.0%})")
    return why, {
        "compared_positions": compared, "route_near_ties": near,
        "compared_beyond_window": 0,
        "max_logit_gap": float(gap[~tie].max()) if (~tie).any() else 0.0,
        "max_logit_gap_at_near_ties":
            float(gap[tie].max()) if near else 0.0,
        "near_ties_over_logit_tol": over,
        "near_ties_over_share": over / near if near else 0.0,
        "median_logit_below_top": float(np.median(
            np.concatenate(below))) if below else None,
        # (gap, routing margin, prompt length, output index)
        "largest_gaps": sorted(largest, reverse=True)[:6],
        "compared_prompt_lens": [len(r["prompt"]) for r in recs],
        "limits": limits}


def run(ctx) -> dict:
    arch = serve_arch.load_arch(ctx.config["arch"])
    limits = {name: float(getattr(arch, name)) for name in LIMITS}
    return serve_arch.run(ctx, functools.partial(reference_check, limits))
