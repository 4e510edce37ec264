"""Runner of the ``serve_arch_blocks`` kind: ``serve_arch`` — its load,
window, judging and audits, line for line (``runners/serve_arch.py``
runs; nothing of it is copied) — for a model that GENERATES BY
DIFFUSION OVER BLOCKS, with the comparison that way of generating needs
handed to ``serve_arch.run`` in place of ``serve_arch._reference_check``,
as ``serve_arch_ties`` hands in its own. Its rows take a clean and a
noised stream and are one program a prompt length's whole blocks, not
``serve_arch.ReferenceRows``' one program a cell: they keep their own
``jax.jit`` (``rows_ahead=False``) and compile at their first call.

Why a kind of its own. A teacher-forced row says nothing of such a
program: a token was not predicted from the tokens before it but from
its block AS IT STOOD at the pass that unmasked it — some positions
final, the rest the mask token — against the clean keys of the blocks
before. So a finished request carries, for every output token, the pass
of its block at which it was unmasked (``Request.unmask_pass``, a field
of the stream's last frame), and the comparison rebuilds the block
states from it:

* the reference (``arch.reference_rows``) runs ONE clean stream (the
  final tokens, block-causal) and, for each pass ``s``, ONE noised
  stream (every block as it stood going into pass ``s``; a noised query
  sees the clean keys of earlier blocks and the noised keys of its own
  block). A request's last block, cut at ``max_tokens``, is not
  compared (the tokens beyond the cut were never sent);
* for every token unmasked at pass ``s`` (i): its logit in stream ``s``
  within ``LOGIT_TOL`` of the reference's top logit there (the mask
  id's at ``-inf``) — judged by SHARE, as ``serve_arch_ties`` judges
  routing near-ties: a position whose routing margin (the reference's,
  the smallest over the layers) is under ``ROUTE_TOL`` may be over, at
  most ``NEAR_TIE_OVER_MAX`` of them are;
* for every pass (ii): the positions the program unmasked are the ones
  the reference's rule unmasks on the reference's confidences over the
  positions the PROGRAM still held masked — or, where they are not,
  the confidences of the two choices differ by at most ``CONF_TOL``
  (near-equal confidences may swap on rounding; a sequential or random
  order may not): at most ``CONF_OVER_MAX`` of the passes may differ by
  more;
* audited on the compared requests: no mask id emitted; every block's
  passes unmask what the static schedule says (``passes = (steps + 1)
  x blocks``).

The arch file gives the six limits, each with its two readings. The
mix's ``generation`` repeats the configuration's ``serve.generation``
defaults (the wire's request names none); the runner checks they agree.
The block lane's counters (``serving_diffusion_*``) over the whole run
go into the records, for the ``.blockgen`` metrics.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark.runners import serve_arch

LIMITS = ("LOGIT_TOL", "ROUTE_TOL", "NEAR_TIE_OVER_MAX", "ROUTE_SHARE_MAX",
          "CONF_TOL", "CONF_OVER_MAX")
COUNTERS = ("serving_diffusion_blocks_total",
            "serving_diffusion_tokens_total")


def block_states(prompt, tokens, unmask, gen: dict, seq_len: int):
    """``(clean (seq_len,), noised (steps, seq_len - start), start,
    when (seq_len,), end)``: the final tokens, the rows from ``start``
    (the prompt's whole blocks) on as they stood going into each pass,
    the pass that unmasked each position (-1: the prompt's) and the end
    of the last WHOLE block of prompt + output."""
    B, M = gen["block_length"], gen["mask_token_id"]
    P = len(prompt)
    start = P // B * B
    seq = np.concatenate([prompt, tokens]).astype(np.int32)
    end = min(len(seq) // B * B, seq_len // B * B)
    clean = np.zeros(seq_len, np.int32)
    clean[:end] = seq[:end]
    when = np.full(seq_len, -1, np.int64)
    when[P:end] = np.asarray(unmask, np.int64)[:end - P]
    passes = np.arange(gen["denoising_steps"])[:, None]
    noised = np.where(when[None, start:] < passes, clean[None, start:], M)
    noised[:, end - start:] = 0
    return clean, noised.astype(np.int32), start, when, end


def readings(arch, config, params, recs, max_len: int):
    """What the reference says of ``recs``' tokens and passes: ``(why
    (the audits), gap, margin, below, swap, largest)`` — per compared
    token its logit's gap under the reference's top at the pass that
    unmasked it, its routing margin and what a random token would read;
    per compared pass how far the program's choice lies from the
    reference rule's in confidence (0: the same positions). One jitted
    call a request at fixed shapes (the row padded to ``max_len``); it
    compiles once a prompt length's whole blocks."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import sdar_moe as reference
    gen = arch.generation(config)
    B, M, steps = (gen["block_length"], gen["mask_token_id"],
                   gen["denoising_steps"])
    counts = reference.transfer_counts(B, steps)
    order = reference.CONTROL.get("order", "confidence")

    @functools.lru_cache(maxsize=None)
    def rows(start: int):
        def fn(p, clean, noised):
            lg, margin = arch.reference_rows(config, p, clean, noised,
                                             start)
            _, conf = reference.confidences(lg, M)
            far = lg.max(-1) - lg.mean(-1)
            lg = lg.at[..., M].set(-jnp.inf)
            mine = jnp.take_along_axis(
                lg, clean[None, start:, None], axis=-1)[..., 0]
            return lg.max(-1) - mine, conf, margin, far
        return jax.jit(fn)

    why, gaps, margins, below, largest, swap = [], [], [], [], [], []
    for r in recs:
        prompt = np.asarray(r["prompt"], np.int32)
        toks = np.asarray(r["tokens"], np.int32)
        unmask = (r.get("result") or {}).get("unmask_pass")
        P = len(prompt)
        if unmask is None or len(unmask) != len(toks):
            why.append(f"request of {P} prompt tokens: no unmask pass "
                       f"a token in its result")
            continue
        if (toks == M).any():
            why.append(f"request of {P} prompt tokens: the mask id "
                       f"{M} was emitted")
        clean, noised, start, when, end = block_states(
            prompt, toks, unmask, gen, max_len)
        gap, conf, margin, far = (np.asarray(x) for x in rows(start)(
            params, clean, noised))
        at = np.arange(P, end)
        s_at = when[at]
        gaps.append(gap[s_at, at - start])
        margins.append(margin[s_at, at - start])
        below.append(far[s_at, at - start])
        largest += [(float(gaps[-1][j]), float(margins[-1][j]), P, int(j))
                    for j in np.argsort(-gaps[-1])[:3]]
        for b0 in range(start, end, B):
            w = when[b0:b0 + B]
            if gen["remasking"] == "low_confidence_static" and any(
                    (w == s).sum() != min(counts[s], (w >= s).sum())
                    for s in range(steps)):
                why.append(
                    f"request of {P} prompt tokens: the block at {b0} "
                    f"was unmasked at passes {w.tolist()}, not "
                    f"{counts} a pass")
                break
            for s in range(steps):
                masked = w >= s
                if not masked.any():
                    break
                c = conf[s, b0 - start:b0 - start + B]
                want = reference.pick(c, masked, counts[s],
                                      gen["remasking"],
                                      gen["confidence_threshold"],
                                      order=order)
                got = w == s
                a, b = np.sort(c[want & ~got]), np.sort(c[got & ~want])
                # choices of unequal size differ by a whole position
                swap.append(0.0 if (want == got).all() else 1.0
                            if len(a) != len(b) else float(
                                (np.abs(a - b) / np.maximum(a, b)).max()))
    cat = lambda xs: np.concatenate(xs) if xs else np.zeros(0)  # noqa: E731
    return why, cat(gaps), cat(margins), cat(below), np.asarray(swap), \
        sorted(largest, reverse=True)[:6]


def reference_check(limits: dict, arch, config, rows, params, recs,
                    max_len: int) -> tuple[list[str], dict]:
    """See the module docstring: :func:`readings` under the arch
    file's limits (``rows`` is ``None``: see there)."""
    from benchmark.reference import sdar_moe as reference
    del rows
    why, gap, margin, below, swap, largest = readings(
        arch, config, params, recs, max_len)
    tie = margin < limits["ROUTE_TOL"]
    logit_tol = limits["LOGIT_TOL"]
    if (gap[~tie] > logit_tol).any():
        why.append(f"an output token is {float(gap[~tie].max())} below "
                   f"the float32 reference's top logit at the pass that "
                   f"unmasked it (tolerance {logit_tol}, no near-tie)")
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
    n_pass, swaps = len(swap), int((swap > limits["CONF_TOL"]).sum())
    if n_pass and swaps > limits["CONF_OVER_MAX"] * n_pass:
        why.append(f"{swaps} of {n_pass} passes unmasked positions "
                   f"whose confidences lie more than "
                   f"{limits['CONF_TOL']:.0%} from the reference rule's "
                   f"choice (at most {limits['CONF_OVER_MAX']:.0%})")
    return why, {
        "compared_positions": compared, "route_near_ties": near,
        "compared_beyond_window": 0,
        "max_logit_gap": float(gap[~tie].max()) if (~tie).any() else 0.0,
        "max_logit_gap_at_near_ties":
            float(gap[tie].max()) if near else 0.0,
        "near_ties_over_logit_tol": over,
        "near_ties_over_share": over / near if near else 0.0,
        "logit_gap_quantiles": [float(q) for q in np.quantile(
            gap, [0.5, 0.9, 0.99])] if compared else [],
        # the shares the limits were read from, at other tolerances
        "over_share_at": {str(t): float((gap > t).mean())
                          for t in (0.1, 0.3, 0.5, 0.75, 1.0)}
        if compared else {},
        "swap_share_at": {str(t): float((swap > t).mean())
                          for t in (0.0, 0.1, 0.2, 0.3, 0.4, 0.5)}
        if n_pass else {},
        "routing_margin_quantiles": [float(q) for q in np.quantile(
            margin, [0.01, 0.5, 0.99])] if compared else [],
        # what a token drawn at random would read as its gap
        "mean_logit_below_top":
            float(below.mean()) if compared else None,
        "compared_passes": n_pass, "confidence_swaps": swaps,
        "confidence_swap_share": swaps / n_pass if n_pass else 0.0,
        "largest_confidence_swap": float(swap.max()) if n_pass else 0.0,
        # (gap, routing margin, prompt length, output index)
        "largest_gaps": largest,
        "compared_prompt_lens": [len(r["prompt"]) for r in recs],
        "limits": limits, "control": {
            k: str(v) for k, v in reference.CONTROL.items()}}


def run(ctx) -> dict:
    from hetu_tpu import telemetry
    arch = serve_arch.load_arch(ctx.config["arch"])
    gen = arch.generation(ctx.config)
    differ = {k: (v, gen.get(k)) for k, v in
              ctx.mix.get("generation", {}).items() if gen.get(k) != v}
    if differ:
        raise ValueError(f"the mix's generation and the configuration's "
                         f"serve.generation differ: {differ}")
    limits = {name: float(getattr(arch, name)) for name in LIMITS}
    out = serve_arch.run(ctx, functools.partial(reference_check, limits),
                         rows_ahead=False)
    reg = telemetry.get_registry()
    passes = reg.counter("serving_diffusion_passes_total")
    out["records"]["diffusion"] = {
        "denoise_passes": passes.value(kind="denoise"),
        "commit_passes": passes.value(kind="commit"),
        **{n: reg.counter(n).value() for n in COUNTERS}}
    out["info"]["diffusion"] = out["records"]["diffusion"]
    return out
