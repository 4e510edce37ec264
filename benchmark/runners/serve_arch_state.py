"""Runner of the ``serve_arch_state`` kind: ``serve_arch``'s load,
window and judging (``runners/serve_arch.py``, called as
``serve_arch_ties`` calls it) for a model whose layers keep NO token
rows — a recurrent state a slot and nothing else, so its engine holds
no arena and its step no attention.

What differs from ``serve_arch``:

* **the audit of the step.** ``serve_arch`` asks that "decode ran the
  paged kernel"; of a step without attention that cannot be said, and
  the engine says ``attn_kernel: "none"``. This kind asks instead that
  the engine resolved NO attention kernel, that the retention layers'
  two kernels advanced rows in both lanes
  (``retention_rows_total{lane}``: the layer has no other path — no
  ``jax.numpy`` form stands behind ``hetu_retention_scan`` /
  ``hetu_retention_update``), that on a chip they were compiled and
  not interpreted, and — a traced run, which reads the compiled step's
  text anyway — that the step holds instructions under
  ``hetu.retention_scan`` and ``hetu.retention_update``.
  ``kernel_fallbacks()`` empty is ``serve_arch``'s own check and
  stays;
* **the comparison** is the plain one at the ARCH's ``LOGIT_TOL``:
  every compared token within it of the float32 reference's top logit
  (a dense model: no routing near-tie excuses a position);
* **the records.** The unit the cache is handed out in is a slot's
  state: ``kv_blocks`` = the slots and ``block_size`` = 1, so that the
  readers' "one layer's leaf of the cache" (``kv_blocks x block_size x
  n_embd``, ``n_embd`` = a slot's state elements in a layer) is one
  layer of the state leaf; ``arena_blocks`` keeps the engine's own 0.
  ``slots_live``: ``serving_slots{state=live}`` sampled each second of
  the window, and ``slots``.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark.runners import serve_arch

RETENTION_SCOPES = ("hetu.retention_scan", "hetu.retention_update")


def gaps(arch, config, rows, params, recs, max_len: int) -> list:
    """Per compared request ``(gap (n,), below (n,), prompt length)``:
    how far each emitted token lies below the float32 reference's top
    logit at its position, and how far a token drawn at random would
    (the top logit over the median)."""
    out = []
    for r in recs:
        toks = np.asarray(r["tokens"], np.int64)
        n, p_len = len(toks), len(r["prompt"])
        ids = np.zeros(max_len, np.int32)
        seq = np.concatenate([r["prompt"], toks])[:max_len]
        ids[:len(seq)] = seq
        # the token emitted at output index j was predicted from
        # position prompt_len - 1 + j
        lg, _ = rows(params, ids, np.int32(p_len - 1))
        lg = np.asarray(lg)[:n]
        out.append((lg.max(-1) - lg[np.arange(n), toks],
                    lg.max(-1) - np.median(lg, -1), p_len))
    return out


def judge(got: list, tol: float) -> tuple[list[str], dict]:
    """:func:`gaps`' readings against the limit: what fails, and what
    was seen."""
    why, largest = [], []
    for gap, _, p_len in got:
        largest += [(float(gap[j]), p_len, int(j))
                    for j in np.argsort(-gap)[:3]]
        if gap.max() > tol:
            j = int(np.argmax(gap))
            why.append(
                f"request of {p_len} prompt tokens: output token {j} is "
                f"{float(gap[j])} below the float32 reference's top "
                f"logit (tolerance {tol})")
    return why, {"compared_positions": sum(len(g) for g, _, _ in got),
                 "route_near_ties": 0, "compared_beyond_window": 0,
                 "max_logit_gap": max([float(g.max()) for g, _, _ in got],
                                      default=0.0),
                 "logit_tolerance": tol,
                 # what a token drawn at random would read as its gap
                 "median_logit_below_top": float(np.median(np.concatenate(
                     [b for _, b, _ in got]))) if got else None,
                 # (gap, prompt length, output index)
                 "largest_gaps": sorted(largest, reverse=True)[:6],
                 "compared_prompt_lens": [p for _, _, p in got]}


def reference_check(arch, config, rows, params, recs, max_len: int
                    ) -> tuple[list[str], dict]:
    """Every emitted token within ``arch.LOGIT_TOL`` of the float32
    reference's top logit at its position."""
    return judge(gaps(arch, config, rows, params, recs, max_len),
                 arch.LOGIT_TOL)


class _SlotSampler:
    """``serving_slots{state=live}`` each second, with the clock."""

    def __init__(self):
        self.samples: list[tuple[float, float]] = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._work, daemon=True,
                                        name="bench-slot-sampler")

    def _work(self) -> None:
        from hetu_tpu import telemetry
        reg = telemetry.get_registry()
        while not self._stop.wait(1.0):
            g = reg.get("serving_slots")
            if g is not None:
                self.samples.append((time.perf_counter(),
                                     g.value(state="live")))

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join(timeout=5.0)


def run(ctx) -> dict:
    from hetu_tpu import telemetry
    with _SlotSampler() as sampler:
        out = serve_arch.run(ctx, reference_check)
    rec, info = out["records"], out["info"]
    # (of a step without attention "the paged kernel ran" is not asked)
    why = [w for w in out["why_incorrect"]
           if not w.startswith("decode ran 'none'")]
    if info["attn_kernel"] != "none":
        why.append(f"the engine resolved the attention kernel "
                   f"{info['attn_kernel']!r}: some layer keeps token "
                   f"rows, and this kind is for a model of which none "
                   f"does")
    reg = telemetry.get_registry()
    rows = reg.get("retention_rows_total")
    for lane in ("prefill", "decode"):
        if rows is None or not rows.value(lane=lane):
            why.append(f"the retention kernels advanced no {lane} row")
    if ctx.on_chip:
        from hetu_tpu.ops.flash_pallas import _interpret_default
        if _interpret_default():
            why.append("the retention kernels ran interpreted")
        if ctx.trace:
            from hetu_tpu.telemetry import device_scopes
            held = {sc.label for by_name in
                    device_scopes.registered_scopes().values()
                    for sc in by_name.values()}
            for scope in RETENTION_SCOPES:
                if scope not in held:
                    why.append(f"the compiled step holds no instruction "
                               f"under {scope}")
    lo, hi = rec["window"]
    rec["arena_blocks"] = rec["kv_blocks"]
    rec["kv_blocks"], rec["block_size"] = info["slots"], 1
    rec["slots"] = info["slots"]
    rec["slots_live"] = [v for t, v in sampler.samples if lo <= t < hi]
    info["slots_live_mean"] = float(np.mean(rec["slots_live"])) \
        if rec["slots_live"] else None
    out["why_incorrect"], out["correct"] = why, not why
    return out
