"""Runner of the ``serve_arch_ssm`` kind: ``serve_arch_ties`` — so
``serve_arch``'s load, window, judging and audits, and the comparison of
the emitted tokens at the arch file's limits — for a model whose
selective-scan layers keep a STATE a slot in a stated precision, plus
the one comparison that can see that precision.

Why a kind of its own. The emitted tokens do not reliably resolve the
state: against a reference whose state is kept in ``bfloat16`` the
program's 256 tokens read 1.14 below the top logit on one seed and 0.26
on another, under the arch's ``LOGIT_TOL`` of 0.4 (PERF.md section 6, PR
55, call 9), so a program that halved its state could read ``correct:
true``. This kind therefore READS the state:

* once the window's comparison is done, ONE of its compared requests'
  prompts goes through the program again, offline — an engine of the
  cell's own shapes (the step's executable comes from the compilation
  cache), chunked prefill and then the mix's longest output decoded —
  and the slot's state leaf is read where the last chunk leaves it and
  where the last decoded token leaves it (``arch.program_states``);
* the reference's rows (the SAME compiled program the tokens were
  compared with: ``arch.reference_rows`` gives the states after those
  two positions beside the logits) run on the probe's prompt and
  tokens;
* ``arch.state_gap`` reads how far the two lie apart and
  ``arch.state_tol(config)`` is the limit, from two readings as every
  limit.
"""

from __future__ import annotations

import functools

import numpy as np

from benchmark.model import dtype
from benchmark.runners import serve_arch, serve_arch_ties


def probe(arch, eng, prompts, max_tokens: int) -> list[dict]:
    """``prompts`` through ``eng`` (a fresh engine, at most its slots
    of them): a request -> ``{"prompt", "tokens", "states"}``, ``states
    (Mamba layers, 2, N, D)`` the slot's state after the prompt's last
    chunk and after the last token the program read."""
    from hetu_tpu.serving.scheduler import SamplingParams
    reqs = [eng.submit(p, SamplingParams(max_tokens=max_tokens))
            for p in prompts]
    slot, first, last = {}, {}, {}
    while eng.has_work():
        eng.step()
        for i, r in enumerate(reqs):
            if r.slot is not None:
                slot[i] = r.slot
            if r.tokens and i not in first:
                first[i] = arch.program_states(eng.pool.caches, slot[i])
            if len(r.tokens) == max_tokens and i not in last:
                last[i] = arch.program_states(eng.pool.caches, slot[i])
    return [{"prompt": np.asarray(r.prompt), "tokens": list(r.tokens),
             "states": np.stack([first[i], last[i]], 1)}
            for i, r in enumerate(reqs)]


def state_check(arch, config, rows, params, probed: list, max_len: int
                ) -> tuple[list[str], dict]:
    """The probed requests' states against the reference's rows on the
    same prompt and tokens: what fails, and what was read."""
    why, read, tol = [], [], arch.state_tol(config)
    for r in probed:
        p_len = len(r["prompt"])
        ids = np.zeros(max_len, np.int32)
        seq = np.concatenate([r["prompt"], r["tokens"]])[:max_len]
        ids[:len(seq)] = seq
        want = np.asarray(rows(params, ids, np.int32(p_len - 1))[2])
        gap = arch.state_gap(config, params, r["states"], want)
        read.append(gap)
        if gap["gap"] > tol:
            why.append(
                f"request of {p_len} prompt tokens: the slot's state lies "
                f"{gap['gap']} from the float32 recurrence's (tolerance "
                f"{tol})")
    return why, {"state_gap": max((g["gap"] for g in read), default=0.0),
                 "state_tolerance": tol, "state_readings": read}


def reference_check(limits: dict, max_out: int, arch, config, rows, params,
                    recs, max_len: int) -> tuple[list[str], dict]:
    """``serve_arch_ties.reference_check`` on the window's requests,
    then the first of them again through an engine of the cell's shapes
    for its state (the module docstring)."""
    from hetu_tpu.serving import ServingEngine
    why, seen = serve_arch_ties.reference_check(
        limits, arch, config, lambda *a: rows(*a)[:2], params, recs,
        max_len)
    serve = config["serve"]
    eng = ServingEngine(
        arch.build(config), params, max_len=serve["max_len"],
        prefill_chunk=serve["prefill_chunk"],
        cache_dtype=dtype(serve["cache_dtype"]),
        block_size=serve["block_size"], slots=serve["slots"],
        kv_blocks=serve["kv_blocks"])
    probed = probe(arch, eng, [recs[0]["prompt"]], max_out)
    eng.pool.caches = None          # the reference's rows need the room
    more, state = state_check(arch, config, rows, params, probed, max_len)
    return why + more, {**seen, **state}


def run(ctx) -> dict:
    arch = serve_arch.load_arch(ctx.config["arch"])
    limits = {name: float(getattr(arch, name))
              for name in serve_arch_ties.LIMITS}
    return serve_arch.run(ctx, functools.partial(
        reference_check, limits, int(ctx.mix["output_len"]["max"])))
