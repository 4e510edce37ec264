"""Runner of the ``serve_arch`` kind: the ``serve`` runner's load, window,
judging and audits (``runners/serve.py``) for a model found BY NAME.

The configuration file's ``arch`` names ``<benchmark>/archs/<arch>.py``,
which gives ``build(config)`` (the program's model, with the interface
``ServingEngine`` uses), ``arena_row_elements(config)``,
``window(config)`` (or ``None``) and ``reference_rows(...)`` (the plain
float32 reference at chosen positions of one row): the next architecture
adds one such file and no runner. The engine is sized by the
configuration's explicit ``serve.slots`` / ``serve.kv_blocks``.

``correct`` is decided as in ``serve`` — requests judged, the backlog
not empty, one trace, one executable, the paged kernel and no fallback,
finished requests complete — plus the reference comparison of this
kind:

* 8 finished requests of the window are compared, the 2 LONGEST among
  them and the seed's draw of the others (the mix's
  ``reference_requests`` / ``reference_longest`` where it gives them: a
  count of the traffic file's, never of the program's speed; a mix of
  ONE length says ``reference_longest: 0``, where "the longest" would
  be the first offered on every seed, and all it compares is the
  seed's draw), teacher-forced on prompt + output, one row at a time (an 8k
  row beside the served weights; the arena is released first). The
  rows' one program is lowered in set-up and compiled on a host thread
  while the ramp runs (:class:`ReferenceRows`); nothing of it runs on
  the device before the window has closed;
* every emitted token must sit within ``LOGIT_TOL`` of the reference's
  top logit at its position — except where the reference's own top-k
  ROUTING is a near-tie on an expert held here (in some layer a held
  expert's router logit lies within ``ROUTE_TOL`` of the cut between
  chosen and not chosen, as a share of the token's spread of router
  logits): there rounding chooses another set of experts, a different
  and equally valid computation, so the position is counted
  (``route_near_ties``) and held to ``NEAR_TIE_TOL`` only — what
  another choice of experts moves the top logit by, far under what a
  wrong token costs (``median_logit_below_top``). The share of such
  positions is a property of the weights and the tokens, not of the
  program; a run in which more than ``ROUTE_SHARE_MAX`` of the
  positions are in that state compared too little closely and fails;
* the run says how many compared positions lay beyond the window; one
  in which none did says so on an information line.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from benchmark.model import dtype
from benchmark.runners.serve import _Client, _blank, _free_port

#: greedy near-tie tolerance in float32 reference logits. The program
#: computes with bf16 operands (float32 accumulation, results rounded to
#: bf16) and a bf16 arena: PERF.md section 6 (PR 26) gives the two
#: readings it lies between
LOGIT_TOL = 0.1
#: a held expert whose router logit is closer to the top-k's cut than
#: this share of the token's spread of router logits may be chosen or
#: not on rounding: 1.4x the largest margin (0.0109) at which a gap
#: over 0.03 was read (PERF.md section 6, PR 26)
ROUTE_TOL = 0.015
#: ... a token at such a position may lie this far below the top logit
#: (largest reading there 0.32; a wrong token lies ~5 below)
NEAR_TIE_TOL = 1.0
#: ... and at most this share of the compared positions may be such
#: (readings 25-46 % of a run's positions)
ROUTE_SHARE_MAX = 0.55
REFERENCE_REQUESTS = 8
LONGEST = 2
#: counters of the expert layer (``hetu_tpu/nn/moe.py``), read before
#: and after the window; absent on a program that has none
MOE_COUNTERS = ("moe_local_calls_total", "moe_local_assignments_total",
                "moe_local_experts_touched_total")


def load_arch(name: str):
    from benchmark import harness
    return harness._load_module(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "archs", f"{name}.py"))


class ReferenceRows:
    """``arch.reference_rows`` at the cell's fixed shapes (the row
    padded to ``max_len``, outputs to ``max_out``): ONE program, traced
    and lowered where this is built (Python: the caller's thread, in
    set-up), compiled on a host thread of its own once :meth:`start` is
    called (the compiler releases the interpreter; nothing touches the
    device), and called like the jitted function it stands for — the
    first call waits for the compile. The executable is what
    ``jax.jit`` would have compiled at its first call after the window:
    the same lowering, so the same logits to the bit and the same entry
    of the compilation cache."""

    def __init__(self, arch, config, params, max_len: int, max_out: int):
        import jax
        t0 = time.perf_counter()
        self._lowered = jax.jit(lambda p, ids, start: arch.reference_rows(
            config, p, ids, start, max_out)).lower(
                params, jax.ShapeDtypeStruct((max_len,), np.int32),
                jax.ShapeDtypeStruct((), np.int32))
        self.lower_s = time.perf_counter() - t0
        self.compile_s = self.compiled_at = None
        self._compiled = self._error = None
        self._thread = threading.Thread(
            target=self._compile, daemon=True,
            name="bench-reference-compile")

    def _compile(self) -> None:
        t0 = time.perf_counter()
        try:
            self._compiled = self._lowered.compile()
        except Exception as e:              # raised again by the caller
            self._error = e
        finally:
            self._lowered = None            # the module's text goes
            self.compiled_at = time.perf_counter()
            self.compile_s = self.compiled_at - t0

    def start(self) -> None:
        self._thread.start()

    def wait(self) -> None:
        """Until the compile has ended (started here if it was not);
        what it raised is raised here."""
        if self._thread.ident is None:
            self.start()
        self._thread.join()
        if self._error is not None:
            raise self._error

    def __call__(self, params, ids, start):
        self.wait()
        return self._compiled(params, ids, start)


def pick_reference(finished: list, seed: int, n: int, longest: int
                   ) -> list[int]:
    """Indices into ``finished`` of the requests to compare: the
    ``longest`` LONGEST prompts first (equal lengths: as offered), then
    a draw from ``seed`` of the others, ``n`` in all (fewer where fewer
    finished)."""
    from benchmark import traffic
    by_len = sorted(range(len(finished)),
                    key=lambda i: -len(finished[i]["prompt"]))
    pick = by_len[:min(longest, n)]
    rest = [i for i in traffic.rng_for(seed, "reference")
            .permutation(len(finished)) if i not in pick]
    return pick + rest[:n - len(pick)]


def _reference_check(arch, config, rows, params, recs, max_len: int
                     ) -> tuple[list[str], dict]:
    """See the module docstring. One call of ``rows`` (a
    :class:`ReferenceRows`) per request at its fixed shapes."""
    window = arch.window(config)
    why, compared, near, beyond, worst = [], 0, 0, 0, 0.0
    worst_near, largest, below = 0.0, [], []
    for r in recs:
        toks = np.asarray(r["tokens"], np.int64)
        n, p_len = len(toks), len(r["prompt"])
        ids = np.zeros(max_len, np.int32)
        seq = np.concatenate([r["prompt"], toks])[:max_len]
        ids[:len(seq)] = seq
        # the token emitted at output index j was predicted from
        # position prompt_len - 1 + j
        lg, margin = rows(params, ids, np.int32(p_len - 1))
        lg, margin = np.asarray(lg)[:n], np.asarray(margin)[:n]
        gap = lg.max(-1) - lg[np.arange(n), toks]
        below.append(lg.max(-1) - np.median(lg, -1))
        tie = margin < ROUTE_TOL
        compared += n
        near += int(tie.sum())
        if window is not None:
            beyond += int((p_len - 1 + np.arange(n) >= window).sum())
        if (~tie).any():
            worst = max(worst, float(gap[~tie].max()))
        if tie.any():
            worst_near = max(worst_near, float(gap[tie].max()))
        largest += [(float(gap[j]), float(margin[j]), p_len, int(j))
                    for j in np.argsort(-gap)[:3]]
        tol = np.where(tie, NEAR_TIE_TOL, LOGIT_TOL)
        bad = gap > tol
        if bad.any():
            j = int(np.argmax(np.where(bad, gap, -1.0)))
            why.append(
                f"request of {p_len} prompt tokens: output token {j} is "
                f"{float(gap[j])} below the float32 reference's top "
                f"logit (tolerance {float(tol[j])}; routing margin "
                f"{float(margin[j])})")
    if compared and near > ROUTE_SHARE_MAX * compared:
        why.append(f"{near} of {compared} compared positions are "
                   f"routing near-ties (at most {ROUTE_SHARE_MAX:.0%})")
    return why, {"compared_positions": compared,
                 "route_near_ties": near,
                 "compared_beyond_window": beyond,
                 "max_logit_gap": worst,
                 "max_logit_gap_at_near_ties": worst_near,
                 # what a token drawn at random would read as its gap
                 "median_logit_below_top": float(np.median(
                     np.concatenate(below))) if below else None,
                 # (gap, routing margin, prompt length, output index)
                 "largest_gaps": sorted(largest, reverse=True)[:6],
                 "compared_prompt_lens": [len(r["prompt"]) for r in recs]}


def run(ctx, reference_check=_reference_check, *,
        rows_ahead: bool = True) -> dict:
    """``reference_check(arch, config, rows, params, recs, max_len)`` is
    the comparison of the runner's kind (``serve_arch_ties`` and
    ``serve_arch_blocks`` hand in their own); ``rows`` is the ONE
    :class:`ReferenceRows` built here, or ``None`` with ``rows_ahead``
    false (a comparison whose rows have another signature compiles its
    own)."""
    t_run = time.perf_counter()
    import jax
    from benchmark import harness, traffic
    from hetu_tpu import telemetry
    from hetu_tpu.engine import trace_counts
    from hetu_tpu.ops.attention import kernel_fallbacks
    from hetu_tpu.serving import ServingEngine
    from hetu_tpu.serving.server import ServingServer

    mix, config = ctx.mix, ctx.config
    serve = config["serve"]
    arch = load_arch(config["arch"])
    dev = ctx.devices[0]
    telemetry.enable(True)
    reg = telemetry.get_registry()
    model = arch.build(config)
    vocab = config["vocab_size"]
    # weights on the device, in the type they are served in, in one
    # jitted call from the seed
    params = jax.jit(
        lambda k: model.init(k, dtype=dtype(serve["param_dtype"])),
        out_shardings=jax.sharding.SingleDeviceSharding(dev))(
            jax.random.key(traffic.jax_seed(ctx.seed)))
    traces0 = trace_counts().get("serving_step", 0)
    t_build = time.perf_counter()
    eng = ServingEngine(
        model, params, max_len=serve["max_len"],
        prefill_chunk=serve["prefill_chunk"],
        cache_dtype=dtype(serve["cache_dtype"]),
        block_size=serve["block_size"], slots=serve["slots"],
        kv_blocks=serve["kv_blocks"],
        seed=traffic.jax_seed(ctx.seed))

    horizon = mix["ramp_s"] + ctx.seconds
    reqs = traffic.serve_requests(
        mix, vocab_size=vocab, max_len=serve["max_len"],
        horizon_s=horizon, seed=ctx.seed)
    reqs = [_blank(r) for r in reqs if r["due"] < horizon]

    build_s = time.perf_counter() - t_build
    srv = ServingServer(eng, _free_port())
    srv.start()
    stop = threading.Event()
    sender = cli = None
    try:
        srv.wait_ready()
        cli = _Client(srv.coordinator.port)
        # warm-up: the one fused step compiles on the first request; a
        # prompt of more than one chunk and a few decode steps run
        # every lane of it before the clock starts
        rng = traffic.rng_for(ctx.seed, "warmup")
        warm = [_blank({"prompt": rng.integers(1, vocab, n,
                                               dtype=np.int32),
                        "max_tokens": 4})
                for n in (serve["prefill_chunk"] + 8, 8)]
        t0 = time.perf_counter()
        for w in warm:
            cli.submit(w)
        # the reference's rows: traced and lowered here, while the
        # engine's own thread compiles and warms the step (this thread
        # would only sleep), so no Python of it runs beside the serving
        # loop once the clock has started
        rows = ReferenceRows(
            arch, config, params, serve["max_len"],
            int(mix["output_len"]["max"])) if rows_ahead else None
        while not all(w["done_t"] or w["failed"] for w in warm):
            if time.perf_counter() - t0 > 900:
                raise RuntimeError("warm-up did not finish")
            time.sleep(0.01)
        if any(w["failed"] for w in warm):
            raise RuntimeError(f"warm-up failed: "
                               f"{[w['failed'] for w in warm]}")
        t_warm = time.perf_counter()
        warm_s = t_warm - t0
        traces_warm = trace_counts().get("serving_step", 0)

        origin = time.perf_counter()
        w_lo = origin + mix["ramp_s"]
        w_hi = w_lo + ctx.seconds
        deadline = w_hi + mix["drain_s"]
        for r in reqs:
            r["due"] += origin

        def send():
            with ctx.span("sender"):
                for r in reqs:
                    if stop.wait(max(0.0, r["due"]
                                     - time.perf_counter())):
                        return
                    cli.submit(r)

        sender = threading.Thread(target=send, daemon=True,
                                  name="bench-sender")
        sender.start()
        if rows is not None:
            rows.start()        # the compile, on the host, in the ramp

        def iters() -> float:
            return reg.counter("serving_attn_kernel_total").value(
                path=eng.attn_kernel)

        bs = serve["block_size"]
        window = arch.window(config)
        window_pages = None if window is None else window // bs + 1

        def live_pages() -> tuple[int, int]:
            """Pages the decode lane has to read now, in a full layer
            and in a window layer: each request that has its first
            token and is not done holds ceil((prompt + received) /
            block_size) of them, of which a window layer needs at most
            window / block_size + 1."""
            full = [-(-(len(r["prompt"]) + len(r["token_times"])) // bs)
                    for r in reqs if r["token_times"]
                    and not r["done_t"] and not r["failed"]]
            return sum(full), sum(min(p, window_pages or p)
                                  for p in full)

        def moe_counts() -> dict:
            got = {n: reg.counter(n).value() for n in MOE_COUNTERS}
            per = reg.counter("moe_local_expert_tokens")
            got["per_expert"] = [per.value(expert=str(e))
                                 for e in range(config["num_experts"])]
            return got

        time.sleep(max(0.0, w_lo - time.perf_counter()))
        iters0, moe0 = iters(), moe_counts()
        ctx.start_trace_slice(w_lo)
        kv_used, pages, pages_win, dead = [], [], [], []
        with ctx.span("window"):
            while True:
                kv_used.append(eng.blocks.blocks_in_use)
                full, win = live_pages()
                pages.append(full)
                pages_win.append(win)
                dead.append(reg.gauge("kv_window_dead_blocks").value())
                left = w_hi - time.perf_counter()
                if left <= 0:
                    break
                time.sleep(min(1.0, left))
        iters1, moe1 = iters(), moe_counts()
        t_iters = time.perf_counter()
        depth_at_end = eng.scheduler.depth
        backlog = mix["arrivals"]["process"] == "backlog"
        if backlog:
            judged = [r for r in reqs if r["failed"] or (
                r["done_t"] and w_lo <= r["done_t"] < w_hi)]
        else:
            judged = [r for r in reqs if w_lo <= r["due"] < w_hi]
            with ctx.span("drain"):
                while time.perf_counter() < deadline and not all(
                        r["done_t"] or r["failed"] for r in judged):
                    time.sleep(0.05)
        t_drained = time.perf_counter()
    finally:
        stop.set()
        if sender is not None:
            sender.join(timeout=10.0)
        if cli is not None:
            cli.close()
        srv.stop()
        # the server's drainers (daemon threads, one a stream) end once
        # they see the connection closed: a process that exits while
        # they still run can abort in the interpreter's shutdown. Five
        # seconds for all of them together, not each
        until = time.perf_counter() + 5.0
        for th in threading.enumerate():
            if th.name.startswith("stream-drain-"):
                th.join(timeout=max(0.0, until - time.perf_counter()))
    ctx.finish_trace_slice()

    for r in judged:
        if not r["token_times"] and not r["failed"]:
            r["failed"] = "no first token by the deadline"
    finished = [r for r in judged if r["done_t"]]

    why = []
    if not judged:
        why.append("no request belongs to the window")
    if backlog and depth_at_end == 0:
        why.append("the backlog emptied before the window ended")
    n_traces = trace_counts().get("serving_step", 0) - traces0
    if n_traces != 1 or traces_warm - traces0 != 1:
        why.append(f"the fused step traced {n_traces} times "
                   f"({traces_warm - traces0} in warm-up), not once")
    if eng.step_executables() != 1:
        why.append(f"the fused step compiled {eng.step_executables()} "
                   f"times under its one trace")
    if ctx.on_chip:
        if eng.attn_kernel != "paged":
            why.append(f"decode ran {eng.attn_kernel!r}, not the paged "
                       f"kernel")
        if kernel_fallbacks():
            why.append(f"kernel fallbacks: {kernel_fallbacks()}")
    short = [r for r in finished if len(r["tokens"]) != r["max_tokens"]]
    if short:
        why.append(f"{len(short)} finished requests have the wrong "
                   f"number of tokens")

    # the reference: the LONGEST finished requests, and a seeded draw
    # of the others; how many of each is the traffic file's to say
    pick = pick_reference(
        finished, ctx.seed,
        int(mix.get("reference_requests", REFERENCE_REQUESTS)),
        int(mix.get("reference_longest", LONGEST)))
    arena_bytes, n_blocks = eng.pool.nbytes(), eng.pool.n_blocks
    slots = eng.pool.slots
    eng.pool.caches = None          # the reference's rows need the room
    t0 = time.perf_counter()
    checked = {}
    if rows is not None:
        rows.wait()             # it is never cancelled, only reported
        margin = w_lo - rows.compiled_at
        if margin < min(10.0, mix["ramp_s"] / 2):
            harness.say(note=f"the reference's compile ended {margin} s "
                             f"before the window opened (under 10 s: it "
                             f"shares the window's host)")
    if finished:
        more, checked = reference_check(
            arch, config, rows, params, [finished[i] for i in pick],
            serve["max_len"])
        why += more
        if window is not None and not checked["compared_beyond_window"]:
            harness.say(note="no compared position lay beyond the "
                             f"window of {window}")
    check_s = time.perf_counter() - t0

    timings = [(r["result"] or {}).get("timing", {}) for r in finished]
    judged_ids = {id(r) for r in judged}
    moe = {n: moe1[n] - moe0[n] for n in MOE_COUNTERS}
    moe["per_expert"] = [b - a for a, b in zip(moe0["per_expert"],
                                               moe1["per_expert"])]
    records = {
        "setup_s": w_lo - ctx.t_process,
        "window": (w_lo, w_hi), "deadline": deadline,
        "requests": [{"due": r["due"], "sent": r["sent"],
                      "token_times": r["token_times"],
                      "failed": r["failed"],
                      "prompt_len": len(r["prompt"]),
                      "max_tokens": r["max_tokens"]} for r in reqs],
        "judged": [i for i, r in enumerate(reqs)
                   if id(r) in judged_ids],
        "timings": timings,
        "engine_iterations": iters1 - iters0,
        "engine_iterations_s": t_iters - w_lo,
        "kv_blocks_in_use": kv_used, "kv_blocks": n_blocks,
        "live_pages": pages, "live_pages_window": pages_win,
        "window_dead_blocks": dead,
        "block_size": bs, "moe": moe,
        "arena_row_elements": arch.arena_row_elements(config),
        "n_devices": len(ctx.devices),
    }
    from benchmark import stats
    info = {
        "n_requests": len(judged), "n_finished": len(finished),
        "failures": sorted({str(r["failed"]) for r in judged
                            if r["failed"]})[:5],
        "n_offered": len(reqs),
        "tokens_in_window": stats.tokens_in_window(
            records["requests"], w_lo, w_hi),
        "engine_iterations": records["engine_iterations"],
        "preemptions": sum(t.get("preemptions", 0) for t in timings),
        "queue_depth_at_window_end": depth_at_end,
        # where the run's wall goes (NEW_CELLS.md rule 7), in order:
        # ``process_to_runner_s`` (the harness's), the weights (the
        # model's description and the one jitted ``model.init`` call),
        # the engine and the requests, the warm-up, the ramp (from the
        # warm-up's end to the window's start), the window, the drain,
        # stopping the server and the tracer, the comparison
        "weights_init_s": t_build - t_run,
        "engine_build_and_requests_s": build_s, "warmup_s": warm_s,
        "ramp_s": w_lo - t_warm, "window_s": w_hi - w_lo,
        "drain_s": t_drained - w_hi, "teardown_s": t0 - t_drained,
        "reference_check_s": check_s, "reference": checked,
        # which of the window's finished requests (offered order)
        "reference_pick": [int(i) for i in pick[:len(finished)]],
        **({} if rows is None else {
            "reference_lower_s": rows.lower_s,
            "reference_compile_s": rows.compile_s,
            # it ended before the window opened, by this many seconds
            "reference_compile_overlapped": rows.compiled_at < w_lo,
            "reference_compile_to_window_s": w_lo - rows.compiled_at}),
        "arena_blocks": n_blocks, "slots": slots,
        "arena_bytes": arena_bytes,
        "kv_blocks_in_use_peak": max(kv_used),
        "attn_kernel": eng.attn_kernel,
        "prefill_attn": eng.prefill_attn,
        "moe_in_window": {n: moe[n] for n in MOE_COUNTERS},
    }
    return {"correct": not why, "why_incorrect": why,
            "attempted": len(judged),
            "failed": sum(1 for r in judged if r["failed"]),
            "records": records, "info": info}
