"""Runner of the ``serve`` kind: open-loop load on ``ServingServer`` ->
``ServingEngine`` over the loopback wire, the way users reach it.

The calls are ``chip_smoke.run_serve``'s (commit b30dce1): the engine
sized from the device's memory, ``ServingServer`` on a loopback port in
this process, the streaming channel of ``CoordinatorClient.
generate_stream`` (``rpc.stream.StreamChannel.stream_submit``, whose
sink is called on the channel's reader thread with every token event —
one sender thread and one reader thread carry all requests), the
one-executable audit and the near-tie rule against float32 logits.

The traffic file gives ``arrivals`` (``poisson``/``burst`` at
``rate_per_s``, or ``backlog`` with ``count``), ``prompt_len``,
``output_len``, ``ramp_s`` (arrivals start during set-up; the window's
requests are those DUE inside the window) and ``drain_s`` (after the
window the run waits this long for them to finish; one that has no
first token by then counts as failed, one still streaming as
unfinished).
"""

from __future__ import annotations

import socket
import threading
import time
import uuid

import numpy as np

from benchmark.model import dtype, gpt_config

#: greedy near-tie tolerance in float32 reference logits
#: (chip_smoke.NEAR_TIE_LOGIT_TOL: a bf16 arena rounds K/V to 2^-8
#: relative and the paged kernel sums 16-row pages, so logits of
#: magnitude ~10 move by up to ~1e-2 between the engine and a dense
#: float32 pass; a computation in a lower precision than the
#: configuration states moves them by more)
NEAR_TIE_LOGIT_TOL = 2e-2
#: finished requests of the window checked against the reference
REFERENCE_REQUESTS = 8


def _blank(rec: dict) -> dict:
    """A request's record before it is sent."""
    rec.update(sent=None, token_times=[], tokens=[], failed=None,
               result=None, done_t=None)
    return rec


class _Client:
    """All of a run's requests over one multiplexed stream channel."""

    def __init__(self, port: int):
        from hetu_tpu.rpc.stream import StreamChannel
        self.ch = StreamChannel(port)
        self.closing = False

    def submit(self, rec: dict) -> None:
        """Send ``rec`` now; its token times land in ``rec`` as the
        channel's reader thread receives them."""
        from hetu_tpu.serving.server import encode_payload

        def sink(fr: dict) -> None:
            if self.closing:    # our own close() tells every open
                return          # stream it is "lost": not a failure
            now = time.perf_counter()
            kind = fr.get("k")
            if kind == "ev":
                toks = fr.get("toks", [])
                rec["tokens"].extend(int(t) for t in toks)
                rec["token_times"].extend([now] * len(toks))
                if fr.get("done"):
                    rec["result"], rec["done_t"] = fr.get("result"), now
                elif fr.get("end"):
                    rec["failed"] = "stream ended (evicted/cancelled)"
            else:                       # drop / err / lost
                rec["failed"] = f"{kind}: {fr.get('msg', fr.get('reason'))}"

        payload = encode_payload({
            "prompt": [int(t) for t in rec["prompt"]],
            "max_tokens": rec["max_tokens"], "temperature": 0.0,
            "idem": uuid.uuid4().hex})
        rec["sent"] = time.perf_counter()
        try:
            self.ch.stream_submit(payload, sink=sink)
        except Exception as e:  # noqa: BLE001 — a refused request fails
            rec["failed"] = f"{type(e).__name__}: {e}"

    def close(self) -> None:
        self.closing = True
        self.ch.close()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _reference_check(config, params, recs, max_len: int,
                     max_out: int) -> list[str]:
    """Teacher-forced float32 reference over prompt + output of each
    sampled request: every emitted token must sit within the near-tie
    tolerance of the reference's top logit at its position. One jitted
    call at fixed shapes (rows padded to ``max_len``, outputs to
    ``max_out``), so that it compiles once per configuration."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import gpt2 as reference

    n = len(recs)
    ids = np.zeros((n, max_len), np.int32)
    start = np.zeros(n, np.int32)
    toks = np.zeros((n, max_out), np.int32)
    live = np.zeros((n, max_out), bool)
    for i, r in enumerate(recs):
        seq = np.concatenate([r["prompt"], np.asarray(r["tokens"])])
        ids[i, :len(seq)] = seq[:max_len]
        # the token emitted at output index j was predicted from
        # position prompt_len - 1 + j
        start[i] = len(r["prompt"]) - 1
        toks[i, :len(r["tokens"])] = r["tokens"]
        live[i, :len(r["tokens"])] = True

    @jax.jit
    def gaps(p, ids, start, toks):
        h = reference.hidden_states(
            p, ids, n_head=config["n_head"],
            eps=config["layer_norm_epsilon"])
        h = jnp.pad(h, ((0, 0), (0, max_out), (0, 0)))
        rows = jax.vmap(lambda x, s: jax.lax.dynamic_slice_in_dim(
            x, s, max_out))(h, start)
        with jax.default_matmul_precision("highest"):
            lg = rows @ jnp.asarray(p["wte"]["weight"], jnp.float32).T
        got = jnp.take_along_axis(lg, toks[..., None], -1)[..., 0]
        return lg.max(-1) - got

    gap = np.where(live, np.asarray(gaps(params, ids, start, toks)), 0.0)
    why = []
    for i, r in enumerate(recs):
        if not (gap[i] <= NEAR_TIE_LOGIT_TOL).all():
            j = int(np.argmax(gap[i]))
            why.append(
                f"request of {len(r['prompt'])} prompt tokens: output "
                f"token {j} is {float(gap[i, j])} below the float32 "
                f"reference's top logit (tolerance "
                f"{NEAR_TIE_LOGIT_TOL})")
    return why


def run(ctx) -> dict:
    import jax
    from benchmark import traffic
    from hetu_tpu import telemetry
    from hetu_tpu.engine import trace_counts
    from hetu_tpu.models import GPTLMHeadModel
    from hetu_tpu.ops.attention import kernel_fallbacks
    from hetu_tpu.serving import ServingEngine
    from hetu_tpu.serving.server import ServingServer

    mix, config = ctx.mix, ctx.config
    serve = config["serve"]
    cfg = gpt_config(config)
    dev = ctx.devices[0]
    telemetry.enable(True)
    reg = telemetry.get_registry()
    model = GPTLMHeadModel(cfg)
    # weights on the device, in the type they are served in, in one
    # jitted call from the seed
    params = jax.jit(
        lambda k: model.init(k, dtype=dtype(serve["param_dtype"])),
        out_shardings=jax.sharding.SingleDeviceSharding(dev))(
            jax.random.key(traffic.jax_seed(ctx.seed)))
    sizing = {"slots": serve["slots"],
              "kv_blocks": serve.get("kv_blocks")} \
        if "slots" in serve else \
        {"hbm_budget_bytes": serve["hbm_budget_share"]
         * dev.memory_stats()["bytes_limit"]}
    traces0 = trace_counts().get("serving_step", 0)
    t_build = time.perf_counter()
    eng = ServingEngine(
        model, params, max_len=serve["max_len"],
        prefill_chunk=serve["prefill_chunk"],
        cache_dtype=dtype(serve["cache_dtype"]),
        block_size=serve["block_size"],
        seed=traffic.jax_seed(ctx.seed), **sizing)

    horizon = mix["ramp_s"] + ctx.seconds
    reqs = traffic.serve_requests(
        mix, vocab_size=cfg.vocab_size, max_len=serve["max_len"],
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
        warm = [_blank({"prompt": rng.integers(1, cfg.vocab_size, n,
                                               dtype=np.int32),
                        "max_tokens": 4})
                for n in (serve["prefill_chunk"] + 8, 8)]
        t0 = time.perf_counter()
        for w in warm:
            cli.submit(w)
        while not all(w["done_t"] or w["failed"] for w in warm):
            if time.perf_counter() - t0 > 900:
                raise RuntimeError("warm-up did not finish")
            time.sleep(0.01)
        if any(w["failed"] for w in warm):
            raise RuntimeError(f"warm-up failed: "
                               f"{[w['failed'] for w in warm]}")
        warm_s = time.perf_counter() - t0
        traces_warm = trace_counts().get("serving_step", 0)

        # the open loop: ramp, window, drain — all on one clock
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

        def iters() -> float:
            return reg.counter("serving_attn_kernel_total").value(
                path=eng.attn_kernel)

        def live_pages() -> int:
            """Pages the decode lane has to read now: each request
            that has its first token and is not done holds
            ceil((prompt + received) / block_size) of them."""
            bs = serve["block_size"]
            return sum(-(-(len(r["prompt"]) + len(r["token_times"]))
                         // bs) for r in reqs
                       if r["token_times"] and not r["done_t"]
                       and not r["failed"])

        time.sleep(max(0.0, w_lo - time.perf_counter()))
        iters0 = iters()
        ctx.start_trace_slice(w_lo)
        kv_used, pages = [], []
        with ctx.span("window"):
            while True:
                kv_used.append(eng.blocks.blocks_in_use)
                pages.append(live_pages())
                left = w_hi - time.perf_counter()
                if left <= 0:
                    break
                time.sleep(min(1.0, left))
        iters1 = iters()
        t_iters = time.perf_counter()
        depth_at_end = eng.scheduler.depth
        backlog = mix["arrivals"]["process"] == "backlog"
        if backlog:
            # everything was offered at the start: the window's
            # requests are those that ended in it
            judged = [r for r in reqs if r["failed"] or (
                r["done_t"] and w_lo <= r["done_t"] < w_hi)]
        else:
            # open loop: the window's requests are those DUE in it
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
    ctx.finish_trace_slice()

    # a request whose first token had not come by the deadline failed;
    # one that is still streaming then (a long output due late in the
    # window) is unfinished, not failed: its gaps so far count
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
    pick = traffic.rng_for(ctx.seed, "reference").permutation(
        len(finished))[:REFERENCE_REQUESTS]
    t0 = time.perf_counter()
    if finished:
        why += _reference_check(
            config, params, [finished[i] for i in pick],
            serve["max_len"], int(mix["output_len"]["max"]))
    check_s = time.perf_counter() - t0

    timings = [(r["result"] or {}).get("timing", {}) for r in finished]
    judged_ids = {id(r) for r in judged}
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
        "kv_blocks_in_use": kv_used, "kv_blocks": eng.pool.n_blocks,
        "live_pages": pages, "block_size": serve["block_size"],
        "n_devices": len(ctx.devices),
    }
    from benchmark import stats
    on = [records["requests"][i] for i in records["judged"]]
    ttft = stats.ttft_samples(on, deadline)
    gaps = stats.token_gaps(on)
    info = {
        "n_requests": len(judged), "n_finished": len(finished),
        "failures": sorted({str(r["failed"]) for r in judged
                            if r["failed"]})[:5],
        "n_gaps": len(gaps), "n_offered": len(reqs),
        "ttft_mean_ms": 1e3 * sum(ttft) / max(len(ttft), 1),
        "ttft_p50_ms": 1e3 * (stats.median(ttft) or float("nan")),
        "ttft_p95_ms": 1e3 * (stats.percentile(ttft, 95)
                              or float("nan")),
        "gap_p50_ms": 1e3 * (stats.median(gaps) or float("nan")),
        "gap_p95_ms": 1e3 * (stats.percentile(gaps, 95)
                             or float("nan")),
        "tokens_in_window": stats.tokens_in_window(
            records["requests"], w_lo, w_hi),
        "engine_iterations": records["engine_iterations"],
        "preemptions": sum(t.get("preemptions", 0) for t in timings),
        "queue_depth_at_window_end": depth_at_end,
        "drain_s": t_drained - w_hi, "warmup_s": warm_s,
        "engine_build_and_requests_s": build_s,
        "reference_check_s": check_s,
        "arena_blocks": eng.pool.n_blocks, "slots": eng.pool.slots,
        "arena_bytes": eng.pool.nbytes(),
        "attn_kernel": eng.attn_kernel,
        "prefill_attn": eng.prefill_attn,
    }
    return {"correct": not why, "why_incorrect": why,
            "attempted": len(judged),
            "failed": sum(1 for r in judged if r["failed"]),
            "records": records, "info": info}
