"""chip_smoke.py — the quickest proof that hetu_tpu still starts on the chip.

One process, one TPU: GPT-2 small at its published widths takes a few
optimizer steps through ``Trainer`` and answers a few requests through
``ServingServer`` -> ``ServingEngine``, each checked by the repo's own
means (loss curve, kernel presence in the compiled program, re-trace
audit, token identity against one-shot ``generate``). The script takes
the device JAX gives it and exits non-zero at once when that is not a
TPU. ``--chips 4`` runs only the sharded train path and its one-chip
comparison on a four-chip host.

The phases are plain functions so tests and rehearsals can call them at
``GPTConfig.tiny()`` on the CPU (``tests/test_chip_smoke.py``);
``main()`` itself never runs a phase without a chip. What the chip says
about time is printed as information — first run, not a benchmark.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import socket
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

PACKED_KEYS = ("input_ids", "labels", "positions", "segment_ids")
#: bf16 loss-curve agreement between a sharded and a one-device run.
#: ``tests/test_train_step.py`` holds fp32 strategy parity to 2e-4 and
#: the reduction-order-changing variants (microbatching, Megatron-SP)
#: to 2e-3; bf16 compute carries 2^-8 relative rounding per matmul, so
#: the curve is held to 2e-2 (relative and absolute, losses are ~10).
BF16_LOSS_TOL = 2e-2
#: greedy-token near-tie tolerance, in float32 reference logits: where
#: the engine and one-shot ``generate`` first pick different tokens,
#: both picks must sit within this of the reference's top logit. A bf16
#: arena rounds K/V to 2^-8 relative, and the paged kernel sums 16-row
#: pages where ``generate`` sums one dense row, so logits of magnitude
#: ~10 move by up to ~1e-2 between the two paths; the first chip run
#: (CHANGES.md, PR 21) saw one flip in 8 requests x 64 tokens at a
#: reference gap of 1.8e-3. Most requests must still match exactly.
NEAR_TIE_LOGIT_TOL = 2e-2


def say(**facts) -> None:
    print(json.dumps(facts, default=str), flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke: {what}")


def _epochs(cfg, *, seq_len: int, batch_rows: int, n_batches: int,
            seed: int):
    """The same ``n_batches`` seeded packed batches, epoch after epoch —
    as ``examples/pretrain.py`` builds its loader, on a corpus small
    enough that the loss has to fall."""
    from hetu_tpu.data import SyntheticLMDataset, build_data_loader
    ds = SyntheticLMDataset(cfg.vocab_size,
                            num_docs=2 * batch_rows * n_batches,
                            min_len=seq_len // 2, max_len=seq_len,
                            seed=seed)
    first = list(itertools.islice(build_data_loader(
        ds, seq_len=seq_len, batch_rows=batch_rows, pack=True,
        seed=seed), n_batches))
    check(len(first) == n_batches, "the seeded corpus is too small")
    return itertools.cycle(first)


def _trainer(cfg, strategy, *, steps: int, devices, seed: int):
    from hetu_tpu import optim
    from hetu_tpu.engine.trainer import Trainer, TrainerConfig
    from hetu_tpu.models import GPTLMHeadModel
    opt = optim.chain(optim.clip_by_global_norm(1.0),
                      optim.adamw(3e-4, weight_decay=0.01))
    return Trainer(
        GPTLMHeadModel(cfg), opt, strategy, devices=devices,
        config=TrainerConfig(total_steps=steps, log_every=1,
                             precision="bf16", seed=seed))


def _aot_step(trainer, strategy, batch_shape):
    """AOT-compile ``strategy``'s step through ``Trainer.precompile`` —
    the steps that follow dispatch this executable — and return it with
    its compile seconds."""
    from hetu_tpu.engine.train_step import _batch_key, abstract_batch
    res = trainer.precompile([strategy], batch_shape=batch_shape,
                             batch_keys=PACKED_KEYS,
                             block=True).results[-1]
    check(res.ok, f"train step did not compile: {res.error}")
    entry = trainer.cache.lookup(trainer._cache_key(strategy))
    exe = entry.aot[_batch_key(abstract_batch(
        entry.plan, batch_shape, keys=PACKED_KEYS))]
    return exe, res.seconds


def _train_losses(trainer, batches, steps: int, batch_tokens: int):
    from hetu_tpu.engine import trace_counts
    t0 = dict(trace_counts())
    hist = [r for r in trainer.train(batches, steps) if "loss" in r]
    losses = [float(r["loss"]) for r in hist]
    check(len(losses) == steps, f"{len(losses)} of {steps} steps ran")
    check(bool(np.all(np.isfinite(losses))), f"loss not finite: {losses}")
    check(trace_counts() == t0,
          f"a step re-traced: {t0} -> {trace_counts()}")
    step_ms = [1e3 * batch_tokens / r["tokens_per_sec"] for r in hist]
    return losses, step_ms


def run_train(cfg, *, seq_len: int, batch_rows: int, steps: int,
              expect_kernels: bool, seed: int = 0, devices=None):
    """A few optimizer steps through ``Trainer`` on one device. Returns
    (facts, trained params)."""
    from hetu_tpu.ops.attention import kernel_fallbacks
    from hetu_tpu.parallel.strategy import Strategy
    strategy = Strategy(remat="selective")
    devices = devices if devices is not None else jax.devices()[:1]
    trainer = _trainer(cfg, strategy, steps=steps, devices=devices,
                       seed=seed)
    exe, compile_s = _aot_step(trainer, strategy, (batch_rows, seq_len))
    flash = "tpu_custom_call" in exe.as_text()
    batches = _epochs(cfg, seq_len=seq_len, batch_rows=batch_rows,
                      n_batches=2, seed=seed)
    losses, step_ms = _train_losses(trainer, batches, steps,
                                    batch_rows * seq_len)
    check(losses[-1] < losses[0],
          f"loss did not fall on the seeded data: {losses}")
    if expect_kernels:
        check(flash, "no tpu_custom_call in the compiled train step")
        check(not kernel_fallbacks(),
              f"kernel fallbacks: {kernel_fallbacks()}")
    facts = {"phase": "train", "compile_s": round(compile_s, 2),
             "losses": [round(x, 4) for x in losses],
             "step_ms_median": float(np.median(step_ms[1:] or step_ms)),
             "flash_kernel_in_step": flash,
             "peak_bytes_in_use": _peak_bytes(devices[0])}
    params = trainer.state.params
    trainer.close()
    return facts, params


def _peak_bytes(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _reference_logits(model, params, tokens):
    """float32 one-shot logits at the last position of ``tokens``."""
    with jax.default_matmul_precision("highest"):
        h = model.hidden_states(
            params, jnp.asarray(tokens, jnp.int32)[None],
            attn_impl="reference")[0, -1].astype(jnp.float32)
        w = params["wte"]["weight"].astype(jnp.float32)
        return np.asarray(w @ h, np.float32)


def run_serve(cfg, params, *, max_len: int, prompt_lens, max_tokens: int,
              prefill_chunk: int, cache_dtype, expect_kernels: bool,
              hbm_budget_bytes=None, slots=None, seed: int = 0):
    """Greedy requests through ``ServingServer`` + ``CoordinatorClient``
    against one-shot ``generate`` on the same prompts."""
    from hetu_tpu import telemetry
    from hetu_tpu.engine import trace_counts
    from hetu_tpu.models import GPTLMHeadModel
    from hetu_tpu.models.generation import generate
    from hetu_tpu.ops.attention import kernel_fallbacks
    from hetu_tpu.rpc.client import CoordinatorClient
    from hetu_tpu.serving import ServingEngine
    from hetu_tpu.serving.server import ServingServer

    telemetry.enable(True)
    reg = telemetry.get_registry()
    model = GPTLMHeadModel(cfg)
    # the train -> serve handoff: ``params`` are the trainer's, as it
    # left them (typed with its mesh)
    traces0 = trace_counts().get("serving_step", 0)
    t0 = time.perf_counter()
    eng = ServingEngine(model, params, max_len=max_len,
                        prefill_chunk=prefill_chunk,
                        cache_dtype=cache_dtype, slots=slots,
                        hbm_budget_bytes=hbm_budget_bytes, seed=seed)
    build_s = time.perf_counter() - t0
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, cfg.vocab_size, (n,)).tolist()
               for n in prompt_lens]
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    srv = ServingServer(eng, port)
    srv.start()
    try:
        srv.wait_ready()
        cli = CoordinatorClient(port)
        # the first request compiles the one fused step
        t0 = time.perf_counter()
        first = cli.serving_generate(prompts[0], max_tokens=max_tokens)
        first_s = time.perf_counter() - t0
        steps0 = reg.counter("serving_attn_kernel_total").value(
            path=eng.attn_kernel)
        t0 = time.perf_counter()
        docs = [first] + [cli.serving_generate(p, max_tokens=max_tokens)
                          for p in prompts[1:]]
        rest_s = time.perf_counter() - t0
        cli.close()
    finally:
        srv.stop()
    decode_steps = reg.counter("serving_attn_kernel_total").value(
        path=eng.attn_kernel) - steps0
    check(all(d["status"] == "done" and len(d["tokens"]) == max_tokens
              for d in docs), "a request did not complete")
    n_compiles = trace_counts().get("serving_step", 0) - traces0
    check(n_compiles == 1,
          f"the fused step traced {n_compiles} times, not once")
    check(eng.step_executables() == 1,
          f"the fused step compiled {eng.step_executables()} times "
          f"under its one trace")

    diverged = []
    for p, d in zip(prompts, docs):
        want = np.asarray(generate(
            model, params, jnp.asarray(p, jnp.int32)[None],
            max_new_tokens=max_tokens, max_len=max_len,
            cache_dtype=cache_dtype)[0, len(p):]).tolist()
        got = list(d["tokens"])
        if got == want:
            continue
        # token identity is the contract; where reduction order on the
        # chip breaks it, the first differing pick must be a near-tie
        # in the float32 reference — anything else is a wrong kernel
        i = next(k for k in range(max_tokens) if got[k] != want[k])
        ref = _reference_logits(model, params, p + got[:i])
        gap = float(ref.max() - min(ref[got[i]], ref[want[i]]))
        diverged.append({"prompt_len": len(p), "at": i,
                         "ref_logit_gap": round(gap, 5)})
        check(gap <= NEAR_TIE_LOGIT_TOL,
              f"prompt of {len(p)} tokens diverges from generate() at "
              f"token {i} by {gap} in float32 reference logits")

    check(2 * len(diverged) <= len(docs),
          f"{len(diverged)} of {len(docs)} requests diverge from "
          f"generate(): {diverged}")
    paged_steps = reg.counter("serving_attn_kernel_total").value(
        path="paged")
    if expect_kernels:
        check(eng.attn_kernel == "paged" and paged_steps > 0,
              f"decode ran {eng.attn_kernel!r}, paged steps "
              f"{paged_steps}")
        check(eng.prefill_attn == "flash",
              f"prefill lane resolved to {eng.prefill_attn!r}")
        check(not kernel_fallbacks(),
              f"kernel fallbacks: {kernel_fallbacks()}")
    return {"phase": "serve", "cache_dtype": jnp.dtype(cache_dtype).name,
            "attn_kernel": eng.attn_kernel,
            "prefill_attn": eng.prefill_attn,
            "engine_build_s": round(build_s, 2),
            "first_request_s_with_compile": round(first_s, 2),
            "engine_iteration_ms": round(
                1e3 * rest_s / max(decode_steps, 1), 3),
            "requests": len(docs), "identical_to_generate":
                len(docs) - len(diverged), "near_ties": diverged,
            "arena_blocks": eng.pool.n_blocks, "slots": eng.pool.slots,
            "block_size": eng.pool.block_size,
            "arena_bytes": eng.pool.nbytes(),
            "peak_bytes_in_use": _peak_bytes(jax.devices()[0])}


def run_four_chip(cfg, *, seq_len: int, batch_rows: int, steps: int,
                  expect_kernels: bool, seed: int = 0):
    """The same seeded steps under ``Strategy(dp=2, tp=2)`` on four
    devices and under the one-device strategy, then one hot switch to
    ``Strategy(tp=4)`` with the next loss continuing the curve."""
    from hetu_tpu.parallel.strategy import Strategy
    devices = jax.devices()
    check(len(devices) >= 4, f"{len(devices)} devices, need 4")
    devices = devices[:4]
    one, _ = run_train(cfg, seq_len=seq_len, batch_rows=batch_rows,
                       steps=steps + 1, expect_kernels=expect_kernels,
                       seed=seed, devices=devices[:1])

    sharded = Strategy(dp=2, tp=2, remat="selective")
    trainer = _trainer(cfg, sharded, steps=steps, devices=devices,
                       seed=seed)
    exe, compile_s = _aot_step(trainer, sharded, (batch_rows, seq_len))
    hlo = exe.as_text()
    collectives = sorted(op for op in ("all-reduce", "all-gather",
                                       "reduce-scatter", "all-to-all",
                                       "collective-permute")
                         if f" {op}(" in hlo or f" {op}-start(" in hlo)
    flash = "tpu_custom_call" in hlo
    batches = _epochs(cfg, seq_len=seq_len, batch_rows=batch_rows,
                      n_batches=2, seed=seed)
    losses, step_ms = _train_losses(trainer, batches, steps,
                                    batch_rows * seq_len)
    check(bool(np.allclose(losses, one["losses"][:steps],
                           rtol=BF16_LOSS_TOL, atol=BF16_LOSS_TOL)),
          f"dp2 x tp2 losses {losses} leave the one-device curve "
          f"{one['losses']} by more than {BF16_LOSS_TOL}")
    check(bool(collectives), "no collective in the sharded step")
    if expect_kernels:
        check(flash, "no tpu_custom_call in the sharded train step")

    def homes(state):
        return sorted({d.id for leaf in jax.tree.leaves(state.params)
                       for d in leaf.sharding.device_set})

    def shard_homes(state):
        # devices holding DISTINCT shards of the widest-sharded leaf
        leaf = max(jax.tree.leaves(state.params),
                   key=lambda x: len({s.index for s in
                                      x.addressable_shards}))
        return len({s.index for s in leaf.addressable_shards})

    check(homes(trainer.state) == sorted(d.id for d in devices),
          f"parameters live on devices {homes(trainer.state)}")
    n_shards = shard_homes(trainer.state)
    check(n_shards >= 2, "no parameter is split under tp=2")

    # the system's signature: hot-switch the live state to another layout
    t0 = time.perf_counter()
    trainer.set_strategy(Strategy(tp=4, remat="selective"))
    check(homes(trainer.state) == sorted(d.id for d in devices),
          f"after the switch parameters live on {homes(trainer.state)}")
    check(shard_homes(trainer.state) == 4,
          "no parameter is split four ways under tp=4")
    after = float(trainer.train_step(next(batches))["loss"])
    switch_s = time.perf_counter() - t0
    check(bool(np.isclose(after, one["losses"][steps],
                          rtol=BF16_LOSS_TOL, atol=BF16_LOSS_TOL)),
          f"loss after the switch {after} leaves the one-device curve "
          f"{one['losses'][steps]}")
    param_devices = homes(trainer.state)
    trainer.close()
    return {"phase": "four_chip", "one_device": one,
            "dp2tp2": {"compile_s": round(compile_s, 2),
                       "losses": [round(x, 4) for x in losses],
                       "step_ms_median": float(np.median(step_ms[1:])),
                       "collectives": collectives,
                       "flash_kernel_in_step": flash,
                       "param_devices": param_devices,
                       "distinct_shards": n_shards},
            "switch_to_tp4": {"switch_and_first_step_s_with_compile":
                              round(switch_s, 2),
                              "loss_after": round(after, 4),
                              "one_device_loss": one["losses"][steps]},
            "loss_tolerance": BF16_LOSS_TOL}


def _native_cores() -> dict:
    """Which native cores (``utils/native.py``) built here; the Python
    fallbacks serve otherwise. All are off the hot path."""
    from hetu_tpu.utils.native import build_native
    csrc = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "hetu_tpu", "csrc")
    return {name: build_native(os.path.join(csrc, src), out,
                               shared=shared) is not None
            for name, src, out, shared in (
                ("bpe", "bpe.cpp", "libbpe.so", True),
                ("coordinator", "coordinator.cpp", "coordinator", False))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu" or len(jax.devices()) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX gives "
              f"{len(jax.devices())} x {dev.platform}", file=sys.stderr)
        return 1

    from hetu_tpu.engine.precompile import (
        enable_persistent_compilation_cache)
    from hetu_tpu.models import GPTConfig
    import jaxlib
    from importlib.metadata import PackageNotFoundError, version
    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "unknown"
    say(jax=jax.__version__, jaxlib=jaxlib.__version__, libtpu=libtpu,
        device_kind=dev.device_kind, devices=len(jax.devices()),
        bytes_limit=dev.memory_stats()["bytes_limit"],
        compile_cache=enable_persistent_compilation_cache(),
        native_cores=_native_cores(),
        serving_front_end="rpc/py_server.py (python)")

    cfg = GPTConfig.small()
    if args.chips == 4:
        say(**run_four_chip(cfg, seq_len=1024, batch_rows=8, steps=6,
                            expect_kernels=True, seed=args.seed))
    else:
        facts, params = run_train(cfg, seq_len=1024, batch_rows=8,
                                  steps=10, expect_kernels=True,
                                  seed=args.seed)
        say(**facts)
        # a real arena, sized from the device. The budget leaves room
        # for what the fused step allocates next to the arena today: a
        # second copy of it (the layer scan's stacked output), and for
        # int8 the scale leaves' tile-padded temporaries (PERF.md)
        limit = dev.memory_stats()["bytes_limit"]
        serve = dict(max_len=1024, max_tokens=64, prefill_chunk=256,
                     expect_kernels=True, seed=args.seed)
        say(**run_serve(cfg, params, cache_dtype=jnp.bfloat16,
                        hbm_budget_bytes=0.4 * limit,
                        prompt_lens=(32, 64, 96, 128, 192, 256, 384, 512),
                        **serve))
        say(**run_serve(cfg, params, cache_dtype=jnp.int8,
                        hbm_budget_bytes=0.2 * limit,
                        prompt_lens=(48, 320), **serve))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
