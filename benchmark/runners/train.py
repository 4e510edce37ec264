"""Runner of the ``train`` kind: a timed window of optimizer steps
through ``Trainer``, the way ``examples/pretrain.py`` trains.

The calls are ``chip_smoke.py``'s (``_trainer``, ``_aot_step``,
``_train_losses``, ``_epochs`` at commit b30dce1), copied so that later
PRs may change the program and the smoke but not the yardstick; around
them a seeded corpus that does not repeat in a window, a window that
ends by the clock, and the records the metrics read.

The traffic file gives ``seq_len``, ``batch_rows``, ``doc_len``,
``corpus_docs``, ``optimizer``, ``warmup_steps``, ``strategy`` (keyword
arguments of ``Strategy``) and optionally ``switch``: ``{"strategies":
[...], "every_steps": n}`` — every strategy is compiled in set-up
through ``Trainer.precompile`` and the trainer hot-switches round-robin
through base, strategies[0], ... every n steps.
"""

from __future__ import annotations

import functools
import itertools
import time

import numpy as np

from benchmark.model import gpt_config

PACKED_KEYS = ("input_ids", "labels", "positions", "segment_ids")
#: bf16 loss against the float32 reference (chip_smoke.BF16_LOSS_TOL:
#: bf16 compute carries 2^-8 relative rounding per matmul; losses ~10)
BF16_LOSS_TOL = 2e-2
#: the float32 reference runs over the whole first batch, this many
#: rows a call (its logits are rows x seq x vocab in float32)
REFERENCE_CHUNK = 8
IGNORE = -100


def _optimizer(spec: dict):
    from hetu_tpu import optim
    if spec["name"] != "adamw":
        raise ValueError(f"unknown optimizer {spec['name']!r}")
    return optim.chain(
        optim.clip_by_global_norm(spec["clip_global_norm"]),
        optim.adamw(spec["lr"], weight_decay=spec["weight_decay"]))


def _batches(ctx, vocab_size: int):
    """Packed batches without end: the seeded corpus, reshuffled every
    epoch (``corpus_docs`` is sized so that a window never reaches the
    second)."""
    from benchmark.traffic import Corpus, jax_seed
    from hetu_tpu.data import build_data_loader
    mix = ctx.mix
    corpus = Corpus(mix, vocab_size=vocab_size, seed=ctx.seed)
    for epoch in itertools.count():
        yield from build_data_loader(
            corpus, seq_len=mix["seq_len"], batch_rows=mix["batch_rows"],
            pack=True, seed=jax_seed(ctx.seed) + epoch)


def _pairs_per_row(batch) -> float:
    """Mean over rows of the (query, key) pairs inside documents: the
    sum over a row's documents of L(L+1)/2. The padding tail is a
    segment of its own without a label and is not counted."""
    seg = batch["segment_ids"]
    lab = batch["labels"] != IGNORE
    total = 0.0
    for r in range(seg.shape[0]):
        n = np.bincount(seg[r]).astype(np.float64)
        is_doc = np.bincount(seg[r], weights=lab[r]) > 0
        total += float((n * (n + 1) / 2)[is_doc].sum())
    return total / seg.shape[0]


class _Feed:
    """Wraps the loader: times every ``next`` (the input pipeline's
    cost per batch), counts what each batch holds, and ends at a
    deadline so that ``Trainer.train`` returns by the clock."""

    def __init__(self, it, span):
        self.it, self.span = it, span
        self.deadline = None           # perf_counter; None = no limit
        self.max_batches = None
        self.wait_s: list[float] = []
        self.labelled: list[int] = []
        self.pairs: list[float] = []

    def __iter__(self):
        return self

    def __next__(self):
        if self.deadline is not None \
                and time.perf_counter() >= self.deadline:
            raise StopIteration
        if self.max_batches is not None:
            if self.max_batches <= 0:
                raise StopIteration
            self.max_batches -= 1
        t0 = time.perf_counter()
        with self.span("next_batch"):
            b = next(self.it)
        self.wait_s.append(time.perf_counter() - t0)
        self.labelled.append(int((b["labels"] != IGNORE).sum()))
        self.pairs.append(_pairs_per_row(b))
        return b

    def take(self, n: int):
        """The next ``n`` batches, then stop (warm-up, switch legs)."""
        self.max_batches = n
        return self


def _train_leg(trainer, feed, steps: int, raw_tokens: int):
    """One ``Trainer.train`` call -> per-step (finish time on the
    perf_counter clock, step seconds, loss). ``Trainer`` logs every
    step (``log_every=1``) after the blocking fetch of its loss: the
    record's ``elapsed_s`` is the trainer's clock at that moment and
    ``tokens_per_sec`` the raw tokens over the time since the previous
    one, so the first record's pair places the trainer's clock on
    ours."""
    t_call = time.perf_counter()
    hist = [r for r in trainer.train(feed, steps) if "loss" in r]
    out = []
    if hist:
        first_s = raw_tokens / hist[0]["tokens_per_sec"]
        offset = t_call - (hist[0]["elapsed_s"] - first_s)
        for r in hist:
            out.append((offset + r["elapsed_s"],
                        raw_tokens / r["tokens_per_sec"],
                        float(r["loss"])))
    return out


def run(ctx) -> dict:
    import jax
    from benchmark.reference import gpt2 as reference
    from benchmark.traffic import jax_seed
    from hetu_tpu.engine import trace_counts
    from hetu_tpu.engine.train_step import _batch_key, abstract_batch
    from hetu_tpu.engine.trainer import Trainer, TrainerConfig
    from hetu_tpu.models import GPTLMHeadModel
    from hetu_tpu.ops.attention import kernel_fallbacks
    from hetu_tpu.parallel.strategy import Strategy

    mix, config = ctx.mix, ctx.config
    cfg = gpt_config(config)
    shape = (mix["batch_rows"], mix["seq_len"])
    raw_tokens = shape[0] * shape[1]
    base = Strategy(**mix["strategy"])
    switch = mix.get("switch")
    legs = [base] + [Strategy(**s) for s in
                     (switch["strategies"] if switch else [])]

    trainer = Trainer(
        GPTLMHeadModel(cfg), _optimizer(mix["optimizer"]), base,
        devices=ctx.devices,
        config=TrainerConfig(total_steps=10**9, log_every=1,
                             precision=config["train"]["precision"],
                             seed=jax_seed(ctx.seed)))
    t0 = time.perf_counter()
    handle = trainer.precompile(legs, batch_shape=shape,
                                batch_keys=PACKED_KEYS, block=True)
    bad = [r.error for r in handle.results if not r.ok]
    if bad:
        raise RuntimeError(f"a train step did not compile: {bad}")
    compile_s = time.perf_counter() - t0
    entry = trainer.cache.lookup(trainer._cache_key(base))
    exe = entry.aot[_batch_key(abstract_batch(entry.plan, shape,
                                              keys=PACKED_KEYS))]
    flash = "tpu_custom_call" in exe.as_text()
    mem = exe.memory_analysis()
    program_peak = None
    if mem is not None:
        program_peak = int(mem.argument_size_in_bytes
                           + mem.output_size_in_bytes
                           - mem.alias_size_in_bytes
                           + mem.temp_size_in_bytes)

    t_data = time.perf_counter()
    feed = _Feed(_batches(ctx, cfg.vocab_size), ctx.span)
    trainer.initialize()
    # the step donates its state: keep the initial parameters for the
    # reference, and the batch they are compared on
    init_params = jax.tree.map(lambda x: x.copy(), trainer.state.params)

    # warm-up: every leg's executable runs before the window opens
    first_batch = None

    def remember(it):
        nonlocal first_batch
        for b in it:
            if first_batch is None:
                first_batch = {k: b[k].copy() for k in PACKED_KEYS}
            yield b

    warm = []
    for leg in legs:
        if leg is not trainer.strategy:
            trainer.set_strategy(leg)
        warm += _train_leg(trainer, remember(feed.take(
            mix["warmup_steps"])), mix["warmup_steps"], raw_tokens)
    if trainer.strategy is not base:
        trainer.set_strategy(base)
    n_warm = len(feed.labelled)
    traces0 = dict(trace_counts())

    # the measured window
    t_start = time.perf_counter()
    t_end = t_start + ctx.seconds
    ctx.start_trace_slice(t_start)
    steps, switch_s = [], []
    feed.deadline = t_end
    with ctx.span("train_window"):
        if switch is None:
            feed.max_batches = None
            steps = _train_leg(trainer, feed, 10**9, raw_tokens)
        else:
            for leg in itertools.cycle(legs[1:] + legs[:1]):
                steps += _train_leg(
                    trainer, feed.take(switch["every_steps"]),
                    switch["every_steps"], raw_tokens)
                if time.perf_counter() >= t_end:
                    break
                ts = time.perf_counter()
                with ctx.span("set_strategy"):
                    trainer.set_strategy(leg)
                switch_s.append(time.perf_counter() - ts)
    traces1 = dict(trace_counts())
    ctx.finish_trace_slice()

    labelled = feed.labelled[n_warm:n_warm + len(steps)]
    pairs = feed.pairs[n_warm:n_warm + len(steps)]
    done = [(end, s, lab, pr) for (end, s, _), lab, pr
            in zip(steps, labelled, pairs) if end <= t_end]
    # the rate is taken from the window's start to the last step that
    # finished in it: all the work, and all the time it took
    window_s = (done[-1][0] - t_start) if done else float("nan")
    losses = [l for _, _, l in warm + steps]

    why = []
    if not done:
        why.append("no step finished in the window")
    if not all(np.isfinite(losses)):
        why.append(f"a loss is not finite: {losses}")
    if traces1 != traces0:
        why.append(f"a step re-traced in the window: {traces0} -> "
                   f"{traces1}")
    terms = jax.jit(functools.partial(
        reference.loss_terms, n_head=config["n_head"],
        eps=config["layer_norm_epsilon"]))
    total = count = 0.0
    for i in range(0, shape[0], REFERENCE_CHUNK):
        rows = {k: v[i:i + REFERENCE_CHUNK]
                for k, v in first_batch.items()}
        s, n = terms(init_params, rows["input_ids"], rows["labels"],
                     positions=rows["positions"],
                     segment_ids=rows["segment_ids"])
        total, count = total + float(s), count + float(n)
    ref_loss = total / count
    if not abs(warm[0][2] - ref_loss) <= BF16_LOSS_TOL:
        why.append(f"first loss {warm[0][2]} leaves the float32 "
                   f"reference {ref_loss} by more than {BF16_LOSS_TOL}")
    if ctx.on_chip:
        if not flash:
            why.append("no tpu_custom_call in the compiled train step")
        if kernel_fallbacks():
            why.append(f"kernel fallbacks: {kernel_fallbacks()}")
    trainer.close()

    records = {
        "setup_s": t_start - ctx.t_process,
        "window_s": window_s,
        "step_s": [s for _, s, _, _ in done],
        "step_labelled_tokens": [lab for _, _, lab, _ in done],
        "step_pairs_per_row": [pr for _, _, _, pr in done],
        "data_wait_s": feed.wait_s[n_warm:n_warm + len(steps)],
        "switch_s": switch_s,
        "batch_rows": shape[0], "seq_len": shape[1],
        "n_devices": len(ctx.devices),
    }
    info = {"n_steps": len(done), "compile_s": compile_s,
            "init_and_warmup_s": t_start - t_data,
            "step_ms_median": 1e3 * float(np.median(records["step_s"]))
            if done else None,
            "labelled_share": sum(records["step_labelled_tokens"])
            / max(1, raw_tokens * len(done)),
            "first_loss": warm[0][2], "reference_loss": ref_loss,
            "last_loss": losses[-1],
            "flash_kernel_in_step": flash,
            "program_peak_bytes": program_peak,
            "n_switches": len(switch_s)}
    return {"correct": not why, "why_incorrect": why,
            "attempted": len(done),
            "failed": sum(1 for e, _, l in steps
                          if e <= t_end and not np.isfinite(l)),
            "records": records, "info": info,
            "program_peak_bytes": program_peak}
