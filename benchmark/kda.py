"""What the ``.video`` readers (``layer_metrics/*.video.py``) share
beyond ``longctx``'s scope arithmetic (a scope ANYWHERE in an
instruction's path; the window's decode rows and prompt tokens an
iteration from the ``serve/step`` events): the KDA scopes' rooflines,
the copies of the slot leaves, the group-limited router's counters.
Where the program has no such scope, counter or leaf (an older commit),
or the run no device plane (the CPU rehearsal), the answer is ``None``
and the reader leaves its metric out."""

from __future__ import annotations

from typing import Optional

from benchmark import longctx, program_trace

SCOPES = ("hetu.kda_conv", "hetu.kda_scan", "hetu.kda_update")


def roofline_pct(run, scope: str, need) -> Optional[float]:
    """``need(config, units) -> {"flops", "bytes"}`` an iteration (all
    KDA layers), over the seconds an iteration spends under ``scope``;
    ``units``: the window's ``{"decode": rows, "prefill": tokens}`` an
    iteration."""
    from benchmark import flops
    if run.peaks is None or "short_conv_kernel_size" not in run.config:
        return None
    took = longctx.seconds_per_step(run, scope)
    units = longctx.window_units(run)
    if not took or units is None:
        return None
    call = need(run.config, units)
    return 100.0 * flops.roofline_seconds(
        call["flops"], call["bytes"], run.peaks) / took


def state_copies_ms_per_step(run) -> Optional[float]:
    """Device self milliseconds an iteration in ``copy*`` instructions
    that move the KDA layers' slot leaves — a float32 result of whole
    layers of every slot's state (slots x H x d x d) or tail (slots x 3
    x 3 H d) — outside the KDA scopes; 0.0 where the program has such
    leaves and the slice none of them."""
    steps = program_trace._registered_scopes()
    t, cfg = run.trace, run.config
    if not steps or not t or not t.get("n_devices") \
            or "short_conv_kernel_size" not in cfg:
        return None
    host = program_trace.read(run)["host"]
    if host is None or not host["steps_in_slice"]:
        return None
    slots = cfg["serve"]["slots"]
    inner = cfg["num_attention_heads"] * cfg["head_dim"]
    layers = (slots * inner * cfg["head_dim"],
              slots * (cfg["short_conv_kernel_size"] - 1) * 3 * inner)
    scoped = {name: sc.path for by_name in steps.values()
              for name, sc in by_name.items()}
    s = 0.0
    for name, sec in t["op_seconds"].items():
        text = t["op_text"].get(name, "")
        if not name.startswith("copy") or not text.split(" = ", 1)[-1] \
                .startswith("f32["):
            continue
        n = program_trace._result_elements(text)
        if any(n >= layer and n % layer == 0 for layer in layers) \
                and not any(p in scoped.get(name, ()) for p in SCOPES):
            s += sec
    return 1e3 * s / host["steps_in_slice"]


def group_held_share() -> Optional[float]:
    """Tokens whose kept routing groups include the one held here, over
    the tokens routed (the process's totals of ``moe_group_held_total``
    / ``moe_group_tokens_total``: the runner hands the readers no
    window deltas of counters it does not know, and the share does not
    depend on the phase of the run)."""
    try:
        from hetu_tpu import telemetry
    except ImportError:
        return None
    reg = telemetry.get_registry()
    held, tokens = (reg.get(n) for n in ("moe_group_held_total",
                                         "moe_group_tokens_total"))
    if held is None or tokens is None or not tokens.value():
        return None
    return held.value() / tokens.value()
