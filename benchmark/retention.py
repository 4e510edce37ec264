"""What the ``.retention`` readers (``layer_metrics/*.retention.py``)
share beyond ``longctx``'s scope arithmetic (a scope ANYWHERE in an
instruction's path; the window's decode rows and prompt tokens an
iteration from the ``serve/step`` events): the two retention scopes'
rooflines, the copies of the state leaf, the live slots. Where the
program has no such scope, gauge or leaf (an older commit), or the run
no device plane (the CPU rehearsal), the answer is ``None`` and the
reader leaves its metric out."""

from __future__ import annotations

from typing import Optional

from benchmark import longctx, program_trace

SCOPES = ("hetu.retention_scan", "hetu.retention_update")


def _ours(run) -> bool:
    return run.config.get("model_type") == "brumby"


def roofline_pct(run, scope: str, need) -> Optional[float]:
    """``need(config, units) -> {"flops", "bytes"}`` an iteration (all
    layers), over the seconds an iteration spends under ``scope``;
    ``units``: the window's ``{"decode": rows, "prefill": tokens}`` an
    iteration."""
    from benchmark import flops
    if run.peaks is None or not _ours(run):
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
    whose float32 result holds at least one layer of every slot's state
    AS THE MODEL DEFINES IT (slots x 8 x 8,256 x 129 elements: a copy of
    the program's padded tiles is larger still), anywhere in the step;
    0.0 where the step holds the retention scopes and the slice no such
    copy."""
    from benchmark import flops_brumby
    steps = program_trace._registered_scopes()
    t = run.trace
    if not steps or not t or not t.get("n_devices") or not _ours(run):
        return None
    host = program_trace.read(run)["host"]
    if host is None or not host["steps_in_slice"]:
        return None
    if not any(sc.label in SCOPES for by_name in steps.values()
               for sc in by_name.values()):
        return None
    layer = run.config["serve"]["slots"] \
        * flops_brumby.state_bytes(run.config) // 4
    s = 0.0
    for name, sec in t["op_seconds"].items():
        text = t["op_text"].get(name, "")
        if name.startswith("copy") and text.split(" = ", 1)[-1] \
                .startswith("f32[") \
                and program_trace._result_elements(text) >= layer:
            s += sec
    return 1e3 * s / host["steps_in_slice"]


def slots_live_pct(run) -> Optional[float]:
    """The mean, over the window's one-second samples, of the share of
    slots that held a request (``serving_slots{state=live}`` over the
    slots; the runner's ``slots_live`` samples)."""
    got = run.records.get("slots_live")
    slots = run.records.get("slots")
    if not got or not slots:
        return None
    return 100.0 * sum(got) / (len(got) * slots)
