"""What the ``.gdn`` readers (``layer_metrics/*.gdn.py``) share beyond
``longctx``'s scope arithmetic (a scope ANYWHERE in an instruction's
path; the window's decode rows and prompt tokens an iteration from the
``serve/step`` events): the Gated DeltaNet scopes' rooflines, the copies
of the two slot leaves, the gated attention layers' paged decode call.
Where the program has no such scope or leaf (an older commit), the
configuration is another architecture's, or the run has no device plane
(the CPU rehearsal), the answer is ``None`` and the reader leaves its
metric out."""

from __future__ import annotations

from typing import Optional

from benchmark import longctx, program_trace

SCOPES = ("hetu.gdn_conv", "hetu.gdn_scan", "hetu.gdn_update")


def _ours(run) -> bool:
    return "linear_num_value_heads" in run.config


def ms_per_step(run, scope: str) -> Optional[float]:
    """``longctx.ms_per_step`` on a run of this configuration."""
    return longctx.ms_per_step(run, scope) if _ours(run) else None


def roofline_pct(run, scope: str, need) -> Optional[float]:
    """``need(config, units) -> {"flops", "bytes"}`` an iteration (all
    Gated DeltaNet layers), over the seconds an iteration spends under
    ``scope``; ``units``: the window's ``{"decode": rows, "prefill":
    tokens}`` an iteration."""
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


def paged_decode_roofline_pct(run) -> Optional[float]:
    """The decode rows' paged call of ONE gated attention layer against
    its roofline (``flops_qwen3_next.paged_decode_call`` on the runner's
    mean ``live_pages``) over the device seconds one call under
    ``hetu.decode_lane`` -> ``hetu.paged_attn`` took."""
    from benchmark import flops, flops_qwen3_next
    r = run.records
    if run.peaks is None or not _ours(run) or not r.get("live_pages"):
        return None
    took = program_trace.kernel_seconds_per_call(
        run, "hetu.decode_lane>hetu.paged_attn")
    if not took:
        return None
    pages = sum(r["live_pages"]) / len(r["live_pages"])
    call = flops_qwen3_next.paged_decode_call(run.config, pages,
                                              r["block_size"])
    return 100.0 * flops.roofline_seconds(
        call["flops"], call["bytes"], run.peaks) / took


def state_copies_ms_per_step(run) -> Optional[float]:
    """Device self milliseconds an iteration in ``copy*`` instructions
    whose float32 result holds whole layers of every slot's state (slots
    x H x d x d elements) or tail (slots x (taps - 1) x channels) outside
    the Gated DeltaNet scopes (inside ``hetu.gdn_conv`` the gathered
    tails of a pack's runs are one layer's worth by design); 0.0 where
    the step holds the scopes and the slice no such copy."""
    from benchmark import flops_qwen3_next as f
    steps = program_trace._registered_scopes()
    t = run.trace
    if not steps or not t or not t.get("n_devices") or not _ours(run):
        return None
    host = program_trace.read(run)["host"]
    if host is None or not host["steps_in_slice"] or not any(
            sc.label in SCOPES for by_name in steps.values()
            for sc in by_name.values()):
        return None
    slots = run.config["serve"]["slots"]
    layers = (slots * f.state_bytes(run.config) // 4,
              slots * f.tail_bytes(run.config) // 4)
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
