"""What the ``.longctx`` readers (``layer_metrics/*.longctx.py``) share:
the program's counters of the block-sparse and lightning layers, and the
device seconds under a named scope wherever it stands in an
instruction's scope path (the sparse read's kernel is the paged call,
whose own scope ``hetu.paged_attn`` is the innermost:
``scopes.seconds`` would give it to that).

What an iteration held is the WINDOW's: the decode rows and the prompt
tokens of the ``serve/step`` events that lie in the measured window
(the program's tracer, as ``iteration_account`` reads it — the ramp's
first wave decodes fewer rows an iteration than the window does). The
pages a row or a token chose and could see are rates of the PROCESS
(the counters' totals over the rows and tokens counted beside them:
the runner hands the readers no window deltas of counters it does not
know): every decode row of this traffic stands at position 32,000 to
32,255 and every request prefills the same 32,000 positions, so the
rates are the window's but for the warm-up's short requests (PERF.md
section 7 has the measured difference). Where the program has no such
counter, scope or span attribute (an older commit), or the run no
device plane (the CPU rehearsal), the answer is ``None`` and the reader
leaves its metric out."""

from __future__ import annotations

from typing import Optional

from benchmark import program_trace


def _rates() -> Optional[dict]:
    """Per lane, over the process: the pages a unit (a decode row, a
    prompt token) chose and could see, all sparse layers and kv
    heads."""
    try:
        from hetu_tpu import telemetry
    except ImportError:
        return None
    reg = telemetry.get_registry()

    def total(name, **labels):
        m = reg.get(name)
        return m.value(**labels) if m is not None else 0.0

    out = {}
    for lane, units in (
            ("decode", total("serving_decode_slot_steps_total")),
            ("prefill", total("serving_tokens_total", kind="prompt"))):
        visible = total("serving_sparse_pages_total", state="visible",
                        lane=lane)
        if not units or not visible:
            return None
        out[lane] = {
            "chosen": total("serving_sparse_pages_total", state="chosen",
                            lane=lane) / units,
            "visible": visible / units}
    return out


def window_units(run) -> Optional[dict]:
    """Decode rows and prompt tokens an engine iteration, means over
    the ``serve/step`` events wholly inside the run's window."""
    from benchmark import iteration_account
    window = run.records.get("window")
    try:
        from hetu_tpu import telemetry
        tracer = telemetry.get_tracer()
    except ImportError:
        return None
    if window is None:
        return None
    it = iteration_account.iterations(tracer.events(), tracer.epoch,
                                      *window)
    if it is None:
        return None
    return {"decode": float(it["active"].mean()),
            "prefill": float(it["prefill_tokens"].mean())}


def chosen_share() -> Optional[float]:
    """Pages the decode rows chose over the pages they could see."""
    r = _rates()
    return None if r is None else \
        r["decode"]["chosen"] / r["decode"]["visible"]


def counts(run) -> Optional[dict]:
    """Per engine iteration of the window and lane: its rows (decode)
    or tokens (prefill), and the pages they chose and could see."""
    rates, units = _rates(), window_units(run)
    if rates is None or units is None:
        return None
    return {lane: {"units": units[lane],
                   "chosen": units[lane] * rates[lane]["chosen"],
                   "visible": units[lane] * rates[lane]["visible"]}
            for lane in rates}


def path_seconds(run, scope: str) -> Optional[float]:
    """Device self seconds, in the traced slice, of the instructions
    that have ``scope`` ANYWHERE in their scope path."""
    steps = program_trace._registered_scopes()
    t = run.trace
    if not steps or not t or not t.get("n_devices"):
        return None
    owners: dict[str, list] = {}
    for by_name in steps.values():
        for name, sc in by_name.items():
            owners.setdefault(name, []).append(sc)
    s, seen = 0.0, False
    for name, sec in t["op_seconds"].items():
        found = owners.get(name, [])
        if len(found) == 1 and scope in found[0].path:
            s, seen = s + sec, True
    return s if seen else None


def seconds_per_step(run, scope: str) -> Optional[float]:
    s = path_seconds(run, scope)
    host = program_trace.read(run)["host"]
    if s is None or host is None or not host["steps_in_slice"]:
        return None
    return s / host["steps_in_slice"]


def ms_per_step(run, scope: str) -> Optional[float]:
    s = seconds_per_step(run, scope)
    return None if s is None else 1e3 * s


def roofline_pct(run, scope: str, need) -> Optional[float]:
    """``need(config, counts) -> {"flops", "bytes"}`` an iteration,
    over the seconds an iteration spends under ``scope``."""
    from benchmark import flops
    if run.peaks is None or "lightning_nh" not in run.config:
        return None
    took, c = seconds_per_step(run, scope), counts(run)
    if not took or c is None:
        return None
    call = need(run.config, c)
    return 100.0 * flops.roofline_seconds(
        call["flops"], call["bytes"], run.peaks) / took


def state_copies_ms_per_step(run) -> Optional[float]:
    """Device self milliseconds an iteration in ``copy*`` instructions
    that move the lightning layers' slot states (a float32 result of
    whole layers of every slot's state) outside the lightning scopes;
    0.0 where the program has such states and the slice none of them."""
    steps = program_trace._registered_scopes()
    t = run.trace
    if not steps or not t or not t.get("n_devices") \
            or "lightning_nh" not in run.config:
        return None
    host = program_trace.read(run)["host"]
    if host is None or not host["steps_in_slice"]:
        return None
    layer = run.config["serve"]["slots"] * run.config["lightning_nh"] \
        * run.config["lightning_head_dim"] ** 2
    inside = ("hetu.linear_scan", "hetu.linear_update")
    scoped = {name: sc.path for by_name in steps.values()
              for name, sc in by_name.items()}
    s = 0.0
    for name, sec in t["op_seconds"].items():
        text = t["op_text"].get(name, "")
        if not name.startswith("copy") or not text.split(" = ", 1)[-1] \
                .startswith("f32["):
            continue
        n = program_trace._result_elements(text)
        if n >= layer and n % layer == 0 and not any(
                p in scoped.get(name, ()) for p in inside):
            s += sec
    return 1e3 * s / host["steps_in_slice"]
