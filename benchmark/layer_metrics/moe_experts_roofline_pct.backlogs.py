"""The routed-expert layer's share of its roofline where the experts
are ``flops_mla_moe``'s (DeepSeek-V3-shaped: Kimi, Ling): the least
time the chip could take for one call — the held experts that got a
token read once, 6 x hidden x width operations per (token, choice)
pair routed here (``flops_mla_moe.moe_experts_call`` on the window's
means of the program's counters ``moe_local_experts_touched_total``
and ``moe_local_assignments_total`` per ``moe_local_calls_total``) —
over the device seconds one call took under ``hetu.moe_experts`` (the
scope's seconds over a third of its grouped-matmul calls: a layer call
makes three). ``.mixed`` and ``.blockgen`` count with other
configurations' functions and keep readers of their own."""
NAME, UNIT = "moe_experts_roofline_pct.backlogs", "%"
LAYER = "expert layer (nn/moe.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import flops, flops_mla_moe, scopes
    moe = run.records.get("moe")
    if run.peaks is None or not moe \
            or not moe.get("moe_local_calls_total"):
        return None
    took = scopes.seconds(run, "hetu.moe_experts")
    n = scopes.calls(run, "hetu.moe_experts", "custom-call")
    if not took or not n:
        return None
    calls = moe["moe_local_calls_total"]
    call = flops_mla_moe.moe_experts_call(
        run.config, moe["moe_local_assignments_total"] / calls,
        moe["moe_local_experts_touched_total"] / calls)
    need = flops.roofline_seconds(call["flops"], call["bytes"],
                                  run.peaks)
    return 100.0 * need / (took / (n / 3.0))
