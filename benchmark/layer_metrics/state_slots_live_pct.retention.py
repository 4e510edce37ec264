"""The share of slots that held a request, the mean over the window's
one-second samples of ``serving_slots{state=live}``: what
``kv_used_peak_pct`` is to an engine with an arena — here a slot's
state is the whole price of a request (``retention.slots_live_pct``)."""
NAME, UNIT = "state_slots_live_pct.retention", "%"
LAYER = "KV manager (serving/kv_pool.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import retention
    return retention.slots_live_pct(run)
