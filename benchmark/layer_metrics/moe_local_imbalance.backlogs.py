"""Load imbalance of the experts held here over the window: the busiest
local expert's (token, choice) pairs over the mean
(``moe_local_expert_tokens{expert}``, the program's counter, sampled
on the benchmark's clock: ``source`` says ``host_clock``; 1.0 is
even). Where a selection bias is drawn, not learned, it balances
nothing here."""
NAME, UNIT = "moe_local_imbalance.backlogs", "x"
LAYER = "expert layer (nn/moe.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    per = (run.records.get("moe") or {}).get("per_expert")
    if not per or not sum(per):
        return None
    return max(per) * len(per) / sum(per)
