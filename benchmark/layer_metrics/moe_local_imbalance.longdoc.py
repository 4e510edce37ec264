"""Load imbalance of the routed experts over the window: the busiest
expert's (token, choice) pairs over the mean
(``moe_local_expert_tokens{expert}``, the program's counter, sampled on
the benchmark's clock; 1.0 is even). The selection bias is drawn, not
learned, so it does not balance anything here."""
NAME, UNIT = "moe_local_imbalance.longdoc", "x"
LAYER = "expert layer (nn/moe.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    per = (run.records.get("moe") or {}).get("per_expert")
    if not per or not sum(per):
        return None
    return max(per) * len(per) / sum(per)
