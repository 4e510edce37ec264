"""Load imbalance of the 64 held experts over the window: the busiest
one's (token, choice) pairs over the mean
(``moe_local_expert_tokens{expert}``; 1.0 is even). The selection bias
is drawn, not learned, so it balances nothing here."""
NAME, UNIT = "moe_local_imbalance.video", "x"
LAYER = "expert layer (nn/moe.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    per = (run.records.get("moe") or {}).get("per_expert")
    if not per or not sum(per):
        return None
    return max(per) * len(per) / sum(per)
