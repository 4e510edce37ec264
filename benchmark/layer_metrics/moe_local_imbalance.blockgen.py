"""Load imbalance of the experts held here over the window: the busiest
local expert's (token, choice) pairs over the mean
(``moe_local_expert_tokens{expert}``, the program's counter; 1.0 is
even). ``source`` in the manifest says ``host_clock``, as the
``.mixed`` entry's docstring explains."""
NAME, UNIT = "moe_local_imbalance.blockgen", "x"
LAYER = "expert layer (nn/moe.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    per = (run.records.get("moe") or {}).get("per_expert")
    if not per or not sum(per):
        return None
    return max(per) * len(per) / sum(per)
