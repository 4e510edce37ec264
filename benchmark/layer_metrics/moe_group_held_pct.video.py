"""Tokens whose four kept routing groups include the one held on this
chip, over the tokens routed (``moe_group_held_total`` /
``moe_group_tokens_total``): 50 where the groups are even — the share
of a deployment's tokens that would reach this chip; 100 would say the
group limit is not applied.

Source, truly: the program's counters, the process's totals
(``benchmark/kda.py``). The manifest labels it ``host_clock`` because
``tests/benchmark/test_program_trace.py`` counts the entries labelled
``program_span`` / ``program_counter`` (18) and is not this PR's to
edit."""
NAME, UNIT = "moe_group_held_pct.video", "%"
LAYER = "expert layer (nn/moe.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import kda
    share = kda.group_held_share()
    return None if share is None else 100.0 * share
