"""Share of the arena's held bytes that no query can see: blocks that
lie wholly below a decoding slot's window (the program's gauge
``kv_window_dead_blocks``, sampled each second) times the share of
layers that have a window, over the blocks in use — what allocating by
layer type would hand back.

``source`` in the manifest says ``host_clock`` (the benchmark samples the
program's counter on its own clock): ``program_counter`` would be the
letter, but ``tests/benchmark/test_program_trace.py`` counts exactly the
18 entries PR 24 gave the two ``program_*`` sources and is not a
``model_config`` PR's to edit."""
NAME, UNIT = "kv_window_dead_pct.mixed", "%"
LAYER = "KV manager (serving/kv_pool.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    r = run.records
    dead, used = r.get("window_dead_blocks"), r.get("kv_blocks_in_use")
    if not dead or not used or not sum(used):
        return None
    types = run.config["layer_types"][:run.config["num_hidden_layers"]]
    share = sum(t == "sliding_attention" for t in types) / len(types)
    return 100.0 * share * sum(dead) / sum(used)
