"""The sparse read against its roofline: the least time the chip could
take to read every chosen page of a (row, kv head) ONCE — 64 keys and
values of that head, 64 x 512 B — and to spend the group's 16 x 4 x 128
operations a key (``flops_minicpm_sala.sparse_attn_call`` on the pages
the program counted an iteration, both lanes:
``serving_sparse_pages_total{state=chosen}``), over the device seconds
an iteration spends under ``hetu.sparse_attn`` (``longctx``)."""
NAME, UNIT = "sparse_attn_roofline_pct.longctx", "%"
LAYER = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import flops_minicpm_sala as f, longctx
    return longctx.roofline_pct(
        run, "hetu.sparse_attn", lambda cfg, c: f.sparse_attn_call(
            cfg, c["decode"]["chosen"] + c["prefill"]["chosen"]))
