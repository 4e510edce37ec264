"""The paged kernel's share of its roofline on the decode rows at heads
of 256, 8 query heads a kv head, 2 kv heads: the least time the chip
could take to read every live page's k and v once (a page of 64 tokens
is 32 KB a kv head and leaf; ``flops_qwen3_next.paged_decode_call`` on
the runner's mean ``live_pages``) over the device seconds ONE call under
``hetu.decode_lane`` -> ``hetu.paged_attn`` took
(``gdn.paged_decode_roofline_pct``)."""
NAME, UNIT = "paged_decode_roofline_pct.gdn", "%"
LAYER = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import gdn
    return gdn.paged_decode_roofline_pct(run)
