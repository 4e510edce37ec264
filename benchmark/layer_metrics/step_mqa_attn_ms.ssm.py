"""Device self time per engine iteration under ``hetu.paged_attn`` — both
paged calls of the two multi-query attention layers: the decode rows'
read of their pages and the prefill pack's read of its history, one kv
head of 128 under 20 query heads (``longctx.ms_per_step``)."""
NAME, UNIT = "step_mqa_attn_ms.ssm", "ms"
LAYER = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import ssm
    return ssm.ms_per_step(run, "hetu.paged_attn")
