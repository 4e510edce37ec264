"""Device self time per engine iteration, both lanes and all layers,
in the latent attention's absorbed products: the queries through
``W_uk`` into the latent space and the latent results back through
``W_uv`` (``hetu.mla_absorb``) (``scopes``)."""
NAME, UNIT = "step_mla_absorb_ms.longdoc", "ms"
LAYER = "latent attention (nn/parallel.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import scopes
    return scopes.ms_per_step(run, "hetu.mla_absorb")
