"""Tokens the block lane handed its requests per slot-pass, over the
whole run (``serving_diffusion_tokens_total`` over
``serving_diffusion_passes_total``, both kinds; the program's counters,
read by the runner when the run ends: ``host_clock`` in the manifest,
as the ``.mixed`` imbalance entry explains): a block of 4 in 4 denoise
passes and a commit pass is 0.8; a last block cut at ``max_tokens``
hands on fewer."""
NAME, UNIT = "diffusion_tokens_per_pass.blockgen", "tokens/pass"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    d = run.records.get("diffusion")
    if not d or not d["denoise_passes"] + d["commit_passes"]:
        return None
    return d["serving_diffusion_tokens_total"] \
        / (d["denoise_passes"] + d["commit_passes"])
