"""The share of the block lane's slot-passes that were commit passes —
a block's final tokens run once more for their K/V, their logits unused
— over the whole run (``serving_diffusion_passes_total{kind}``, read by
the runner when the run ends): 20 at 4 denoise passes a block."""
NAME, UNIT = "diffusion_commit_pass_pct.blockgen", "%"
LAYER = "fused serving step (serving/engine.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    d = run.records.get("diffusion")
    if not d or not d["denoise_passes"] + d["commit_passes"]:
        return None
    return 100.0 * d["commit_passes"] \
        / (d["denoise_passes"] + d["commit_passes"])
