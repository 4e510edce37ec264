"""CPU rehearsal of ``arch: sdar_moe`` (``benchmark/archs/sdar_moe.py``)
under the ``serve_arch_blocks`` runner: the model and its plain
reference end to end at a tiny size through a manifest, a configuration
and a mix of their own (new files HERE only), with and without
``--trace``; each planted control refused THROUGH the harness; the
block states rebuilt from a request's unmask passes; what
``BENCHMARK.json`` says of the cell — by NAME, so that the next cell can
be appended behind it;
the configuration against the catalog's row; and the arithmetic of
``benchmark/flops_sdar_moe.py``."""

import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import flops, flops_sdar_moe as fs, harness  # noqa: E402
from benchmark.peaks import peaks_for  # noqa: E402
from benchmark.runners import serve_arch_blocks  # noqa: E402

sys.path.insert(0, HERE)
import manifest_checks as mc  # noqa: E402
import tiny_run  # noqa: E402

MANIFEST = os.path.join(HERE, "manifest_blocks.json")
CELL = "sdar-30b-a3b-ep8.reason-1k-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: the cell's own entries — bodies no other cell shares: the sampler's
#: scope is ``hetu.diffusion_sample``, the experts count with
#: ``flops_sdar_moe`` — of which the last three stand together ...
OWN = {
    "step_sample_ms.blockgen": ("ms", mc.STEP, mc.TOKENS),
    "moe_experts_roofline_pct.blockgen": ("%", mc.MOE, mc.TOKENS),
    "paged_block_roofline_pct.blockgen": ("%", mc.KERNELS, mc.TOKENS),
    "diffusion_tokens_per_pass.blockgen": ("tokens/pass", mc.STEP,
                                           mc.TOKENS),
    "diffusion_commit_pass_pct.blockgen": ("%", mc.STEP, mc.TOKENS)}
#: ... and, beside what every backlog cell needs, the folded entries
#: its program feeds
FOLDED = {
    **mc.KV_PEAK, **mc.ENGINE_ITER,
    **mc.of(["step_moe_experts_ms", "step_moe_route_ms"], ".backlogs",
            "ms", mc.MOE),
    "moe_local_imbalance.backlogs": ("x", mc.MOE, mc.TOKENS)}
#: read without a device plane: counters and the window's iterations
COUNTED = {"engine_iter_ms.backlogs", "moe_local_imbalance.backlogs",
           "diffusion_tokens_per_pass.blockgen",
           "diffusion_commit_pass_pct.blockgen", "setup_compile_s",
           "kv_used_peak_pct"}


def _config():
    with open(os.path.join(
            ROOT, "benchmark/configs/sdar-30b-a3b-ep8.json")) as f:
        return json.load(f)


def _run(trace=False, workload="tiny.blocks", need=8):
    return tiny_run.run_cell(MANIFEST, workload, seed=2**31 + 45,
                             trace=trace, need=need)


@pytest.mark.parametrize("trace", [False, True])
def test_serve_arch_blocks_cell_end_to_end_at_tiny_size(trace):
    out = _run(trace)
    assert not out["why_incorrect"]
    line = out["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    ref = out["info"]["reference"]
    assert ref["compared_positions"] == ref["route_near_ties"] > 0
    assert ref["max_logit_gap_at_near_ties"] <= 1e-3   # float32 both sides
    assert ref["compared_passes"] > 0 and ref["confidence_swaps"] == 0
    assert ref["limits"] and ref["control"] == {}
    assert len(ref["compared_prompt_lens"]) == 8
    # prompts with a tail of 1-3 among the compared
    assert any(n % 4 for n in ref["compared_prompt_lens"])
    # every block's K/V is committed ONCE: a block is counted where
    # it is committed and hands on at most its 4 tokens, and the commit
    # passes are one a block — or none at all, the FUSED case (S13: a
    # commit done inside another pass takes no pass of its own). A
    # program that commits some blocks and not others, or one twice,
    # reads neither. That the committed keys are the clean ones is the
    # ``no_commit_pass`` control's to refuse, below
    d = out["info"]["diffusion"]
    blocks = d["serving_diffusion_blocks_total"]
    assert blocks > 0 and d["commit_passes"] in (0, blocks)
    assert blocks <= d["serving_diffusion_tokens_total"] <= 4 * blocks
    assert d["denoise_passes"] >= blocks
    # K and V of 3 layers x 65 blocks x 4 x 32, float32
    assert out["info"]["arena_bytes"] == 2 * 3 * 65 * 4 * 32 * 4
    if not trace:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    else:
        # no device plane on the CPU: the metrics that read device
        # scopes are left out, the counted ones are there
        assert set(line["metrics"]) == COUNTED
        m = line["metrics"]
        # 4 denoise passes a block and one commit pass: 20 % of five
        # passes, or 0 where the commit is fused into another pass (the
        # reader then finds no commit pass); 4 tokens a block in 5
        # passes are 0.8 a pass, in 4 passes 1.0, and tails and cuts
        # hand on fewer than 4 of some blocks
        commit = m["diffusion_commit_pass_pct.blockgen"]["value"]
        assert commit == 0 or 19.9 <= commit <= 25
        assert (commit == 0) == (d["commit_passes"] == 0)
        per_pass = m["diffusion_tokens_per_pass.blockgen"]["value"]
        assert 0.5 < per_pass < (0.8 if commit else 1.0 + 1e-9)
        assert line["device"]["busy_s"] == 0.0
    json.dumps(line)


CONTROLS = {
    "causal_inside_a_block": {"intra": "causal"},
    "no_commit_pass": {"keys_from": "last_denoise"},
    "own_keys_left_out": {"intra": "none"},
    "left_to_right": {"order": "left_to_right"},
    "sigmoid_router": {"score": "sigmoid"},
    "no_qk_norm": {"qk_norm": False},
    "float8_e4m3fn_operands": {"operands": "float8_e4m3fn"},
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_planted_control_is_refused_through_the_harness(control):
    """The seven computations the limits must refuse (``reference.
    CONTROL``), each planted in the reference's seat of a whole
    ``harness.run_cell``: the program's tokens and passes are then NOT
    that computation's, and the run comes out ``correct: false`` by the
    limits of ``archs/sdar_moe.py``. (The tiny configuration draws its
    weights at 0.3: at 0.02 and 32 columns the logits are flat.)"""
    import jax.numpy as jnp
    from benchmark.reference import sdar_moe as reference
    reference.CONTROL.update({
        k: getattr(jnp, v) if k == "operands" else v
        for k, v in CONTROLS[control].items()})
    try:
        # 24 finished requests compared (the mix's count): the shares
        # are held to the limits over 220-275 positions (over eight
        # requests ``no_commit_pass`` read 6-18 % of its near-ties over,
        # against the limit's 5 %, by which eight a loaded machine
        # finished; over 24 it read 11.4-15.2 % on three seeds)
        out = _run(workload="tiny.blocks-24", need=24)
    finally:
        reference.CONTROL.clear()
    ref = out["info"]["reference"]
    assert len(ref["compared_prompt_lens"]) == 24
    assert out["line"]["correct"] is False and out["line"]["failed"] == 0
    why = " ".join(out["why_incorrect"])
    if control == "left_to_right":
        assert "from the reference rule's choice" in why
        assert ref["near_ties_over_share"] == 0      # the logits are right
    else:
        assert "below the reference's top logit" in why
    assert ref["control"]


def test_block_states_from_the_unmask_passes():
    gen = {"block_length": 4, "mask_token_id": 99, "denoising_steps": 4}
    prompt = np.arange(1, 7)                     # a tail of 2
    toks = np.asarray([10, 11, 20, 21, 22, 23, 30])   # the last block cut
    unmask = [1, 0, 2, 0, 3, 1, 0]
    clean, noised, start, when, end = serve_arch_blocks.block_states(
        prompt, toks, unmask, gen, 20)
    assert (start, end) == (4, 12)               # 13 // 4 * 4
    assert clean.tolist() == [1, 2, 3, 4, 5, 6, 10, 11, 20, 21, 22, 23] \
        + [0] * 8
    assert when[:12].tolist() == [-1] * 6 + [1, 0, 2, 0, 3, 1]
    M = 99
    assert noised[:, :8].tolist() == [
        [5, 6, M, M, M, M, M, M],                # going into pass 0
        [5, 6, M, 11, M, 21, M, M],
        [5, 6, 10, 11, M, 21, M, 23],
        [5, 6, 10, 11, 20, 21, M, 23]]
    assert (noised[:, 8:] == 0).all()            # beyond the whole blocks


@mc.cell_needs
def the_blocks_cell(m):
    cell, _ = mc.cell_of(m, CELL, config="sdar-30b-a3b-ep8",
                         traffic="reason-block4-backlog",
                         reduced=["num_experts", "vocab_size"])
    mix = mc.traffic_of(cell)
    assert mix["kind"] == "serve_arch_blocks" and mix["schedule_seed"] == 45
    assert mix["arrivals"] == {"process": "backlog", "count": 400}
    assert mix["drain_s"] == 0 and mix["ramp_s"] >= 50
    assert mix["prompt_len"] == {"dist": "fixed", "value": 512,
                                 "min": 512, "max": 512}
    assert mix["output_len"] == {"dist": "uniform", "min": 512,
                                 "max": 1024}
    gen = _config()["serve"]["generation"]
    assert all(gen[k] == v for k, v in mix["generation"].items())
    mc.needs(m, CELL, mc.BACKLOG_CELL)
    mc.needs(m, CELL, FOLDED, mirrored_in=MANIFEST)
    mc.needs(m, CELL, OWN, mirrored_in=MANIFEST,
             sources=("device_trace", "host_clock"))
    mc.stand_together(m, list(OWN)[-3:])


def test_benchmark_json_names_what_the_blocks_cell_needs():
    """By name, not by place: a later PR appends behind these."""
    the_blocks_cell(mc.real())


@mc.cell_needs
def the_block_lane_is_read_once(m):
    """Two quantities of this cell have a body of their own AND a folded
    namesake other cells list: the cell is read by its own and not by
    the namesake — a run prints each quantity once."""
    for own in ("step_sample_ms.blockgen",
                "moe_experts_roofline_pct.blockgen"):
        assert mc.lists(m, own, CELL)
        twin = own.rsplit(".", 1)[0] + ".backlogs"
        assert not mc.lists(m, twin, CELL), twin
        assert mc.reader_body(m, own) != mc.reader_body(m, twin)


def test_the_block_lane_has_its_own_sampler_and_expert_count():
    the_block_lane_is_read_once(mc.real())


def test_published_widths_are_in_the_sdar_configuration():
    c = _config()
    rows = []
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(x) for x in f if '"SDAR-30B-A3B-Chat"' in x]
    for row in rows:                # every key of the catalog's config
        assert c["source"] == row["source_url"]
        for k, v in row["config"].items():
            assert c[k] == v or k in c["reduced"], k
            assert c["published"].get(k, v) == v, k
    assert c["reduced"] == ["num_experts", "vocab_size"]
    assert (c["hidden_size"], c["moe_intermediate_size"],
            c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["num_experts_per_tok"], c["rope_theta"],
            c["rms_norm_eps"], c["tie_word_embeddings"]) == (
        2048, 768, 32, 4, 128, 8, 1000000, 1e-6, False)
    # ALL the layers; an eighth of the experts and of the vocabulary
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) \
        == (48, 16, 18992)
    pub = c["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (48, 128, 151936)
    assert c["num_experts"] * 8 == pub["num_experts"]
    assert c["vocab_size"] * 8 == pub["vocab_size"]
    assert c["deployment"]["chips"] == 8
    s, g = c["serve"], c["serve"]["generation"]
    assert (s["max_len"], s["slots"], s["kv_blocks"], s["block_size"],
            s["prefill_chunk"]) == (1600, 32, 800, 64, 512)
    assert (g["block_length"], g["denoising_steps"], g["remasking"],
            g["mask_token_id"]) == (4, 4, "low_confidence_static", 18991)
    assert c["assumed"]["qk_norm_gain"] == 2.0
    assert all(n % g["block_length"] == 0 for n in (
        s["max_len"], s["block_size"], s["prefill_chunk"]))
    # the worst request (512 + 1,024) in whole pages, every slot at once
    assert s["slots"] * -(-1536 // s["block_size"]) < s["kv_blocks"]
    from benchmark.runners.serve_arch import load_arch
    import jax
    arch = load_arch(c["arch"])
    model = arch.build(c)
    assert model.cfg.local_experts == (0, 16)
    assert model.cfg.num_experts == 128
    assert model.generation.mask_token_id == 18991
    assert model.blocks.block.attn.attn_block == 4
    assert model.blocks.block.moe.score == "softmax"
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.key(0))))
    assert abs(n - 4.62e9) < 5e6
    leaves = jax.eval_shape(lambda: model.blocks.init_paged_caches(
        s["kv_blocks"], s["block_size"], jax.numpy.bfloat16, s["slots"]))
    assert [x.shape for x in leaves] == [(48, 800, 64, 512)] * 2
    cache = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves)
    assert cache == 800 * 64 * 98304
    assert arch.arena_row_elements(c) == c["n_embd"] == 512
    assert 0.83 <= (2 * n + cache) / 16.91e9 <= 0.86
    assert arch.reference_config(c)["num_experts"] == 128


def test_flops_sdar_arithmetic_and_readers_without_a_device():
    c = _config()
    peaks = peaks_for("TPU v5 lite")
    assert fs.expert_bytes(c) == 3 * 2048 * 768 * 2
    call = fs.moe_experts_call(c, 128, 16)
    assert call == {"bytes": 16 * 9437184.0,
                    "flops": 6.0 * 2048 * 768 * 128}
    # 32 slots of ~14 pages: K and V once a slot, 4 rows against them
    blk = fs.paged_block_call(c, 448, 64)
    assert blk["bytes"] == 2.0 * 448 * 64 * 512 * 2
    assert blk["flops"] == 4.0 * 448 * 64 * 4 * 32 * 128
    # bound by its bytes
    assert flops.roofline_seconds(blk["flops"], blk["bytes"], peaks) \
        == pytest.approx(blk["bytes"] / 819e9)
    run = types.SimpleNamespace(config=c, peaks=peaks, trace=None,
                                cell={"name": "none"}, records={})
    m = harness.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    mc.silent_without_a_device(
        m, [n for n in {**FOLDED, **OWN} if n not in mc.KV_PEAK]
        + ["step_decode_ms.backlogs", "step_prefill_ms.backlogs"], c)
    run.records = {"diffusion": {
        "denoise_passes": 400.0, "commit_passes": 100.0,
        "serving_diffusion_blocks_total": 100.0,
        "serving_diffusion_tokens_total": 398.0}}
    assert harness.find_reader(
        ROOT, m, "diffusion_commit_pass_pct.blockgen").read(run) == 20.0
    assert harness.find_reader(
        ROOT, m, "diffusion_tokens_per_pass.blockgen").read(run) == 0.796
