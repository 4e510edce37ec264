"""CPU rehearsal of ``arch: kda_mla_moe`` (``benchmark/archs/
kda_mla_moe.py``) under the ``serve_arch_ties`` runner: the model and
its plain reference end to end at a tiny size through a manifest, a
configuration and a mix of their own (new files HERE only), with and
without ``--trace``; each planted control refused THROUGH the harness;
what ``BENCHMARK.json`` says of the cell — by NAME, so that the next
cell can be appended behind it; the configuration against the
catalog's row; and
the arithmetic of ``benchmark/flops_kda_mla_moe.py`` and
``benchmark/kda.py``."""

import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import flops, flops_kda_mla_moe as fk, harness  # noqa: E402
from benchmark import kda as kda_readers  # noqa: E402
from benchmark.peaks import peaks_for  # noqa: E402

sys.path.insert(0, HERE)
import manifest_checks as mc  # noqa: E402
import tiny_run  # noqa: E402

MANIFEST = os.path.join(HERE, "manifest_kda.json")
CELL = "ling-3.0-flash-vl-ep8.video-8k-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
KDA = "Kimi Delta Attention (nn/parallel.py, ops/kda.py)"
#: the cell's own entries, which stand together in this order (the
#: expert layer's folded entries stood between the last two) ...
OWN = {
    **mc.of(["step_kda_conv_ms", "step_kda_scan_ms", "step_kda_update_ms",
             "step_state_copies_ms"], ".video", "ms", KDA),
    **mc.of(["kda_scan_roofline_pct", "kda_update_roofline_pct"], ".video",
            "%", mc.KERNELS)}
HELD = {"moe_group_held_pct.video": ("%", mc.MOE, mc.TOKENS)}
#: ... and, beside what every backlog cell needs, the folded entries
#: its program feeds
FOLDED = {
    **mc.KV_PEAK, **mc.ENGINE_ITER,
    **mc.of(["step_sample_ms"], ".backlogs", "ms", mc.STEP),
    **mc.of(["step_moe_experts_ms", "step_moe_shared_ms",
             "step_moe_route_ms"], ".backlogs", "ms", mc.MOE),
    "moe_local_imbalance.backlogs": ("x", mc.MOE, mc.TOKENS),
    "moe_experts_roofline_pct.backlogs": ("%", mc.MOE, mc.TOKENS),
    "mla_decode_roofline_pct.backlogs": ("%", mc.KERNELS, mc.TOKENS)}
#: read without a device plane: counters and the window's iterations
NO_DEVICE = {"engine_iter_ms.backlogs", "moe_local_imbalance.backlogs",
             "moe_group_held_pct.video"}
COUNTED = NO_DEVICE | {"setup_compile_s", "kv_used_peak_pct"}


def _config():
    with open(os.path.join(
            ROOT, "benchmark/configs/ling-3.0-flash-vl-ep8.json")) as f:
        return json.load(f)


def _run(trace=False):
    return tiny_run.run_cell(MANIFEST, "tiny.video", seed=2**31 + 41,
                             trace=trace)


@pytest.mark.parametrize("trace", [False, True])
def test_serve_arch_kda_cell_end_to_end_at_tiny_size(trace):
    out = _run(trace)
    assert not out["why_incorrect"]
    line = out["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    ref = out["info"]["reference"]
    assert ref["compared_positions"] == ref["route_near_ties"] > 0
    assert ref["max_logit_gap_at_near_ties"] <= 1e-3   # float32 both sides
    assert ref["near_ties_over_logit_tol"] == 0 and ref["limits"]
    assert len(ref["compared_prompt_lens"]) == 8
    # the latent rows of 2 layers x 65 blocks, the states and the tails
    # of 5 layers x 4 slots, float32
    assert out["info"]["arena_bytes"] == 2 * 65 * 4 * 48 * 4 \
        + 5 * 4 * 4 * 16 * 16 * 4 + 5 * 4 * 3 * 192 * 4
    if not trace:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    else:
        # no device plane on the CPU: the metrics that read device
        # scopes are left out, the counted ones are there
        assert set(line["metrics"]) == COUNTED
        # two of four groups stay: about half reach the held one
        held = line["metrics"]["moe_group_held_pct.video"]["value"]
        assert 25 < held < 75
        assert line["device"]["busy_s"] == 0.0
    json.dumps(line)


CONTROLS = ["operands", "no_erase", "no_conv", "head_decay",
            "no_group_limit", "ignore_bias"]


@pytest.mark.parametrize("control", CONTROLS)
def test_a_planted_control_is_refused_through_the_harness(control):
    """The six computations the limits must refuse (``reference.
    CONTROL``), each planted in the reference's seat of a whole
    ``harness.run_cell``: the program's tokens are then NOT that
    computation's, and the run comes out ``correct: false`` by the
    limits of ``archs/kda_mla_moe.py``. (The tiny configuration draws
    its weights at 0.16, the 0.02 of the published width scaled to 64
    columns.)"""
    import jax.numpy as jnp
    from benchmark.reference import kda_mla_moe as reference
    reference.CONTROL.update(
        {"operands": jnp.float8_e4m3fn} if control == "operands"
        else {control: True})
    try:
        out = _run()
    finally:
        reference.CONTROL.clear()
    assert out["line"]["correct"] is False and out["line"]["failed"] == 0
    assert "below the reference's top logit" in \
        " ".join(out["why_incorrect"])
    assert out["info"]["reference"]["near_ties_over_share"] > 0.05


@mc.cell_needs
def the_video_cell(m):
    cell, _ = mc.cell_of(
        m, CELL, config="ling-3.0-flash-vl-ep8",
        traffic="video-fixed-8k-backlog",
        reduced=["num_hidden_layers", "num_experts", "vocab_size"])
    mix = mc.traffic_of(cell)
    assert mix["kind"] == "serve_arch_ties" and mix["schedule_seed"] == 41
    assert mix["arrivals"] == {"process": "backlog", "count": 800}
    assert mix["drain_s"] == 0 and mix["ramp_s"] >= 60
    assert mix["prompt_len"] == {"dist": "fixed", "value": 8192,
                                 "min": 8192, "max": 8192}
    assert mix["output_len"] == {"dist": "fixed", "value": 256,
                                 "min": 256, "max": 256}
    mc.needs(m, CELL, mc.BACKLOG_CELL)
    mc.needs(m, CELL, FOLDED, mirrored_in=MANIFEST)
    mc.needs(m, CELL, {**OWN, **HELD}, mirrored_in=MANIFEST,
             sources=("device_trace", "host_clock"))
    mc.stand_together(m, list(OWN))


def test_benchmark_json_names_what_the_video_cell_needs():
    """By name, not by place: a later PR appends behind these."""
    the_video_cell(mc.real())


def test_one_body_reads_both_latent_configurations(monkeypatch):
    """The two folded rooflines count with ``flops_mla_moe`` from the
    run's OWN configuration: Ling's 32 heads and 768-wide experts here,
    Kimi's 16 and 1408 in ``test_serve_arch_mla.py`` — one reader, no
    constant of either cell in it."""
    from benchmark import program_trace, scopes
    c, m = _config(), mc.real()
    run = mc.run_without_a_device(c, {
        "live_pages": [4000, 4200], "block_size": 64, "moe": {
            "moe_local_calls_total": 10,
            "moe_local_assignments_total": 20480,
            "moe_local_experts_touched_total": 640}})
    monkeypatch.setattr(program_trace, "kernel_seconds_per_call",
                        lambda run, k, kernels_per_call=1: 2.6e-3)
    monkeypatch.setattr(scopes, "seconds", lambda run, s: 3.0e-3)
    monkeypatch.setattr(scopes, "calls", lambda run, s, op: 9)
    got = harness.find_reader(
        ROOT, m, "mla_decode_roofline_pct.backlogs").read(run)
    need = 4100 * 64 * 576 * 2 / 819e9          # bound by its bytes
    assert got == pytest.approx(100 * need / 2.6e-3) and 0 < got < 100
    got = harness.find_reader(
        ROOT, m, "moe_experts_roofline_pct.backlogs").read(run)
    # 64 experts of 3 x 2560 x 768 bf16 read once a call; three kernel
    # calls a layer call: 9 calls = 3 layer calls in 3 ms
    need = 64 * 3 * c["hidden_size"] * c["moe_intermediate_size"] * 2 \
        / 819e9
    assert got == pytest.approx(100 * need / 1.0e-3) and 0 < got < 100
    run.config = {"n_embd": 8}                  # no latent rows there
    assert harness.find_reader(
        ROOT, m, "mla_decode_roofline_pct.backlogs").read(run) is None


def test_published_widths_are_in_the_ling_configuration():
    c = _config()
    rows = []
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(x) for x in f if '"Ling-3.0-flash-VL"' in x]
    for row in rows:                # every key of the catalog's config
        assert c["source"] == row["source_url"]
        for k, v in row["config"].items():
            assert c[k] == v or k in c["reduced"], k
            assert c["published"].get(k, v) == v, k
    assert c["reduced"] == ["num_hidden_layers", "num_experts",
                            "vocab_size"]
    assert (c["hidden_size"], c["intermediate_size"],
            c["moe_intermediate_size"],
            c["moe_shared_expert_intermediate_size"],
            c["num_attention_heads"], c["head_dim"], c["kv_lora_rank"],
            c["q_lora_rank"], c["qk_nope_head_dim"], c["qk_rope_head_dim"],
            c["v_head_dim"], c["num_experts_per_tok"], c["n_group"],
            c["topk_group"], c["layer_group_size"],
            c["short_conv_kernel_size"], c["kda_lower_bound"],
            c["first_k_dense_replace"], c["routed_scaling_factor"]) == (
        2560, 6144, 768, 768, 32, 128, 512, None, 128, 64, 128, 8, 8, 4,
        6, 4, -5, 2, 2.5)
    assert (c["num_hidden_layers"], c["num_experts"], c["vocab_size"]) \
        == (12, 64, 19648)
    pub = c["published"]
    assert (pub["num_hidden_layers"], pub["num_experts"],
            pub["vocab_size"]) == (42, 512, 157184)
    # one routing group of eight, an eighth of the vocabulary, two
    # whole periods of the published five to one
    assert c["num_experts"] * c["n_group"] == pub["num_experts"]
    assert c["vocab_size"] * 8 == pub["vocab_size"]
    assert 0 <= c["deployment"]["expert_group"] < c["n_group"]
    assert c["num_hidden_layers"] % c["layer_group_size"] == 0
    assert fk.kda_layers(c) == 10 and fk.kda_layers(pub | {
        "layer_group_size": 6}) == 35
    # no clamp on a held layer
    assert not any(c["expert_swiglu_limit_list"][:12]
                   + c["share_expert_swiglu_limit_list"][:12])
    s = c["serve"]
    assert (s["max_len"], s["slots"], s["kv_blocks"], s["block_size"],
            s["prefill_chunk"]) == (8448, 72, 9600, 64, 2048)
    assert s["kv_blocks"] > s["slots"] * s["max_len"] // s["block_size"]
    assert c["stored_row"] == c["n_embd"] == 640
    # parameters held here, in bf16, the arena, the states and the
    # tails, against the chip's 16.91 GB
    from benchmark.runners.serve_arch import load_arch
    import jax
    arch = load_arch(c["arch"])
    model = arch.build(c)
    assert model.cfg.mixer_types == ("kda",) * 5 + ("mla",) \
        + ("kda",) * 5 + ("mla",)
    assert model.blocks.run_kinds == ["kda", "mla", "kda", "mla"]
    assert model.cfg.local_experts == (192, 64)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.key(0))))
    assert abs(n - 4.63e9) < 5e6
    leaves = jax.eval_shape(lambda: model.blocks.init_paged_caches(
        s["kv_blocks"], s["block_size"], jax.numpy.bfloat16, s["slots"]))
    assert [x.shape for x in leaves] == [
        (2, 9600, 64, 640), (10, 72, 32, 128, 128), (10, 72, 3, 12288)]
    cache = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves)
    assert cache == 9600 * 64 * 2560 + 72 * 10 * (
        fk.state_bytes(c) + fk.tail_bytes(c))
    assert arch.arena_row_elements(c) * 2 * 2 == 2560
    assert 0.72 <= (2 * n + cache) / 16.91e9 <= 0.76


def test_flops_kda_arithmetic_and_readers_without_a_device():
    c = _config()
    peaks = peaks_for("TPU v5 lite")
    assert fk.state_bytes(c) == 32 * 128 * 128 * 4 == 2097152
    assert fk.tail_bytes(c) == 3 * 12288 * 4
    scan = fk.kda_scan_call(c, 2048)
    assert scan["bytes"] == 2048 * 4096 * 14 + 2 * 2097152
    assert scan["flops"] == 2048 * 32 * (5 * 64 * 128 + 6 * 128 * 128)
    upd = fk.kda_update_call(c, 64)
    assert upd["bytes"] == 2 * 64 * 2097152
    assert upd["flops"] == 64 * 32 * 7 * 128 * 128
    # the update is bound by its bytes: 0.33 ms a layer at 64 slots
    assert flops.roofline_seconds(upd["flops"], upd["bytes"], peaks) \
        == pytest.approx(upd["bytes"] / 819e9)
    run = types.SimpleNamespace(config=c, peaks=peaks, trace=None,
                                cell={"name": "none"}, records={})
    m = harness.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    mc.silent_without_a_device(
        m, [n for n in {**FOLDED, **OWN}
            if n not in NO_DEVICE | set(mc.KV_PEAK)]
        + ["step_decode_ms.backlogs", "step_prefill_ms.backlogs"], c)


def test_copies_of_the_slot_leaves_and_the_group_share(monkeypatch):
    from benchmark import program_trace
    from hetu_tpu import telemetry
    from hetu_tpu.telemetry.device_scopes import classify
    from hetu_tpu.telemetry.metrics import MetricRegistry
    c = _config()
    scopes = {("serving_step", 0): {
        "copy.9": classify("jit(step)/hetu.decode_lane/hetu.kda_update/c"),
        "copy.7": classify("jit(step)/while/body/x")}}
    monkeypatch.setattr(program_trace, "_registered_scopes", lambda: scopes)
    monkeypatch.setattr(program_trace, "read", lambda run: {
        "host": {"steps_in_slice": 4}})
    text = "%{} = {}[{}]{{4,3,2,1,0}} copy({}[{}] %p)"

    def op(name, dtype, dims):
        return text.format(name, dtype, dims, dtype, dims)
    ops = {"copy.5": (0.040, op("copy.5", "f32", "10,72,32,128,128")),
           "copy.7": (0.020, op("copy.7", "f32", "72,3,12288")),
           # inside the scope that already counts it; the arena (bf16);
           # one slot's state; no copy
           "copy.9": (1.0, op("copy.9", "f32", "10,72,32,128,128")),
           "copy.11": (1.0, op("copy.11", "bf16", "2,9600,64,640")),
           "copy.13": (1.0, op("copy.13", "f32", "32,128,128")),
           "fusion.1": (1.0, op("fusion.1", "f32", "10,72,32,128,128"))}
    run = types.SimpleNamespace(config=c, trace={
        "n_devices": 1, "op_seconds": {k: v[0] for k, v in ops.items()},
        "op_text": {k: v[1] for k, v in ops.items()}})
    assert kda_readers.state_copies_ms_per_step(run) == \
        pytest.approx(1e3 * 0.060 / 4)
    run.config = {"n_embd": 1}                  # another architecture
    assert kda_readers.state_copies_ms_per_step(run) is None
    run.config, run.trace = c, None
    assert kda_readers.state_copies_ms_per_step(run) is None
    reg = MetricRegistry()
    monkeypatch.setattr(telemetry, "get_registry", lambda: reg)
    assert kda_readers.group_held_share() is None   # no such counter
    reg.counter("moe_group_held_total").inc(510)
    reg.counter("moe_group_tokens_total").inc(1000)
    assert kda_readers.group_held_share() == 0.51
