"""CPU rehearsal of ``arch: kda_mla_moe`` (``benchmark/archs/
kda_mla_moe.py``) under the ``serve_arch_ties`` runner: the model and
its plain reference end to end at a tiny size through a manifest, a
configuration and a mix of their own (new files HERE only), with and
without ``--trace``; each planted control refused THROUGH the harness;
what ``BENCHMARK.json`` says of the cell — by NAME, so that the next
cell can be appended behind it — and of the pins' views in
``tests/conftest.py``; the configuration against the catalog's row; and
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
import tiny_run  # noqa: E402

MANIFEST = os.path.join(HERE, "manifest_kda.json")
CELL = "ling-3.0-flash-vl-ep8.video-8k-backlog"
BEFORE = "minicpm-sala-pp2.longdoc-32k-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
VIDEO = [
    "step_prefill_ms", "step_decode_ms", "step_sample_ms", "engine_iter_ms",
    "step_kda_conv_ms", "step_kda_scan_ms", "step_kda_update_ms",
    "step_state_copies_ms", "kda_scan_roofline_pct",
    "kda_update_roofline_pct", "step_moe_experts_ms", "step_moe_shared_ms",
    "step_moe_route_ms", "moe_experts_roofline_pct", "moe_local_imbalance",
    "moe_group_held_pct", "mla_decode_roofline_pct"]
#: read without a device plane: counters and the window's iterations
NO_DEVICE = {"engine_iter_ms", "moe_local_imbalance", "moe_group_held_pct"}
COUNTED = {n + ".video" for n in NO_DEVICE} | {"setup_compile_s",
                                               "kv_used_peak_pct"}
ACCOUNT = ["engine_host_cpu_ms", "engine_host_offcpu_ms",
           "host_dispatch_ms", "wire_cpu_ms", "step_launch_lag_ms",
           "step_fetch_lag_ms"]


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


def test_manifest_names_what_the_video_cell_needs():
    """By name, not by place: a later PR appends behind these."""
    m = harness.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    cells = [w["name"] for w in m["workloads"]]
    cell = m["workloads"][cells.index(CELL)]
    assert cells.index(CELL) == cells.index(BEFORE) + 1
    assert cell["chips"] == 1 and cell["config"] == "ling-3.0-flash-vl-ep8"
    config = next(c for c in m["configs"] if c["name"] == cell["config"])
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert sum(w["chips"] == 4 for w in m["workloads"]) == 0
    with open(os.path.join(ROOT, "benchmark/traffic",
                           f"{cell['traffic']}.json")) as f:
        mix = json.load(f)
    assert mix["kind"] == "serve_arch_ties" and mix["schedule_seed"] == 41
    assert mix["arrivals"] == {"process": "backlog", "count": 800}
    assert mix["drain_s"] == 0 and mix["ramp_s"] >= 60
    assert mix["prompt_len"] == {"dist": "fixed", "value": 8192,
                                 "min": 8192, "max": 8192}
    assert mix["output_len"] == {"dist": "fixed", "value": 256,
                                 "min": 256, "max": 256}
    names = [x["name"] for x in m["per_layer"]]
    first = names.index(VIDEO[0] + ".video")
    assert names[first:first + len(VIDEO)] == [n + ".video" for n in VIDEO]
    assert first > names.index("engine_iter_ms.longctx")
    rehearsed = {x["name"] for x in
                 harness.load_manifest(MANIFEST)["per_layer"]}
    for x in m["per_layer"][first:first + len(VIDEO)]:
        mod = harness.find_reader(ROOT, m, x["name"])
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == \
            (x["name"], x["unit"], x["layer"], x["moves"])
        assert x["moves"] == "serve_tokens_per_s"
        assert x["workloads"] == [CELL] and x["name"] in rehearsed
        assert x["source"] in ("device_trace", "host_clock")
        if "roofline" in x["name"]:
            assert x["unit"] == "%" and x["better"] == "higher"
    # the cell behind the MiniCPM cell wherever both are listed
    listed = [x for x in m["end_to_end"] + m["per_layer"]
              if CELL in x.get("workloads", []) and x["workloads"] != [CELL]]
    assert [x["name"] for x in listed] == [
        "serve_tokens_per_s", "kv_used_peak_pct", "setup_compile_s"] + [
        n + ".backlogs" for n in ACCOUNT]
    for x in listed:
        w = x["workloads"]
        assert w.index(CELL) == w.index(BEFORE) + 1


def test_the_pins_are_shown_the_files_own_entries():
    """``tests/conftest.py``: ``later_entries_first`` only reorders;
    ``as_of`` leaves out exactly what was appended after a cell, and at
    the last cell it is the file."""
    sys.path.insert(0, os.path.dirname(HERE))
    from conftest import (
        AS_OF_PINS, PINNED_LAST_CELL, PINNED_LAST_ENTRIES, as_of,
        later_entries_first,
    )
    m = harness.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    last = m["workloads"][-1]["name"]
    assert as_of(m, last) == m

    def canon(x):
        return json.dumps(dict(x, workloads=sorted(x.get("workloads", []))),
                          sort_keys=True)
    shown = later_entries_first(m)
    for kind in ("end_to_end", "per_layer"):
        assert sorted(map(canon, shown[kind])) == \
            sorted(map(canon, m[kind]))
        for x in shown[kind]:
            if PINNED_LAST_CELL in x.get("workloads", []):
                assert x["workloads"][-1] == PINNED_LAST_CELL
    assert shown["per_layer"][-1]["name"] == PINNED_LAST_ENTRIES[1]
    assert set(AS_OF_PINS.values()) == {BEFORE}
    old = as_of(m, BEFORE)
    assert [w["name"] for w in old["workloads"]] == \
        [w["name"] for w in m["workloads"]][:6]
    assert [c["name"] for c in old["configs"]] == \
        [c["name"] for c in m["configs"]][:5]
    gone = {x["name"] for x in m["per_layer"]} \
        - {x["name"] for x in old["per_layer"]}
    assert gone == {n + ".video" for n in VIDEO}
    for kind in ("end_to_end", "per_layer"):
        kept = {x["name"]: x for x in m[kind]}
        for x in old[kind]:
            assert CELL not in x.get("workloads", [])
            assert dict(kept[x["name"]], workloads=None) == \
                dict(x, workloads=None)
            if "workloads" in x:
                assert x["workloads"] == [
                    c for c in kept[x["name"]]["workloads"] if c != CELL]


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
    for name in VIDEO:
        if name not in NO_DEVICE:
            assert harness.find_reader(ROOT, m, name + ".video") \
                .read(run) is None, name


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
