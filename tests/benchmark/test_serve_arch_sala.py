"""CPU rehearsal of ``arch: minicpm_sala`` (``benchmark/archs/
minicpm_sala.py``) under the ``serve_arch_ties`` runner: the model and
its plain reference end to end at a tiny size through a manifest, a
configuration and a mix of their own (new files HERE only), with and
without ``--trace``; how many requests the comparison takes (the traffic
file's count), its rows compiled ahead of time, and the account of a
run's wall; what ``BENCHMARK.json`` says of the cell and of the cells
before it; the configuration against the catalog's row; and the
arithmetic of ``benchmark/flops_minicpm_sala.py`` and
``benchmark/longctx.py``."""

import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import flops, flops_minicpm_sala as fs, harness  # noqa: E402
from benchmark import longctx  # noqa: E402
from benchmark.peaks import peaks_for  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import manifest_checks as mc  # noqa: E402
import test_iteration_account as acc  # noqa: E402
import tiny_run  # noqa: E402

MANIFEST = os.path.join(HERE, "manifest_sala.json")
CELL = "minicpm-sala-pp2.longdoc-32k-backlog"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
COUNTED = {"sparse_chosen_share_pct.longctx", "engine_iter_ms.backlogs",
           "engine_host_ms.backlogs", "setup_compile_s", "kv_used_peak_pct"}
SPARSE = "block-sparse attention (nn/parallel.py, ops/sparse_select.py)"
LIGHTNING = "lightning attention (nn/parallel.py, ops/linear_attention.py)"
#: the cell's own entries, which stand together in this order ...
OWN = {
    "step_sparse_select_ms.longctx": ("ms", SPARSE, mc.TOKENS),
    "step_sparse_attn_ms.longctx": ("ms", SPARSE, mc.TOKENS),
    "step_linear_scan_ms.longctx": ("ms", LIGHTNING, mc.TOKENS),
    "step_linear_update_ms.longctx": ("ms", LIGHTNING, mc.TOKENS),
    "sparse_attn_roofline_pct.longctx": ("%", mc.KERNELS, mc.TOKENS),
    "linear_scan_roofline_pct.longctx": ("%", mc.KERNELS, mc.TOKENS),
    "linear_update_roofline_pct.longctx": ("%", mc.KERNELS, mc.TOKENS),
    "sparse_chosen_share_pct.longctx": ("%", SPARSE, mc.TOKENS),
    "step_state_copies_ms.longctx": ("ms", LIGHTNING, mc.TOKENS)}
#: ... and, beside what every backlog cell needs, the folded entries
#: its program feeds
FOLDED = {
    **mc.KV_PEAK, **mc.ENGINE_ITER,
    **mc.of(["step_sample_ms", "engine_host_ms"], ".backlogs", "ms",
            mc.STEP),
    "step_kv_arena_ms.backlogs": ("ms", mc.KV, mc.TOKENS)}
#: read without a device plane: a counter, the window's iterations, the
#: host's spans in the slice
NO_DEVICE = {"sparse_chosen_share_pct.longctx", "engine_iter_ms.backlogs",
             "engine_host_ms.backlogs"}


def _config():
    with open(os.path.join(
            ROOT, "benchmark/configs/minicpm-sala-pp2.json")) as f:
        return json.load(f)


def _run(workload="tiny.longctx", *, trace=False):
    return tiny_run.run_cell(MANIFEST, workload, seed=2**31 + 39,
                             trace=trace)


@pytest.mark.parametrize("trace", [False, True])
def test_serve_arch_sala_cell_end_to_end_at_tiny_size(trace):
    out = _run(trace=trace)
    assert not out["why_incorrect"]
    line = out["line"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    ref = out["info"]["reference"]
    assert ref["compared_positions"] > 0
    assert ref["max_logit_gap"] <= 1e-3     # float32 on both sides
    assert ref["near_ties_over_logit_tol"] == 0 and ref["limits"]
    # a mix without ``reference_requests``: the runner's 8, or all that
    # finished, the 2 longest first
    lens = ref["compared_prompt_lens"]
    assert len(lens) == 8 <= out["info"]["n_finished"]
    assert lens[:2] == sorted(lens, reverse=True)[:2]
    # K and V pages of one kv head each, the stride means, the states:
    # 2 sparse layers x 65 blocks, 3 lightning layers x 4 slots, float32
    pages = 2 * 65 * (2 * 4) * 16 * 4
    assert out["info"]["arena_bytes"] == \
        2 * pages + 2 * 65 * (4 * 2 * 16) * 4 + 3 * 4 * 4 * 16 * 16 * 4
    if not trace:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
        assert line["metrics"]["serve_tokens_per_s"]["value"] > 0
    else:
        # no device plane on the CPU: the metrics that read device
        # scopes are left out, the counted ones are there
        assert set(line["metrics"]) == COUNTED
        share = line["metrics"]["sparse_chosen_share_pct.longctx"]["value"]
        assert 20 < share < 100           # 4 of up to 14 pages chosen
        assert line["device"]["busy_s"] == 0.0
    json.dumps(line)


@pytest.mark.parametrize("control", ["operands", "forced_only", "no_decay"])
def test_a_planted_control_is_refused_through_the_harness(control):
    """The three computations the limits must refuse (``reference.
    CONTROL``), each planted in the reference's seat of a whole
    ``harness.run_cell``: the program's tokens are then NOT that
    computation's, and the run comes out ``correct: false`` by the
    limits of ``archs/minicpm_sala.py``. (The tiny configuration draws
    its weights at 0.16, the 0.02 of the published width scaled to 64
    columns: at 0.02 the mixers move a tiny model's logits by less
    than ``LOGIT_TOL``.)"""
    import jax.numpy as jnp
    from benchmark.reference import minicpm_sala as reference
    planted = {"operands": {"operands": jnp.float8_e4m3fn},
               "forced_only": {"forced_only": True},
               "no_decay": {"no_decay": True}}[control]
    # the two FAR controls at the compared count and pick of the cell's
    # own mix: 2 requests of one length, the seed's draw (64 positions;
    # off near-ties they read 0.26-2.7 against LOGIT_TOL 0.02 on four
    # seeds). The nearest one reads a SHARE, which 64 positions of this
    # size do not hold (refused on 2 seeds of 4): it keeps the eight
    # requests it had, and is refused at the cell's 512 positions on the
    # chip (PERF.md section 6, PR 50)
    workload, n = ("tiny.longctx", 8) if control == "operands" \
        else ("tiny.longctx-two", 2)
    reference.CONTROL.update(planted)
    try:
        out = _run(workload)
    finally:
        reference.CONTROL.clear()
    assert len(out["info"]["reference"]["compared_prompt_lens"]) == n
    assert out["line"]["correct"] is False and out["line"]["failed"] == 0
    assert out["why_incorrect"]
    assert "below the float32 reference's top logit" in \
        out["why_incorrect"][0]
    ref = out["info"]["reference"]
    assert max(ref["max_logit_gap"],
               ref["max_logit_gap_at_near_ties"]) > 0.1


@pytest.fixture(scope="module")
def two():
    """One run of the mix that says ``reference_requests: 2`` and
    ``reference_longest: 0`` as the cell's does (one length; and a ramp
    of 3 s for the reference's compile to end in)."""
    return _run("tiny.longctx-two")


def test_the_traffic_file_says_how_many_requests_are_compared(two):
    """... and WHICH: the mix's ``reference_longest: 0`` goes through
    the run, so both are the seed's draw of the finished requests."""
    from benchmark import traffic
    assert two["line"]["correct"] is True and not two["why_incorrect"]
    info = two["info"]
    assert info["n_finished"] > 2
    lens = info["reference"]["compared_prompt_lens"]
    assert len(lens) == 2
    assert info["reference"]["compared_positions"] > 0
    drawn = traffic.rng_for(2**31 + 39, "reference").permutation(
        info["n_finished"])[:2]
    assert info["reference_pick"] == drawn.tolist()


def _finished(lens):
    return [{"prompt": np.zeros(n, np.int32)} for n in lens]


def test_a_mix_without_the_keys_picks_what_it_picked_before():
    """The pick of the parent's lines (the 2 longest, then the seed's
    draw of the others, 8 in all), pinned for one seed."""
    from benchmark.runners import serve_arch
    lens = [24, 44, 17, 31, 44, 12, 29, 38, 21, 35, 26, 19]
    assert serve_arch.pick_reference(
        _finished(lens), 2**31 + 39, serve_arch.REFERENCE_REQUESTS,
        serve_arch.LONGEST) == [1, 4, 10, 8, 5, 2, 3, 9]
    assert (serve_arch.REFERENCE_REQUESTS, serve_arch.LONGEST) == (8, 2)
    # fewer finished than asked for: all of them, the longest first
    assert serve_arch.pick_reference(
        _finished([5, 9, 7]), 2**31 + 39, 8, 2)[:2] == [1, 2]


def _cell_mix():
    with open(os.path.join(
            ROOT, "benchmark/traffic/longdoc-fixed-32k-backlog.json")) as f:
        return json.load(f)


#: the cell's pick at equal lengths, pinned for one seed
PICKED = {3: [1, 0], 7: [1, 5], 9: [1, 5]}


@pytest.mark.parametrize("n_finished", [3, 7, 9])
def test_two_compared_requests_whatever_finished(n_finished):
    """The cell's own mix (``reference_requests`` 1 since PR 58, 2
    before; ``reference_longest: 0``): a program that finishes more
    requests in its window is compared on as many as a slower one, and
    which is the seed's draw — among equal lengths "the longest" are
    the first offered on every seed (the runner's default, for mixes
    whose lengths differ), and the cell must not compare those alone.
    The draw is a prefix: the one compared now is the first of the two
    compared before."""
    from benchmark.runners import serve_arch
    mix = _cell_mix()
    n, longest = mix["reference_requests"], mix["reference_longest"]
    assert n == 1
    finished = _finished([mix["prompt_len"]["value"]] * n_finished)
    seed = 2**31 + 39
    assert serve_arch.pick_reference(
        finished, seed, 2, serve_arch.LONGEST) == [0, 1]
    assert serve_arch.pick_reference(finished, seed, 2, longest) \
        == PICKED[n_finished]
    drawn = serve_arch.pick_reference(finished, seed, n, longest)
    assert drawn == PICKED[n_finished][:n]
    assert drawn == serve_arch.pick_reference(finished, seed, n, longest)
    # over the seeds every finished request is compared
    seen = {i for s in range(80) for i in serve_arch.pick_reference(
        finished, seed + s, n, longest)}
    assert seen == set(range(n_finished))


def test_rows_compiled_ahead_are_the_jitted_rows_to_the_bit(two):
    """``serve_arch.ReferenceRows`` (lowered, then compiled on a thread)
    against ``jax.jit`` at its first call, on one tiny request; and the
    run says where the compile went."""
    import jax
    from benchmark.runners import serve_arch
    with open(os.path.join(HERE, "configs/sala-tiny.json")) as f:
        config = json.load(f)
    arch = serve_arch.load_arch(config["arch"])
    params = arch.build(config).init(jax.random.key(39),
                                     dtype=jax.numpy.float32)
    max_len, max_out = config["serve"]["max_len"], 16
    ids = np.zeros(max_len, np.int32)
    ids[:40] = np.random.default_rng(39).integers(
        1, config["vocab_size"], 40)
    start = np.int32(29)
    ahead = serve_arch.ReferenceRows(arch, config, params, max_len,
                                     max_out)
    assert ahead.compiled_at is None    # nothing compiled before start
    lg, margin = ahead(params, ids, start)
    lg0, margin0 = jax.jit(lambda p, i, s: arch.reference_rows(
        config, p, i, s, max_out))(params, ids, start)
    assert np.array_equal(np.asarray(lg), np.asarray(lg0))
    assert np.array_equal(np.asarray(margin), np.asarray(margin0))
    assert lg.shape == (max_out, config["vocab_size"])
    # the run: the compile started with the ramp and is accounted for
    info = two["info"]
    assert info["reference_compile_s"] > 0 and info["reference_lower_s"] > 0
    assert info["reference_compile_overlapped"] is \
        (info["reference_compile_to_window_s"] > 0)
    # it started with the ramp, so it ended ramp - compile before the
    # window; where the compile was shorter than the ramp it overlapped
    # (held to what the run measured, not to this machine's speed)
    if info["reference_compile_s"] < info["ramp_s"] - 1.0:
        assert info["reference_compile_overlapped"] is True


WALL = ("process_to_runner_s", "weights_init_s",
        "engine_build_and_requests_s", "warmup_s", "ramp_s", "window_s",
        "drain_s", "teardown_s", "reference_check_s", "metrics_read_s")


def test_a_run_says_where_its_wall_went(two):
    info = two["info"]
    for key in WALL + ("reference_compile_s", "run_wall_s",
                       "reference_compile_overlapped"):
        assert key in info, key
    # the window the run was asked for (``tiny_run`` doubles it where a
    # loaded machine finished too few), not this machine's 1.5 s
    assert info["window_s"] == two["window_asked_s"]
    assert 3.0 <= info["ramp_s"] < 4.0
    assert abs(info["run_wall_s"] - sum(info[k] for k in WALL)) < 1.0


@mc.cell_needs
def the_longctx_cell(m):
    cell, _ = mc.cell_of(m, CELL, config="minicpm-sala-pp2",
                         traffic="longdoc-fixed-32k-backlog",
                         reduced=["num_hidden_layers", "mixer_types"])
    mix = mc.traffic_of(cell)
    assert mix["kind"] == "serve_arch_ties" and mix["schedule_seed"] == 39
    assert mix["arrivals"] == {"process": "backlog", "count": 160}
    assert (mix["ramp_s"], mix["drain_s"]) == (120, 0)
    # the comparison's count is the file's, and the one compared is the
    # seed's draw: every request has the same lengths, none is the
    # longest (ONE since PR 58: 256 positions hold the rule on a share
    # as 512 did, and the cold run had 8 s to the driver's limit)
    assert mix["reference_requests"] == 1
    assert mix["reference_longest"] == 0
    assert (mix["prompt_len"]["dist"], mix["prompt_len"]["value"]) == \
        ("fixed", 32000)
    assert (mix["output_len"]["dist"], mix["output_len"]["value"],
            mix["output_len"]["max"]) == ("fixed", 256, 256)
    mc.needs(m, CELL, mc.BACKLOG_CELL)
    mc.needs(m, CELL, FOLDED, mirrored_in=MANIFEST)
    mc.needs(m, CELL, OWN, mirrored_in=MANIFEST,
             sources=("device_trace", "host_clock"))
    mc.stand_together(m, list(OWN))


def test_benchmark_json_names_what_the_longctx_cell_needs():
    the_longctx_cell(mc.real())


@mc.cell_needs
def the_two_accounts_of_every_serving_cell(m):
    """PR 35's fourteen entries stand together, and so do PR 53's
    eleven; each ``.chat`` entry lists the cells that report
    ``gap_p95_ms``, each ``.backlogs`` entry EVERY cell that reports
    ``serve_tokens_per_s``, in that metric's order — a cell appended
    there is appended here."""
    import test_process_account as proc
    e2e = {x["name"]: x for x in m["end_to_end"]}
    for names in (acc.ALL, proc.ALL):
        mc.stand_together(m, names)
        for name in names:
            x = mc.entry(m, name)
            if name.endswith(".chat"):
                assert x["workloads"] == e2e["gap_p95_ms"]["workloads"]
            elif name.endswith(".backlogs"):
                assert x["workloads"] == e2e[mc.TOKENS]["workloads"]
    assert CELL in e2e[mc.TOKENS]["workloads"]


def test_the_accounts_list_every_cell_that_reports_their_metric():
    the_two_accounts_of_every_serving_cell(mc.real())


def test_published_widths_are_in_the_sala_configuration():
    c = _config()
    rows = []
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            rows = [json.loads(x) for x in f if '"MiniCPM-SALA"' in x]
    for row in rows:                # every key of the catalog's config
        assert c["source"] == row["source_url"]
        for k, v in row["config"].items():
            assert c[k] == v or k in c["reduced"], k
        assert c["mixer_types"] == row["config"]["mixer_types"][0::2]
    assert (c["hidden_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["head_dim"], c["lightning_nh"],
            c["lightning_head_dim"], c["intermediate_size"],
            c["vocab_size"], c["tie_word_embeddings"], c["attn_use_rope"],
            c["lightning_use_rope"], c["scale_emb"], c["scale_depth"],
            c["dim_model_base"]) == (4096, 32, 2, 128, 32, 128, 16384,
                                     73448, False, False, True, 12, 1.4,
                                     256)
    assert c["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert (c["num_hidden_layers"], len(c["mixer_types"]),
            c["published"]["num_hidden_layers"]) == (16, 16, 32)
    # the published ratio, 1 sparse to 3 lightning
    assert fs.layers(c) == (4, 12)
    assert fs.layers({"mixer_types": c["published"]["mixer_types"]}) \
        == (8, 24)
    a = c["assumed"]
    assert (a["kernel_size"], a["kernel_stride"], a["block_size"],
            a["topk"], a["init_blocks"], a["window_size"]) == \
        (32, 16, 64, 64, 1, 2048)
    s = c["serve"]
    assert (s["max_len"], s["slots"], s["kv_blocks"], s["block_size"],
            s["prefill_chunk"]) == (33280, 20, 10400, 64, 2048)
    assert s["block_size"] == a["block_size"]       # a page is a block
    assert s["prefill_chunk"] <= a["window_size"]   # in-pack keys forced
    # parameters held here, in bf16, the arena and the states, against
    # the chip's 16.91 GB
    from benchmark.runners.serve_arch import load_arch
    import jax
    arch = load_arch(c["arch"])
    model = arch.build(c)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(
        jax.eval_shape(model.init, jax.random.key(0))))
    assert abs(n - c["sizes"]["held_parameters"]) < 1e6   # the gains
    assert abs(n - 5.039e9) < 2e6
    leaves = jax.eval_shape(lambda: model.blocks.init_paged_caches(
        s["kv_blocks"], s["block_size"], jax.numpy.bfloat16, s["slots"]))
    cache = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves)
    assert cache == c["sizes"]["arena_bytes"] + c["sizes"]["state_bytes"]
    assert arch.arena_row_elements(c) * 2 * 4 == \
        c["sizes"]["cache_bytes_a_token"] == 4224
    assert 0.78 <= (2 * n + cache) / 16.91e9 <= 0.80


def test_flops_minicpm_sala_arithmetic():
    c = _config()
    peaks = peaks_for("TPU v5 lite")
    assert fs.page_bytes(c) == 64 * 512
    # 991,000 chosen pages an iteration: 32.5 GB, 39.6 ms at the
    # bandwidth peak; 16 heads x 512 operations a key is 16 a byte,
    # under the chip's 240 — bound by the bytes
    call = fs.sparse_attn_call(c, 991000)
    assert call["bytes"] == 991000 * 32768
    assert call["flops"] == 991000 * 64 * 16 * 512
    assert flops.roofline_seconds(call["flops"], call["bytes"], peaks) \
        == pytest.approx(991000 * 32768 / 819e9)
    assert fs.state_bytes(c) == 32 * 128 * 128 * 4 == 2097152
    scan = fs.linear_scan_call(c, 2048)
    assert scan["bytes"] == 2048 * 4096 * 10 + 2 * 2097152
    assert scan["flops"] == 2048 * 32 * 4 * 128 * 128
    upd = fs.linear_update_call(c, 16)
    assert upd["bytes"] == 2 * 16 * 2097152
    # readers of device scopes return nothing without a device plane
    mc.silent_without_a_device(
        mc.real(),
        [n for n in {**FOLDED, **OWN}
         if n not in NO_DEVICE | set(mc.KV_PEAK)]
        + ["step_decode_ms.backlogs", "step_prefill_ms.backlogs"], c)


def test_longctx_counts_and_path_seconds(monkeypatch):
    from hetu_tpu import telemetry
    from hetu_tpu.telemetry.metrics import MetricRegistry
    reg = MetricRegistry()
    monkeypatch.setattr(telemetry, "get_registry", lambda: reg)
    was = telemetry.enabled()
    telemetry.enable(True)
    # two iterations in the window (16 and 17 rows, 2,048 and 1,280
    # tokens), one before it (the ramp: 3 rows) that does not count
    steps = [acc._ev("serve/step", ts, 0.4, iter=i, active=a,
                     prefill_tokens=t, cpu_s=0.01, wait_cpu_s=0.001,
                     lock_wait_s=0.0, frames=1, since_prev_s=0.001)
             for i, (ts, a, t) in enumerate(
                 [(9.0, 3, 2048), (10.0, 16, 2048), (10.5, 17, 1280)])]
    tracer = types.SimpleNamespace(events=lambda: steps, epoch=100.0)
    monkeypatch.setattr(telemetry, "get_tracer", lambda: tracer)
    run = types.SimpleNamespace(records={"window": (110.0, 111.0)})
    try:
        assert longctx.counts(run) is None      # a program without them
        assert longctx.chosen_share() is None
        reg.counter("serving_decode_slot_steps_total").inc(160)
        reg.counter("serving_tokens_total").inc(16384, kind="prompt")
        pages = reg.counter("serving_sparse_pages_total")
        for lane, chosen, visible in (("decode", 81920, 642560),
                                      ("prefill", 7.6e6, 3.3e7)):
            pages.inc(chosen, state="chosen", lane=lane)
            pages.inc(visible, state="visible", lane=lane)
        c = longctx.counts(run)
        assert longctx.chosen_share() == 81920 / 642560
        run.records = {}                        # no window: nothing
        assert longctx.counts(run) is None
    finally:
        telemetry.enable(was)
    # the process's pages a row (512 chosen, 4,016 visible) on the
    # WINDOW's 16.5 rows an iteration
    assert c["decode"] == {"units": 16.5, "chosen": 16.5 * 512,
                           "visible": 16.5 * 4016}
    assert c["prefill"]["units"] == 1664.0
    assert c["prefill"]["chosen"] == 1664.0 * 7.6e6 / 16384
    # a scope counts wherever it stands in an instruction's path
    from hetu_tpu.telemetry.device_scopes import classify
    scopes = {("serving_step", 0): {
        "custom-call.1": classify(
            "jit(step)/hetu.prefill_lane/hetu.sparse_attn/hetu.paged_attn/x"),
        "fusion.2": classify("jit(step)/hetu.decode_lane/hetu.sparse_select/y"),
        "fusion.3": classify("jit(step)/hetu.decode_lane/mul")}}
    from benchmark import program_trace
    monkeypatch.setattr(program_trace, "_registered_scopes", lambda: scopes)
    run = types.SimpleNamespace(trace={"n_devices": 1, "op_seconds": {
        "custom-call.1": 0.5, "fusion.2": 0.25, "fusion.3": 1.0}})
    assert longctx.path_seconds(run, "hetu.sparse_attn") == 0.5
    assert longctx.path_seconds(run, "hetu.sparse_select") == 0.25
    assert longctx.path_seconds(run, "hetu.linear_scan") is None
    run.trace = None
    assert longctx.path_seconds(run, "hetu.sparse_attn") is None


def test_state_copies_are_named_by_what_they_move(monkeypatch):
    """``copy*`` instructions with a float32 result of whole layers of
    every slot's state, outside the lightning scopes."""
    from benchmark import program_trace
    from hetu_tpu.telemetry.device_scopes import classify
    c = _config()
    scopes = {("serving_step", 0): {
        "copy.9": classify("jit(step)/hetu.decode_lane/hetu.linear_update/c"),
        "copy.7": classify("jit(step)/while/body/x")}}
    monkeypatch.setattr(program_trace, "_registered_scopes", lambda: scopes)
    monkeypatch.setattr(program_trace, "read", lambda run: {
        "host": {"steps_in_slice": 4}})
    text = "%{} = {}[{}]{{4,3,2,1,0}} copy({}[{}] %p)"

    def op(name, dtype, dims):
        return text.format(name, dtype, dims, dtype, dims)
    ops = {"copy.5": (0.040, op("copy.5", "f32", "3,20,32,128,128")),
           "copy.7": (0.020, op("copy.7", "f32", "20,3,32,128,128")),
           # inside the scope that already counts it; the arena (bf16);
           # a state of one slot; no copy
           "copy.9": (1.0, op("copy.9", "f32", "3,20,32,128,128")),
           "copy.11": (1.0, op("copy.11", "bf16", "4,10400,64,256")),
           "copy.13": (1.0, op("copy.13", "f32", "32,128,128")),
           "fusion.1": (1.0, op("fusion.1", "f32", "3,20,32,128,128"))}
    run = types.SimpleNamespace(config=c, trace={
        "n_devices": 1, "op_seconds": {k: v[0] for k, v in ops.items()},
        "op_text": {k: v[1] for k, v in ops.items()}})
    assert longctx.state_copies_ms_per_step(run) == \
        pytest.approx(1e3 * 0.060 / 4)
    run.config = {"n_embd": 1}                  # another architecture
    assert longctx.state_copies_ms_per_step(run) is None
    run.config, run.trace = c, None
    assert longctx.state_copies_ms_per_step(run) is None
