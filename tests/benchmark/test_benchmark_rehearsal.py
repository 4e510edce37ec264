"""CPU rehearsal of the benchmark (``benchmark/``): both runners end to
end at a tiny size, the last line's keys, the metrics' arithmetic on
synthetic timestamps, the trace reduction on a trace recorded on the
v5e, the hot-switch schedule on four virtual devices, and ``run.py``'s
refusal to run without a TPU.

The tiny configuration, its traffic mixes and the manifest that names
them live HERE (``tests/benchmark``), not under ``benchmark/``: adding a
cell needs files and manifest entries only.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import flops, harness, stats, traffic  # noqa: E402
from benchmark import trace as trace_mod  # noqa: E402
from benchmark.peaks import peaks_for  # noqa: E402

MANIFEST = os.path.join(HERE, "manifest.json")
RECORDED = os.path.join(HERE, "data", "v5e_small.xplane.pb")


def _run(workload, *, trace=False, seconds=1.5, seed=2**31 + 11):
    import jax
    return harness.run_cell(
        harness.load_manifest(MANIFEST), ROOT, workload, seed=seed,
        seconds=seconds, trace=trace, devices=jax.devices(),
        on_chip=False, t_process=time.perf_counter())


def _check_line(line, names):
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == set(names)
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and np.isfinite(m["value"])
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    json.dumps(line)


@pytest.mark.parametrize("workload,e2e,layers", [
    ("tiny.pretrain", {"train_tokens_per_s", "setup_s"},
     {"data_wait_ms", "train_step_ms"}),
    ("tiny.chat", {"gap_p95_ms", "setup_s"},
     {"gen_late_p95_ms", "first_token_p95_ms", "first_token_mean_ms",
      "queue_wait_p95_ms", "engine_iter_ms.chat"}),
    ("tiny.backlog", {"serve_tokens_per_s", "setup_s"},
     {"kv_used_peak_pct", "engine_iter_ms.backlogs"}),
])
def test_cell_end_to_end_at_tiny_size(workload, e2e, layers):
    out = _run(workload)
    assert not out["why_incorrect"]
    _check_line(out["line"], e2e)
    assert all(m["value"] > 0 for m in out["line"]["metrics"].values())
    # the traced run reports the per-layer metrics; on the CPU there is
    # no device plane, so the device is reported idle (run.py refuses
    # such a run) and metrics that read kernels are left out
    out = _run(workload, trace=True)
    _check_line(out["line"], layers)
    assert out["line"]["device"]["busy_s"] == 0.0
    assert set(out["line"]["breakdown"]) == {"device_ops", "idle_gaps"}


def test_switch_schedule_on_four_virtual_devices():
    out = _run("tiny.switch")
    assert not out["why_incorrect"]
    _check_line(out["line"], {"train_tokens_per_s", "setup_s"})
    assert out["info"]["n_switches"] >= 2


def test_run_py_refuses_without_a_tpu(capsys):
    from benchmark import run
    rc = run.main(["--workload", "gpt2-small.pretrain", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out == ""       # no result line


def test_manifest_matches_the_files_it_names():
    """Every cell of BENCHMARK.json finds its configuration, traffic,
    runner and readers by name; each reader's constants agree with the
    manifest."""
    m = harness.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))
    assert m["command"] == ["python3", "benchmark/run.py"]
    for cell in m["workloads"]:
        cfg = next(c for c in m["configs"] if c["name"] == cell["config"])
        assert os.path.exists(os.path.join(ROOT, cfg["file"]))
        with open(harness._find(ROOT, m, "traffic",
                                f"{cell['traffic']}.json")) as f:
            mix = json.load(f)
        harness._find(ROOT, m, "runners", f"{mix['kind']}.py")
        for group in ("end_to_end", "per_layer"):
            assert harness.cell_metrics(m, cell["name"], group)
    e2e = {x["name"] for x in m["end_to_end"]}
    for x in m["end_to_end"] + m["per_layer"]:
        mod = harness.find_reader(ROOT, m, x["name"])
        assert (mod.NAME, mod.UNIT) == (x["name"], x["unit"])
        if "layer" in x:
            assert (mod.LAYER, mod.MOVES) == (x["layer"], x["moves"])
            assert x["moves"] in e2e


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([], 95) is None
    assert stats.percentile([1, 2, 3, 4], 95) == 4


def test_open_loop_arithmetic_on_synthetic_timestamps():
    reqs = [
        # sent late: TTFT still counts from the due time
        {"due": 10.0, "sent": 10.4, "token_times": [11.0, 11.1, 11.3]},
        # an event that carried two tokens: one gap and one gap of zero
        {"due": 11.0, "sent": 11.0, "token_times": [11.5, 11.5, 11.9]},
        # unfinished by the deadline: counts as deadline - due
        {"due": 12.0, "sent": 12.0, "token_times": []},
        # failed after a token: also the deadline
        {"due": 13.0, "sent": 13.1, "token_times": [13.2],
         "failed": "err"},
        # due outside the window
        {"due": 30.0, "sent": 30.0, "token_times": [30.5]},
    ]
    on = [r for r in reqs if 10.0 <= r["due"] < 20.0]
    assert len(on) == 4
    np.testing.assert_allclose(stats.ttft_samples(on, deadline=25.0),
                               [1.0, 0.5, 13.0, 12.0])
    np.testing.assert_allclose(sorted(stats.token_gaps(on)),
                               [0.0, 0.1, 0.2, 0.4], atol=1e-12)
    np.testing.assert_allclose(stats.late_samples(on),
                               [0.4, 0.0, 0.0, 0.1], atol=1e-12)
    assert stats.tokens_in_window(reqs, 11.0, 12.0) == 6
    assert stats.tokens_in_window(reqs, 0.0, 100.0) == 8


def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", "chat-open-steady.json")))
    fixed = [traffic.serve_requests(mix, vocab_size=50257, max_len=1024,
                                    horizon_s=30.0, seed=s)
             for s in (1, 2**31 + 5)]
    # the cell's schedule is the mix's own: the run's seed draws tokens
    assert [(r["due"], len(r["prompt"]), r["max_tokens"])
            for r in fixed[0]] == \
        [(r["due"], len(r["prompt"]), r["max_tokens"]) for r in fixed[1]]
    assert any((x["prompt"] != y["prompt"]).any()
               for x, y in zip(*fixed))
    del mix["schedule_seed"]            # now the run's seed orders it
    a = traffic.serve_requests(mix, vocab_size=50257, max_len=1024,
                               horizon_s=30.0, seed=1)
    b = traffic.serve_requests(mix, vocab_size=50257, max_len=1024,
                               horizon_s=30.0, seed=2**31 + 5)
    again = traffic.serve_requests(mix, vocab_size=50257, max_len=1024,
                                   horizon_s=30.0, seed=1)
    assert len(a) == len(b) == round(
        mix["arrivals"]["rate_per_s"] * 30.0)
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert all((x["prompt"] == y["prompt"]).all() and
               x["due"] == y["due"] for x, y in zip(a, again))
    lens = np.array([len(r["prompt"]) for r in a])
    assert 32 <= lens.min() and lens.max() <= 768
    assert abs(np.median(lens) - 256) <= 16
    assert all(len(r["prompt"]) + r["max_tokens"] <= 1024 for r in a)
    due = np.array([r["due"] for r in a])
    assert (np.diff(due) >= 0).all() and abs(due[-1] - 30.0) < 2.0


def test_flops_and_peaks():
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "gpt2-small.json")))
    # 12 layers x 12 x 768^2 + 50257 x 768
    assert flops.matmul_params(cfg) == 84934656 + 38597376
    per_tok = flops.train_flops_per_token(cfg, 512.0)
    assert per_tok == 6 * flops.matmul_params(cfg) \
        + 12 * 768 * 512 * 12
    call = flops.flash_train_call(cfg, rows=2, seq_len=1024,
                                  pairs_per_row=1024 * 1025 / 2)
    assert call["bwd_flops"] == 2 * call["fwd_flops"]
    assert call["fwd_bytes"] == 4 * 2 * 2 * 1024 * 768
    v5e = peaks_for("TPU v5 lite")
    assert flops.roofline_seconds(197e12, 0, v5e) == pytest.approx(1.0)
    assert flops.roofline_seconds(0, 819e9, v5e) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks_for("cpu")


def test_reference_agrees_with_the_model_on_packed_rows():
    """The plain float32 reference against ``GPTLMHeadModel`` (float32,
    reference attention) on packed rows: the two are independent
    implementations of one model."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import gpt2 as reference
    from benchmark.model import gpt_config
    from hetu_tpu.data import build_data_loader
    from hetu_tpu.models import GPTLMHeadModel

    config = json.load(open(os.path.join(HERE, "configs",
                                         "gpt2-tiny.json")))
    mix = json.load(open(os.path.join(HERE, "traffic",
                                      "tiny-pretrain.json")))
    model = GPTLMHeadModel(gpt_config(config))
    params = model.init(jax.random.key(3))
    corpus = traffic.Corpus(mix, vocab_size=256, seed=5)
    b = next(iter(build_data_loader(corpus, seq_len=64, batch_rows=4,
                                    pack=True, seed=5)))
    want = reference.loss(params, b["input_ids"], b["labels"],
                          n_head=4, positions=b["positions"],
                          segment_ids=b["segment_ids"])
    got = model.loss(params, jnp.asarray(b["input_ids"]),
                     jnp.asarray(b["labels"]),
                     positions=jnp.asarray(b["positions"]),
                     segment_ids=jnp.asarray(b["segment_ids"]),
                     attn_impl="reference")
    # float32 both sides, "highest" matmuls (tests/conftest.py)
    assert abs(float(got) - float(want)) < 1e-5


def test_trace_reduction_on_the_recorded_v5e_trace():
    """``data/v5e_small.xplane.pb`` was recorded on the v5e by
    ``data/record_trace.py`` (PR 23): a jitted chain of three matmul
    fusions called in a loop under ``bench:call`` spans with a
    ``bench:sleep`` of 20 ms after each call. Three of the calls fall
    inside the traced window."""
    r = trace_mod.reduce_trace(RECORDED)
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(0.086947294, rel=1e-6)
    assert r["busy_s"] == pytest.approx(1.0739e-4, rel=1e-4)
    # device events are named by the whole HLO instruction; the
    # reduction keeps the instruction's name
    assert [n for n, _ in r["device_ops"]][:3] == \
        ["fusion", "fusion.1", "fusion.2"]
    assert all(r["op_calls"][n] == 3
               for n in ("fusion", "fusion.1", "fusion.2"))
    assert r["op_text"]["fusion.1"].startswith("%fusion.1 = bf16[1024,")
    assert sum(r["op_seconds"].values()) == pytest.approx(
        r["busy_s"], rel=1e-3)
    # the device sits idle while the host sleeps, and the host span
    # that covers those gaps names them
    name, seconds = r["idle_gaps"][0]
    assert name == "bench:sleep" and 0.06 < seconds < r["window_s"]
    assert r["busy_s"] + sum(s for _, s in r["idle_gaps"]) == \
        pytest.approx(r["window_s"], rel=1e-6)
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10


def test_self_time_takes_nested_operations_out():
    # a while of 10 holding two body operations of 3 and 4
    s = np.array([0.0, 1.0, 5.0, 20.0])
    e = np.array([10.0, 4.0, 9.0, 22.0])
    assert trace_mod._self_seconds(s, e).tolist() == [3.0, 3.0, 4.0, 2.0]
    assert trace_mod.short_name(
        "%while.7 = (s32[], bf16[2]) while(...)") == "while.7"


def test_union_of_overlapping_intervals():
    s = np.array([0.0, 5.0, 2.0, 20.0])
    e = np.array([4.0, 9.0, 6.0, 21.0])
    busy, ms, me = trace_mod._union_seconds(s, e)
    assert busy == 10.0
    assert ms.tolist() == [0.0, 20.0] and me.tolist() == [9.0, 21.0]
