"""Driver-artifact guards: bench.py must always emit its JSON line and
__graft_entry__ must expose working entry points — these are what the
round driver runs; regressions here erase a round's evidence."""

import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_emits_json_contract():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"   # the CPU smoke, asked for
    r = subprocess.run([sys.executable, os.path.join(_ROOT, "bench.py")],
                       capture_output=True, text=True, timeout=300,
                       env=env, cwd=_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    line = r.stdout.strip().splitlines()[-1]
    rec = json.loads(line)
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in rec, (key, rec)
    assert rec["value"] > 0


def test_bench_serving_emits_json_contract(tmp_path):
    """``bench.py --serving`` must emit the offered-load sweep headline
    and write BENCH_serving.json (the serving-plane round evidence) —
    plus BENCH_spec.json, the speculation + QoS evidence (ISSUE 11):
    tokens-per-slot-step > 1 at high draft acceptance, the
    iteration-normalized TPOT improving monotonically with acceptance,
    and a preempt→spill→resume probe that lost nothing."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py"), "--serving"],
        capture_output=True, text=True, timeout=500, env=env, cwd=_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    for key in ("metric", "value", "unit", "sweep"):
        assert key in rec, (key, rec)
    assert rec["value"] > 0
    assert len(rec["sweep"]) >= 2
    for row in rec["sweep"]:
        for key in ("offered", "tokens_per_sec", "ttft_p50_ms",
                    "ttft_p99_ms", "occupancy_mean"):
            assert key in row, (key, row)
    with open(os.path.join(_ROOT, "BENCH_serving.json")) as f:
        assert json.load(f) == rec

    with open(os.path.join(_ROOT, "BENCH_spec.json")) as f:
        spec = json.load(f)
    assert spec["spec_depth"] >= 2
    rows = sorted(spec["sweep"], key=lambda s: s["acceptance_rate"])
    assert len(rows) >= 3
    # the adversarial floor commits exactly the non-speculative rate;
    # tokens/slot-step rises monotonically with acceptance and beats 1
    # where drafts land (acceptance-weighted — the fused step did the
    # extra tokens' work inside the same iteration)
    assert rows[0]["acceptance_rate"] == 0.0
    assert rows[0]["tokens_per_slot_step"] == 1.0
    for a, b in zip(rows, rows[1:]):
        assert b["acceptance_rate"] > a["acceptance_rate"], rows
        assert b["tokens_per_slot_step"] >= a["tokens_per_slot_step"]
        # iteration-normalized TPOT (slot-steps per token) improves
        # monotonically with acceptance — the wall-clock TPOT column
        # rides along but is not asserted (CPU-smoke noise)
        assert b["slot_steps_per_token"] <= a["slot_steps_per_token"]
    assert rows[-1]["tokens_per_slot_step"] > 1.2, rows
    # ISSUE 17: the temperature axis — sampled speculation through the
    # rejection-sampling verify lane still LANDS drafts (model
    # draftsman, q == p ceiling): every nonzero-temperature row beats
    # 1.0 tokens/slot-step with the sampled-lane counters flowing
    temps = spec["temperature_sweep"]
    assert {r["label"] for r in temps} >= {"greedy", "T=0.7", "T=1.0"}
    for row in temps:
        if row["temperature"] > 0:
            assert row["tokens_per_slot_step"] > 1.0, row
            assert row["sampled_accepted"] > 0, row
        else:
            assert row["sampled_accepted"] == 0, row
    probe = spec["preemption_probe"]
    assert probe["preemptions"] >= 1
    assert probe["spilled_blocks"] >= 1
    assert probe["resumed_blocks"] == probe["spilled_blocks"]
    assert probe["tokens_match_undisturbed"] is True


@pytest.mark.slow
def test_bench_router_emits_json_contract():
    """``bench.py --router`` must emit the fleet sweep headline and
    write BENCH_router.json with the zero-downtime weight-push
    evidence (the fleet-plane round artifact)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py"), "--router"],
        capture_output=True, text=True, timeout=500, env=env, cwd=_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    for key in ("metric", "value", "unit", "replicas", "sweep",
                "weight_push"):
        assert key in rec, (key, rec)
    assert rec["value"] > 0 and rec["replicas"] >= 2
    for row in rec["sweep"]:
        for key in ("offered", "tokens_per_sec", "ttft_p50_ms",
                    "dispatch", "dispatch_balance"):
            assert key in row, (key, row)
    push = rec["weight_push"]
    assert push["trickle_rejected"] == 0
    assert push["trickle_completed"] == push["trickle_submitted"]
    assert push["capacity_floor"] >= 1      # peers absorbed the drain
    assert push["downtime_steps"] == 0
    with open(os.path.join(_ROOT, "BENCH_router.json")) as f:
        assert json.load(f) == rec


@pytest.mark.slow
def test_bench_ragged_emits_json_contract():
    """``bench.py --ragged`` must emit the shape-plane sweep and write
    BENCH_ragged.json with pad fraction and REAL-token throughput
    improving monotonically pad-to-max -> bucketed -> bucketed+packed,
    the per-config compile counts bounded by the ladder, and the
    long-prompt probe served through the CP lane (the shape-plane round
    evidence)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py"), "--ragged"],
        capture_output=True, text=True, timeout=500, env=env, cwd=_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    for key in ("metric", "value", "unit", "sweep", "long_prompt_probe",
                "ladder"):
        assert key in rec, (key, rec)
    labels = [s["label"] for s in rec["sweep"]]
    assert labels == ["pad_to_max", "bucketed", "bucketed_packed"]
    pads = [s["pad_fraction"] for s in rec["sweep"]]
    tps = [s["real_tokens_per_sec"] for s in rec["sweep"]]
    assert pads[0] > pads[1] > pads[2], pads     # padding tax falls...
    # ...and real-token throughput rises. The pad ordering is
    # deterministic; the timing comparison needs noise margin (tiny CPU
    # steps on a loaded CI box), so assert each discipline beats the
    # pad-to-max baseline by a wide factor (the committed smoke shows
    # 4.8x / 6.0x) instead of a strict bucketed-vs-packed ordering.
    assert tps[1] > 1.5 * tps[0], tps
    assert tps[2] > 1.5 * tps[0], tps
    assert rec["sweep"][0]["compiles"] == 1      # pad-to-max: 1 shape
    for s in rec["sweep"][1:]:
        assert 1 <= s["compiles"] <= len(rec["ladder"]), s
    probe = rec["long_prompt_probe"]
    assert probe["status"] == "done"             # served, not rejected
    assert probe["prompt_len"] > probe["slot_max_len"]
    assert probe["serving_step_compiles"] == 1
    assert probe["cp_prefill_compiles"] <= len(probe["lane_buckets"])
    assert probe["ttft_ms"] is not None and probe["ttft_ms"] > 0
    with open(os.path.join(_ROOT, "BENCH_ragged.json")) as f:
        assert json.load(f) == rec


@pytest.mark.slow
def test_bench_chaos_emits_json_contract():
    """``bench.py --chaos`` must emit the recovery-discipline sweep and
    write BENCH_chaos.json: three modes, each surviving two kills driven
    through the real heartbeat/membership path, with the live modes
    reading NOTHING from disk, every discipline converging to the SAME
    final loss (recovery is lossless), and async+delta checkpointing
    blocking the loop measurably less than sync full saves."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py"), "--chaos"],
        capture_output=True, text=True, timeout=580, env=env, cwd=_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    for key in ("metric", "value", "unit", "sweep", "kills_per_run"):
        assert key in rec, (key, rec)
    assert rec["value"] > 0 and rec["kills_per_run"] == 2
    modes = [s["mode"] for s in rec["sweep"]]
    assert modes == ["restart_from_disk", "live_reshard",
                     "live_reshard_delta_async"]
    by = {s["mode"]: s for s in rec["sweep"]}
    for s in rec["sweep"]:
        assert s["kills"] == 2 and s["recoveries"] == 2, s
        assert 0 < s["goodput"] <= 1
        assert s["detect_s_mean"] > 0
    assert by["restart_from_disk"]["recovery_modes"] == ["disk", "disk"]
    assert by["restart_from_disk"]["disk_loads"] == 2
    for m in ("live_reshard", "live_reshard_delta_async"):
        assert by[m]["recovery_modes"] == ["live", "live"]
        assert by[m]["disk_loads"] == 0          # never touched disk
    # recovery is lossless: every discipline lands on the same loss
    finals = {s["final_loss"] for s in rec["sweep"]}
    assert len(finals) == 1, rec["sweep"]
    assert all(s["final_step"] == by["live_reshard"]["final_step"]
               for s in rec["sweep"])
    # the whole point of snapshot-then-write + delta: the loop blocks
    # less per save than the sync full-save discipline
    assert by["live_reshard_delta_async"]["checkpoint_s"] \
        < 0.8 * by["live_reshard"]["checkpoint_s"], by
    assert by["live_reshard_delta_async"]["ckpt_reused_bytes"] > 0
    # fleet soak (ISSUE 15): periodic ChaosMonkey SIGKILLs against the
    # MULTI-PROCESS serving fleet — zero lost/duplicated/corrupted
    soak = rec["fleet_soak"]
    assert soak["kills"] >= 1 and soak["submitted"] > 0
    assert soak["lost"] == 0 and soak["corrupted"] == 0
    assert soak["completed"] == soak["submitted"]
    assert set(soak["dead"]) <= {"r1", "r2"}     # r0 always survives
    with open(os.path.join(_ROOT, "BENCH_chaos.json")) as f:
        assert json.load(f) == rec


@pytest.mark.slow
def test_bench_moe_emits_json_contract():
    """``bench.py --moe`` must emit the expert-plane headline and write
    BENCH_moe.json with the serialized-vs-chunked and eager-vs-delayed
    evidence (the expert-plane round artifact)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py"), "--moe"],
        capture_output=True, text=True, timeout=500, env=env, cwd=_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    for key in ("metric", "value", "unit", "overlap", "delayed_sync",
                "expert_balance"):
        assert key in rec, (key, rec)
    assert rec["value"] > 0 and rec["ep"] > 1
    ov = rec["overlap"]
    assert ov["loss_bitwise_equal"] is True
    assert ov["ep_a2a_bytes_per_trace"] > 0
    assert ov["ep_a2a_overlapped_frac"] == 1.0
    ds = rec["delayed_sync"]
    assert ds["eager_syncs_per_update"] > 1.0   # nm per update
    assert ds["delayed_syncs_per_update"] == 1.0
    bal = rec["expert_balance"]
    assert sum(bal["expert_load"]) > 0
    with open(os.path.join(_ROOT, "BENCH_moe.json")) as f:
        assert json.load(f) == rec


@pytest.mark.slow
def test_bench_kernels_emits_json_contract():
    """``bench.py --kernels`` must emit the kernel-plane microbench and
    write BENCH_kernels.json: the paged-vs-reference decode sweep over
    slots×block_size (parity green, gather-tax byte ratio > 1), the
    packed flash-vs-reference prefill parity, and the W8A8-vs-W8A16 FFN
    comparison — the CPU smoke runs the Pallas kernels in interpret
    mode (schema in place for the real-TPU measurement-debt run)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py"), "--kernels"],
        capture_output=True, text=True, timeout=560, env=env, cwd=_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    for key in ("metric", "value", "unit", "decode_sweep", "prefill",
                "w8a8", "interpret", "device"):
        assert key in rec, (key, rec)
    assert rec["value"] > 1          # the gather tax is real
    assert rec["unit"] == "x_hbm_read_bytes"
    assert len(rec["decode_sweep"]) >= 4
    for row in rec["decode_sweep"]:
        assert row["parity_ok"] is True, row
        assert row["hbm_bytes_reference"] > row["hbm_bytes_paged"]
        assert row["hbm_bytes_ratio"] > 1
    assert rec["prefill"]["parity_ok"] is True
    assert rec["w8a8"]["max_rel_err"] < 0.05
    # all three lanes timed — plus the ISSUE 17 pre-quantized lane
    # (weights int8-quantized ONCE at engine construction: the per-step
    # weight-prep cost disappears from the decode path)
    for k in ("fp32_ms", "w8a16_ms", "w8a8_ms", "w8a8_prequant_ms"):
        assert rec["w8a8"][k] > 0
    assert rec["w8a8"]["prequant_max_rel_err"] < 0.05
    assert rec["w8a8"]["weight_prep_saved_ms"] >= 0
    with open(os.path.join(_ROOT, "BENCH_kernels.json")) as f:
        assert json.load(f) == rec


@pytest.mark.slow
def test_bench_fleet_emits_json_contract():
    """SATELLITE (ISSUE 15): ``python bench.py --fleet`` must exit 0
    and write BENCH_fleet.json: in-process vs multi-process dispatch
    overhead (all requests completing through the coordinator verbs)
    and the colocated vs P/D-split comparison with KV blocks actually
    streamed prefill→decode. ISSUE 18 folds in the fleet-KV sweep:
    the shared-prefix lanes (directory pull on/off) and the SIGKILL
    recovery lanes (buddy replication on/off)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py"), "--fleet"],
        capture_output=True, text=True, timeout=840, env=env, cwd=_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    for key in ("metric", "value", "unit", "in_process",
                "multi_process", "pd", "fleet_kv", "recovery"):
        assert key in rec, (key, rec)
    offered = rec["offered"]
    # every lane completed its whole offered load — the transport works
    assert rec["in_process"]["completed"] == offered
    assert rec["multi_process"]["completed"] == offered
    assert rec["pd"]["colocated"]["completed"] == offered
    assert rec["pd"]["split"]["completed"] == offered
    # the split lane really streamed KV (one handoff per request)
    assert rec["pd"]["split"]["pd_handoffs"] >= offered
    assert rec["pd"]["split"]["kv_stream_blocks"] >= offered
    for lane in (rec["in_process"], rec["multi_process"],
                 rec["pd"]["colocated"], rec["pd"]["split"]):
        assert lane["total_ms_p50"] > 0
    # ISSUE 16: the multi-process lane records its transport/compute
    # split per verb from the RPC wire instrumentation
    rpc = rec["multi_process"]["rpc"]
    assert rpc["client_verb_ms_total"] > 0
    assert "SUBMIT" in rpc["verbs"], rpc["verbs"]
    for verb, row in rpc["verbs"].items():
        assert row["count"] > 0 and row["ms_total"] >= 0, (verb, row)
    assert rpc["empty_polls"] >= 0
    frac = rpc["empty_poll_fraction"]
    assert frac is None or 0.0 <= frac <= 1.0
    # ISSUE 18: the fleet-KV shared-prefix lanes. Both complete the
    # whole load; with the directory on, the drained owner's prefix
    # really travelled (blocks pulled, hit tokens counted) and the off
    # lane pulled nothing — the delta the warm-TTFT column measures.
    warm, cold = rec["fleet_kv"]["pull_on"], rec["fleet_kv"]["pull_off"]
    assert warm["completed"] == 8 and cold["completed"] == 8
    assert warm["pull_blocks"] > 0 and warm["prefix_hit_tokens"] > 0
    assert cold["pull_blocks"] == 0 and cold["prefix_hit_tokens"] == 0
    assert warm["pull_bytes"] > 0
    # ISSUE 18: SIGKILL recovery lanes — zero lost requests either way
    # (the router's requeue contract); recovery times recorded
    ron, roff = rec["recovery"]["replicate_on"], \
        rec["recovery"]["replicate_off"]
    assert ron["completed"] == 6 and roff["completed"] == 6
    assert ron["recovery_s"] > 0 and roff["recovery_s"] > 0
    assert ron["resumed"] >= ron["kv_recoveries"] >= 0
    with open(os.path.join(_ROOT, "BENCH_fleet.json")) as f:
        assert json.load(f) == rec


@pytest.mark.slow
def test_bench_tenants_emits_json_contract():
    """SATELLITE (ISSUE 20): ``python bench.py --tenants`` must exit 0
    and write BENCH_tenants.json: mixed-tenant decode throughput vs the
    base engine (TPOT overhead of the batched-LoRA lane), adapter
    hot-swap latency under a live request trickle with nothing
    rejected, and the noisy-neighbor isolation lane where the bulk
    tenant's slot cap actually throttles."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py"), "--tenants"],
        capture_output=True, text=True, timeout=600, env=env, cwd=_ROOT)
    assert r.returncode == 0, r.stderr[-2000:]
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    for key in ("metric", "value", "unit", "tenants", "rank", "base",
                "mixed", "tpot_overhead", "adapter_swap", "isolation"):
        assert key in rec, (key, rec)
    assert rec["base"]["tokens_per_sec"] > 0
    assert rec["mixed"]["tokens_per_sec"] > 0
    assert rec["tpot_overhead"] > 0
    # hot-swap lane: every push landed, and the live trickle kept
    # flowing — a version push never rejects an in-flight tenant
    swap = rec["adapter_swap"]
    assert swap["pushes"] >= 1 and swap["p50_ms"] > 0
    assert swap["trickle_completed"] == swap["trickle_submitted"]
    assert swap["trickle_rejected"] == 0
    # isolation lane: the bulk flood was really throttled by its slot
    # cap, yet every bulk request still completed (deferred, not shed)
    iso = rec["isolation"]
    assert iso["alone_p50_ms"] > 0 and iso["noisy_p50_ms"] > 0
    assert iso["bulk_completed"] == iso["bulk_offered"]
    assert iso["bulk_throttled_events"] >= 1
    with open(os.path.join(_ROOT, "BENCH_tenants.json")) as f:
        assert json.load(f) == rec


def test_graft_entry_fn_runs():
    import jax
    sys.path.insert(0, _ROOT)
    import __graft_entry__ as g
    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[0] == args[1].shape[0]
    assert bool(jax.numpy.isfinite(out).all())


def test_dryrun_multichip_smoke():
    """The driver's multichip validation, in a FRESH process — exactly
    how the driver invokes it. (In-process after a long test session it
    deadlocks: accumulated executables starve the single-core CPU
    backend's collective rendezvous permanently — see
    cpu-collective-rendezvous notes; the driver never runs it that
    way.)"""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)           # dryrun sets its own
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8)"],
        capture_output=True, text=True, timeout=900, env=env, cwd=_ROOT)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-1500:])
    assert r.stdout.count(" ok") >= 10, r.stdout


def test_sweep_infeasible_table_guards(tmp_path):
    """mfu_sweep's AOT-feasibility skip: only 'fits: false' rows at the
    SAME seq are trusted; anything else (other seq, torn file, fits
    null) must not suppress a measurement."""
    import json
    from workloads.mfu_sweep import _load_infeasible

    p = tmp_path / "sweep_feasible.json"
    p.write_text(json.dumps({"seq": 1024, "rows": {
        "64:selective:1:fp32": {"fits": False},
        "32:selective:1:fp32": {"fits": True},
        "48:selective:1:fp32": {"fits": None, "error": "x"}}}))
    assert _load_infeasible(1024, str(p)) == {"64:selective:1:fp32"}
    assert _load_infeasible(2048, str(p)) == set()      # other seq
    p.write_text("{torn")
    assert _load_infeasible(1024, str(p)) == set()      # torn file
    assert _load_infeasible(1024, str(tmp_path / "no.json")) == set()


def test_calibration_anchor_follows_recorded_config(tmp_path):
    """aot_calibrate's roofline anchor must reproduce the exact config
    the recorded headline measured (a combo-adopted b48/bf16/fused
    record must not be anchored with b32/fp32 flops)."""
    sys.path.insert(0, _ROOT)
    from workloads.aot_calibrate import (_ANCHOR_CFG_FALLBACK,
                                         _anchor_measured_ms)

    # no record -> full fallback config
    ms0, _, cfg0 = _anchor_measured_ms(str(tmp_path / "missing.json"))
    assert cfg0 == _ANCHOR_CFG_FALLBACK and ms0 > 0
    # a record WITH a config: every field must surface
    rec = {"step_time_ms": 123.0, "device": "TPU v5 lite",
           "config": {"batch": 48, "remat": "selective", "unroll": True,
                      "param_dtype": "bf16", "ce": "fused",
                      "attn": "auto"}}
    p = tmp_path / "last_tpu_bench.json"
    with open(p, "w") as f:
        json.dump(rec, f)
    ms2, _, cfg2 = _anchor_measured_ms(str(p))
    assert ms2 == 123.0
    assert cfg2["batch"] == 48 and cfg2["param_dtype"] == "bf16" \
        and cfg2["ce"] == "fused"
    # an OLD record without a config: builtin default, recorded time
    with open(p, "w") as f:
        json.dump({"step_time_ms": 77.0}, f)
    ms3, _, cfg3 = _anchor_measured_ms(str(p))
    assert ms3 == 77.0 and cfg3 == _ANCHOR_CFG_FALLBACK


def test_bench_refuses_without_tpu_or_explicit_cpu(monkeypatch):
    """bench.py takes the device JAX gives its one process: with no TPU
    and no JAX_PLATFORMS=cpu from the caller it exits non-zero — it
    never falls back to a CPU smoke by itself. In-process on a fake
    device: a child without the variable would take a real chip where
    there is one and run the benchmark."""
    sys.path.insert(0, _ROOT)
    import types

    import bench

    def gives(platform):
        monkeypatch.setattr(bench.jax, "devices", lambda: [
            types.SimpleNamespace(platform=platform)])

    gives("cpu")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as e:
        bench.bench_device()
    assert e.value.code not in (0, None)
    with pytest.raises(SystemExit) as e:
        bench.cpu_sim_device("--fleet")
    assert e.value.code not in (0, None)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert bench.bench_device()[1] is False
    gives("tpu")
    assert bench.bench_device()[1] is True
