"""Program-given names on both sides of the dispatch (CPU, tier 1):
``hetu.*`` device scopes read back from optimized HLO, ``hetu:`` host
spans on the profiler's clock, compile events, metric handles bound
once."""

import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu import optim, telemetry
from hetu_tpu.engine import trace_counts
from hetu_tpu.engine.trainer import Trainer, TrainerConfig
from hetu_tpu.models import GPTConfig, GPTLMHeadModel
from hetu_tpu.parallel.strategy import Strategy
from hetu_tpu.serving import ServingEngine
from hetu_tpu.serving.scheduler import SamplingParams
from hetu_tpu.telemetry import device_scopes

CFG = GPTConfig.tiny()


@pytest.fixture
def telem():
    telemetry.reset()
    telemetry.enable(True)
    yield telemetry
    telemetry.enable(False)
    telemetry.reset()


@pytest.fixture
def fresh_scopes():
    device_scopes.clear_registered()
    yield device_scopes
    device_scopes.clear_registered()


def _trainer(**cfg):
    opt = optim.chain(optim.clip_by_global_norm(1.0), optim.adamw(1e-3))
    return Trainer(GPTLMHeadModel(CFG), opt, Strategy(remat="selective"),
                   devices=jax.devices()[:1],
                   config=TrainerConfig(total_steps=100, log_every=1,
                                        precision="bf16", **cfg))


def _batches(n, b=4, s=64):
    for i in range(n):
        ids = np.asarray(jax.random.randint(
            jax.random.key(i), (b, s + 1), 0, CFG.vocab_size))
        yield {"input_ids": ids[:, :-1], "labels": ids[:, 1:]}


def _engine(**kw):
    model = GPTLMHeadModel(CFG)
    kw.setdefault("attn_kernel", "paged")
    kw.setdefault("prefill_attn", "flash_pallas")
    return ServingEngine(model, model.init(jax.random.key(0)), slots=4,
                         max_len=64, prefill_chunk=16, block_size=8,
                         **kw)


# -- the rule ---------------------------------------------------------------
@pytest.mark.parametrize("op_name,label,phase,remat", [
    ("jit(step)/jvp(hetu.loss)/tanh", "hetu.loss.fwd", "fwd", False),
    ("jit(step)/transpose(jvp(hetu.loss))/jvp(hetu.loss)/checkpoint/"
     "rematted_computation/dot_general", "hetu.loss.bwd", "bwd", True),
    ("jit(step)/transpose(jvp(hetu.loss))/mul", "hetu.loss.bwd", "bwd",
     False),
    ("jit(step)/jvp(hetu.loss)/while/body/hetu.flash_fwd/"
     "hetu_flash_fwd", "hetu.flash_fwd", "fwd", False),
    ("jit(step)/transpose(jvp(hetu.loss))/while/body/hetu.flash_bwd/"
     "hetu_flash_bwd_dq", "hetu.flash_bwd", "bwd", False),
    ("jit(step)/hetu.opt/sub", "hetu.opt", None, False),
    ("jit(step)/cond/branch_1_fun/hetu.decode_lane/while/body/"
     "hetu.kv_arena/scatter", "hetu.kv_arena", None, False),
    ("jit(step)/add", "unscoped", None, False),
    ("", "unscoped", None, False),
])
def test_classify_rule(op_name, label, phase, remat):
    sc = device_scopes.classify(op_name)
    assert (sc.label, sc.phase, sc.remat) == (label, phase, remat)
    assert sc.scope == (sc.path[-1] if sc.path else "unscoped")


def test_fusion_without_metadata_is_its_roots():
    hlo = """HloModule jit_f

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %tanh.1 = f32[8]{0} tanh(%p), metadata={op_name="jit(f)/hetu.opt/tanh"}
}

ENTRY %main.3 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation
  ROOT %copy.2 = f32[8]{0} copy(%fusion), metadata={op_name="jit(f)/hetu.sample/copy"}
}
"""
    got = device_scopes.scopes_of(hlo)
    assert got["fusion"] == "hetu.opt" and got["tanh.1"] == "hetu.opt"
    assert got["copy.2"] == "hetu.sample" and got["a"] == "unscoped"


def test_a_compiler_made_op_is_its_consumers():
    """The TPU compiler rewrites ``jax.lax.ragged_dot`` into custom
    calls named after themselves (``op_name="ragged-dot-none"``: the
    program's name stack is gone): they take the scope of their first
    consumer that has one — through a get-tuple-element too. An op with
    a name stack and no ``hetu.`` in it stays unscoped."""
    hlo = """HloModule jit_f

ENTRY %main.9 (x: bf16[8,4], w: bf16[2,4,4], gs: s32[2]) -> f32[8,4] {
  %x = bf16[8,4]{1,0} parameter(0)
  %w = bf16[2,4,4]{2,1,0} parameter(1)
  %gs = s32[2]{0} parameter(2)
  %ragged-dot-metadata = (s32[3]{0}, s32[1]{0}) custom-call(%gs), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %get-tuple-element = s32[1]{0} get-tuple-element(%ragged-dot-metadata), index=1
  %ragged-dot-none = f32[8,4]{1,0} custom-call(%get-tuple-element, %x, %w), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %convert.1 = f32[8,4]{1,0} convert(%x), metadata={op_name="jit(f)/convert_element_type"}
  ROOT %multiply.2 = f32[8,4]{1,0} multiply(%ragged-dot-none, %convert.1), metadata={op_name="jit(f)/hetu.decode_lane/hetu.moe_experts/mul"}
}
"""
    got = device_scopes.describe(hlo)
    assert got["ragged-dot-none"].label == "hetu.moe_experts"
    assert got["ragged-dot-none"].path == ("hetu.decode_lane",
                                           "hetu.moe_experts")
    assert got["ragged-dot-metadata"].label == "hetu.moe_experts"
    assert got["convert.1"].label == "unscoped"


# -- the compiled steps -------------------------------------------------------
def test_train_step_scopes_and_lazy_map(fresh_scopes):
    tr = _trainer(attn_impl="pallas")
    traces0 = trace_counts().get("train_step", 0)
    h = tr.precompile([tr.strategy], batch_shape=(4, 64), block=True)
    assert all(r.ok for r in h.results), [r.error for r in h.results]
    assert [k[0] for k in fresh_scopes.registered_steps()] == \
        ["train_step"]
    assert not fresh_scopes._PARSED          # nothing fetched or parsed
    traces = trace_counts().get("train_step", 0)
    assert traces == traces0 + 1
    (scopes,) = fresh_scopes.registered_scopes().values()
    assert trace_counts().get("train_step", 0) == traces   # no re-trace
    labels = {s.label for s in scopes.values()}
    assert {"hetu.loss.fwd", "hetu.loss.bwd", "hetu.opt",
            "hetu.flash_fwd", "hetu.flash_bwd", "unscoped"} <= labels
    # kernels sit in their phase; a remat replay counts as backward
    assert {s.phase for s in scopes.values()
            if s.scope == "hetu.flash_bwd"} == {"bwd"}
    assert "fwd" in {s.phase for s in scopes.values()
                     if s.scope == "hetu.flash_fwd"}
    replays = [s for s in scopes.values() if s.remat]
    assert replays and all(s.phase == "bwd" for s in replays)
    assert not any(s.phase for s in scopes.values()
                   if s.label == "hetu.opt")
    tr.close()


def test_serving_step_scopes_without_retrace(fresh_scopes):
    eng = _engine()
    traces0 = trace_counts().get("serving_step", 0)
    req = eng.submit(list(range(1, 20)), SamplingParams(max_tokens=4))
    eng.run_until_drained()
    assert len(req.tokens) == 4
    assert fresh_scopes.registered_steps() == [("serving_step", None)]
    assert not fresh_scopes._PARSED
    scopes = fresh_scopes.registered_scopes()[("serving_step", None)]
    # asking re-ran no Python body and added no executable
    assert trace_counts().get("serving_step", 0) == traces0 + 1
    assert eng.step_executables() == 1
    owned = {s.scope for s in scopes.values()}
    assert {"hetu.prefill_lane", "hetu.decode_lane", "hetu.kv_arena",
            "hetu.sample", "hetu.paged_attn"} <= owned
    paths = {s.path for s in scopes.values()}
    assert ("hetu.decode_lane", "hetu.paged_attn") in paths
    assert ("hetu.prefill_lane", "hetu.paged_attn") in paths
    assert ("hetu.decode_lane", "hetu.sample") in paths
    assert ("hetu.prefill_lane", "hetu.kv_arena") in paths
    # a second engine re-registers under the same name: newest wins
    eng2 = _engine()
    eng2.submit([1, 2, 3], SamplingParams(max_tokens=2))
    eng2.run_until_drained()
    assert fresh_scopes.registered_steps() == [("serving_step", None)]
    assert not fresh_scopes._PARSED


# -- host spans ---------------------------------------------------------------
SERVE_CHILDREN = ["serve/admit", "serve/pack", "serve/dispatch",
                  "serve/device_wait", "serve/commit", "serve/pump",
                  "serve/account"]


def test_engine_emits_serve_spans_in_order(telem):
    eng = _engine(spec_depth=2)
    req = eng.submit([5, 6, 7, 5, 6, 7, 5, 6, 7, 5, 6, 7, 5, 6, 7, 5, 6,
                      7, 5, 6], SamplingParams(max_tokens=6))
    assert eng.step() is True
    eng.run_until_drained()
    assert eng.step() is False               # an idle turn records nothing
    evs = [e for e in telem.get_tracer().events()
           if e.name.startswith("serve/")]
    steps = [e for e in evs if e.name == "serve/step"]
    assert [e.attrs["iter"] for e in steps] == \
        list(range(1, len(steps) + 1))
    assert steps[0].attrs["prefill_tokens"] == 16
    assert steps[0].attrs["active"] == 0 and steps[-1].attrs["active"]
    for st in steps:
        kids = [e for e in evs if e.depth == st.depth + 1
                and st.ts_s <= e.ts_s and
                e.ts_s + e.dur_s <= st.ts_s + st.dur_s + 1e-9]
        names = [e.name for e in sorted(kids, key=lambda e: e.ts_s)]
        assert [n for n in names if n != "serve/draft"] == SERVE_CHILDREN
        if "serve/draft" in names:           # between admit and pack
            assert names.index("serve/draft") == 1
    assert any(e.name == "serve/draft" for e in evs)   # speculation ran
    # the request's chunks name the step that ran them
    chunks = [e for e in telem.get_tracer().events()
              if e.name == "prefill_chunk"]
    assert [e.attrs["iter"] for e in chunks] == req.chunk_iters == [1, 2]
    assert not any("token" in e.name for e in evs)     # none per token


def test_preemption_emits_an_aux_span(telem):
    eng = _engine(prefix_cache=False)
    lo = [eng.submit(list(range(1, 12)),
                     SamplingParams(max_tokens=40, priority=0))
          for _ in range(4)]
    for _ in range(4):
        eng.step()
    eng.submit(list(range(1, 12)),
               SamplingParams(max_tokens=4, priority=9))
    eng.run_until_drained()
    aux = [e.attrs["what"] for e in telem.get_tracer().events()
           if e.name == "serve/aux"]
    if sum(r.preemptions for r in lo):
        assert "spill" in aux and "resume" in aux
    else:                                    # nothing had to be evicted
        assert aux == []


def test_trainer_emits_train_spans(telem):
    tr = _trainer()
    tr.train(_batches(3), 3)
    evs = telem.get_tracer().events()
    steps = [e for e in evs if e.name == "train/step"]
    assert [e.attrs["step"] for e in steps] == [1, 2, 3]
    for name in ("train/next_batch", "train/dispatch",
                 "train/loss_fetch"):
        kids = [e for e in evs if e.name == name]
        assert len(kids) == 3 and all(e.depth == steps[0].depth + 1
                                      for e in kids)
    # a loader that ends early leaves one step span marked, not counted
    telem.get_tracer().clear()
    tr.train(_batches(2), 5)
    steps = [e for e in telem.get_tracer().events()
             if e.name == "train/step"]
    assert [bool(e.attrs.get("exhausted")) for e in steps] == \
        [False, False, True]
    tr.close()


def test_spans_land_in_the_xplane_host_plane(tmp_path):
    """Recorded on the CPU while a jax.profiler trace runs, read back
    with ProfileData: ``hetu:<name>``, children nested — with the tracer
    DISABLED (the bare annotation) and enabled alike."""
    from jax.profiler import ProfileData
    telemetry.enable(False)
    f = jax.jit(lambda x: x * 2 + 1)
    f(jnp.ones(8)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for on in (False, True):
            telemetry.enable(on)
            with telemetry.span("outer_" + str(on), k=1) as sp:
                sp.set(extra=2)
                with telemetry.span("inner_" + str(on)):
                    f(jnp.ones(8)).block_until_ready()
    finally:
        telemetry.enable(False)
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    found = {}
    for pl in ProfileData.from_file(path).planes:
        if pl.name != "/host:CPU":
            continue
        for ln in pl.lines:
            for ev in ln.events:
                if ev.name.startswith("hetu:"):
                    found[ev.name] = (ev.start_ns,
                                      ev.start_ns + ev.duration_ns)
    for on in ("False", "True"):
        o, i = found["hetu:outer_" + on], found["hetu:inner_" + on]
        assert o[0] <= i[0] and i[1] <= o[1]
    telemetry.reset()


# -- compile events -----------------------------------------------------------
def test_compile_events_grow_on_a_first_call_only(telem):
    def fresh(x):
        return jnp.tanh(x) * 3 + 1

    f = jax.jit(fresh)
    x = jnp.ones(5)
    stamp = time.perf_counter()
    f(x).block_until_ready()
    first = telemetry.compile_events(since=stamp)
    f(x).block_until_ready()
    assert telemetry.compile_events(since=stamp) == first and first
    new = [e for e in first if e.fun_name and "fresh" in e.fun_name]
    assert {e.stage for e in new} >= {"trace", "lower", "compile"}
    assert all(e.seconds >= 0 and e.key.startswith("/jax/") for e in new)
    snap = telem.get_registry().snapshot()
    assert snap['jax_compiles_total{stage="compile"}'] >= 1
    assert snap['jax_compile_seconds_total{stage="trace"}'] > 0
    flights = [e for e in telemetry.get_flight_recorder().events()
               if e.get("event") == "jax_compile"]
    assert any("fresh" in (e.get("fun") or "") for e in flights)
    # telemetry off: the list still grows, the registry does not
    telemetry.enable(False)
    telem.get_registry().clear()
    jax.jit(lambda x: fresh(x) + 2)(jnp.ones(5)).block_until_ready()
    assert len(telemetry.compile_events(since=stamp)) > len(first)
    assert 'jax_compiles_total{stage="compile"}' not in \
        telem.get_registry().snapshot()


# -- handles bound once ---------------------------------------------------------
def test_bound_handles_give_the_same_series_and_survive_reset(telem):
    eng = _engine()
    reqs = [eng.submit(list(range(1, 10 + i)),
                       SamplingParams(max_tokens=3 + i))
            for i in range(3)]
    eng.run_until_drained()
    snap = telem.get_registry().snapshot()
    committed = sum(len(r.tokens) for r in reqs)
    assert committed == 3 + 4 + 5
    assert snap['serving_tokens_total{kind="generated"}'] == committed
    assert snap['serving_tokens_total{kind="prompt"}'] == \
        sum(len(r.prompt) for r in reqs)
    assert snap['serving_requests_total{outcome="completed"}'] == 3
    assert snap["serving_ttft_seconds"]["count"] == 3
    assert snap["serving_queue_depth"] == 0
    assert snap['serving_attn_kernel_total{path="paged"}'] >= 5
    # reset drops every series; the engine's handles stay live
    telemetry.reset()
    telemetry.enable(True)
    assert telem.get_registry().snapshot() == {}
    assert telem.get_registry().to_prometheus() == ""
    r = eng.submit([1, 2, 3, 4], SamplingParams(max_tokens=2))
    eng.run_until_drained()
    snap = telem.get_registry().snapshot()
    assert snap['serving_tokens_total{kind="generated"}'] == \
        len(r.tokens) == 2
    assert snap['serving_requests_total{outcome="completed"}'] == 1
    assert "# HELP serving_tokens_total serving tokens by kind" in \
        telem.get_registry().to_prometheus()


def test_no_registry_get_or_create_in_the_serving_loop(telem,
                                                       monkeypatch):
    eng = _engine()
    eng.submit(list(range(1, 20)), SamplingParams(max_tokens=3))
    eng.step()                               # first step: traces, binds
    reg = telem.get_registry()
    calls = []
    for kind in ("counter", "gauge", "histogram"):
        real = getattr(reg, kind)
        monkeypatch.setattr(
            reg, kind, lambda *a, _r=real, _k=kind, **kw:
            (calls.append((_k, a[0])), _r(*a, **kw))[1])
    eng.run_until_drained()
    assert calls == []
