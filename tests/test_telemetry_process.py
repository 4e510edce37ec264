"""The process beside the loop (ISSUE 53): compile events say hit or
miss and on which thread, ``telemetry.process.sample()`` reads the
process's own counters, and a garbage collection is a span."""

import gc
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from hetu_tpu import telemetry
from hetu_tpu.telemetry import process


@pytest.fixture
def telem():
    telemetry.reset()
    telemetry.enable(True)
    yield telemetry
    telemetry.enable(False)
    telemetry.reset()


@pytest.fixture
def compile_cache(tmp_path):
    """JAX's persistent cache in a directory of this test's own, with
    no threshold; as found afterwards."""
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    compilation_cache.reset_cache()
    jax.config.update(names[0], str(tmp_path))
    jax.config.update(names[1], 0.0)
    jax.config.update(names[2], 0)
    yield
    for n, v in before.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()


def _compiles(since, fun):
    return [e for e in telemetry.compile_events(since=since)
            if e.stage == "compile" and e.fun_name == f"jit({fun})"]


def test_compile_events_say_miss_then_hit_and_the_thread(
        telem, compile_cache):
    def issue53_cached(a):
        return (a @ a).sum() + 53.0

    x = jnp.ones((32, 32))
    stamp = time.perf_counter()
    jax.jit(issue53_cached)(x).block_until_ready()
    (ev,) = _compiles(stamp, "issue53_cached")
    assert ev.cache == "miss"
    assert ev.thread == threading.current_thread().name
    jax.clear_caches()
    stamp = time.perf_counter()
    out = {}

    def work():
        out["y"] = jax.jit(issue53_cached)(x).block_until_ready()

    t = threading.Thread(target=work, name="issue53-compile")
    t.start()
    t.join()
    (ev,) = _compiles(stamp, "issue53_cached")
    assert ev.cache == "hit" and ev.thread == "issue53-compile"
    # every stage names its thread; only a compile says what the cache did
    for e in telemetry.compile_events(since=stamp):
        assert e.thread and (e.cache is None) == (e.stage != "compile")
    snap = telem.get_registry().snapshot()
    assert snap['jax_compile_cache_total{result="miss"}'] >= 1
    assert snap['jax_compile_cache_total{result="hit"}'] >= 1
    # the hit's seconds are not cold ones
    cold = sum(e.seconds for e in telemetry.compile_events()
               if e.stage == "compile" and e.cache != "hit"
               and e.fun_name == "jit(issue53_cached)")
    assert snap["jax_compile_cold_seconds_total"] >= cold > 0
    # a counter of its own: the stages still sum to the time spent
    assert not any("cold" in k for k in snap
                   if k.startswith("jax_compile_seconds_total"))
    flights = [f for f in telemetry.get_flight_recorder().events()
               if f.get("event") == "jax_compile"
               and f.get("fun") == "jit(issue53_cached)"]
    assert [f["cache"] for f in flights] == ["miss", "hit"]


def test_under_the_caches_thresholds_reads_uncached_and_off_without(
        telem, compile_cache):
    def issue53_small(a):
        return a * 53.0

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e6)
    stamp = time.perf_counter()
    jax.jit(issue53_small)(jnp.ones(8)).block_until_ready()
    (ev,) = _compiles(stamp, "issue53_small")
    assert ev.cache == "uncached"
    # asked again: neither found nor written the first time
    jax.clear_caches()
    stamp = time.perf_counter()
    jax.jit(issue53_small)(jnp.ones(8)).block_until_ready()
    assert _compiles(stamp, "issue53_small")[0].cache == "uncached"
    # the cache turned off: no request
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        jax.clear_caches()
        stamp = time.perf_counter()
        jax.jit(issue53_small)(jnp.ones(8)).block_until_ready()
        assert _compiles(stamp, "issue53_small")[0].cache == "off"
    finally:
        jax.config.update("jax_enable_compilation_cache", True)


def test_compile_event_fields_go_last_with_defaults():
    ev = telemetry.CompileEvent("k", "trace", 0.1, 1.0, None)
    assert ev.cache is None and ev.thread == ""
    assert telemetry.CompileEvent._fields[:5] == (
        "key", "stage", "seconds", "t", "fun_name")


def test_sample_is_monotone_and_sees_a_started_thread(telem):
    reg = telem.get_registry()
    process.sample()
    a = reg.scalars()
    assert a["process_cpu_seconds_total"] == pytest.approx(
        time.process_time(), abs=0.5)
    stop = threading.Event()
    t = threading.Thread(target=stop.wait, name="issue53-idle")
    t.start()
    try:
        sum(i * i for i in range(200_000))      # some CPU of our own
        process.sample()
        b = reg.scalars()
    finally:
        stop.set()
        t.join()
    assert b["process_cpu_seconds_total"] > a["process_cpu_seconds_total"]
    assert b["process_threads"] == a["process_threads"] + 1
    assert b["process_rss_peak_bytes"] >= a["process_rss_peak_bytes"] > 0
    # every series has a reader or a stated use, and rides the tracks
    mine = sorted(k for k in b if k.startswith("process_"))
    assert mine == ["process_cpu_seconds_total", "process_rss_peak_bytes",
                    "process_threads"]
    tr = telem.get_tracer()
    tr.record_counters(b)
    assert set(mine) <= {name for name, _, _ in tr.counter_samples()}
    # a reset starts the counter from the process's start again
    telemetry.reset()
    process.sample()
    assert reg.scalars()["process_cpu_seconds_total"] == pytest.approx(
        time.process_time(), abs=0.5)


def test_compile_series_stay_off_the_counter_tracks(telem):
    """The registry and the flight events serve the operator; the
    benchmark reads ``compile_events()``: nothing reads a track."""
    jax.jit(lambda a: a + 53.5)(jnp.ones(3)).block_until_ready()
    reg, tr = telem.get_registry(), telem.get_tracer()
    assert any(k.startswith("jax_compile") for k in reg.scalars())
    tr.record_counters(reg.scalars())
    assert not [n for n, _, _ in tr.counter_samples()
                if n.startswith("jax_compile")]


def test_a_collection_is_a_span_under_what_caused_it(telem):
    tr = telem.get_tracer()
    with telemetry.span("issue53/outer"):
        with telemetry.span("issue53/inner"):
            gc.collect()
    mine = [e for e in tr.events() if e.name == "gc/collect"
            and e.attrs["generation"] == 2]
    assert len(mine) == 1
    (ev,) = mine
    assert ev.cat == "gc" and ev.attrs["under"] == "issue53/inner"
    assert ev.attrs["thread"] == threading.current_thread().name
    assert ev.attrs["collected"] >= 0 and ev.depth == 2
    inner = next(e for e in tr.events() if e.name == "issue53/inner")
    assert inner.ts_s <= ev.ts_s and \
        ev.ts_s + ev.dur_s <= inner.ts_s + inner.dur_s + 1e-6
    snap = telem.get_registry().snapshot()
    assert snap['gc_collections_total{generation="2"}'] == 1
    assert snap['gc_pause_seconds_total{generation="2"}'] == \
        pytest.approx(ev.dur_s)
    # short sweeps are counted and not kept
    n0 = len(tr.events())
    before = snap.get('gc_collections_total{generation="0"}', 0)
    saved = process.GC_EVENT_MIN_S
    process.GC_EVENT_MIN_S = 10.0
    try:
        gc.collect(0)
    finally:
        process.GC_EVENT_MIN_S = saved
    assert len(tr.events()) == n0
    assert telem.get_registry().snapshot()[
        'gc_collections_total{generation="0"}'] == before + 1
    # off: no event, no callback
    telemetry.enable(False)
    gc.collect()
    telemetry.enable(True)
    assert len([e for e in tr.events() if e.name == "gc/collect"
                and e.attrs["generation"] == 2]) == 1


def test_the_disabled_path_leaves_gc_callbacks_as_found():
    found = list(gc.callbacks)
    telemetry.enable(False)
    assert gc.callbacks == found
    telemetry.enable(True)
    telemetry.enable(True)                       # idempotent
    assert len(gc.callbacks) == len(found) + 1
    telemetry.enable(False)
    assert gc.callbacks == found


def test_a_collection_inside_the_tracers_lock_does_not_deadlock(telem):
    """The hook runs at any bytecode boundary of the collecting thread,
    also one inside a block that holds the tracer's lock."""
    tr = telem.get_tracer()
    done = threading.Event()

    def work():
        with tr._lock:
            gc.collect()
        done.set()

    t = threading.Thread(target=work, daemon=True)
    t.start()
    assert done.wait(30.0)
    assert any(e.name == "gc/collect" for e in tr.events())


@pytest.mark.parametrize("read", ["scalars", "snapshot", "to_prometheus"])
def test_a_collection_inside_the_registrys_iteration_may_add_a_series(
        telem, monkeypatch, read):
    """The hook runs at any bytecode boundary of the collecting thread,
    also one inside the registry's iteration of the collections' own
    counters (``scalars()`` in ``serve/account``), and the first
    collection of a generation since a ``reset`` adds a series there."""
    from hetu_tpu.telemetry import metrics
    reg = telem.get_registry()
    gc.collect(0)                   # the counters hold ONE series each
    assert 'gc_collections_total{generation="1"}' not in reg.scalars()
    seen = []

    def collecting(real):
        def name(*a):
            gen = 1 + len(seen) % 2
            seen.append(gen)
            gc.collect(gen)         # ... and gain one under the reader
            return real(*a)
        return name

    monkeypatch.setattr(metrics, "_series_name",
                        collecting(metrics._series_name))
    monkeypatch.setattr(metrics, "_prom_series",
                        collecting(metrics._prom_series))
    getattr(reg, read)()            # no "dictionary changed size"
    monkeypatch.undo()
    after = reg.scalars()
    for gen in (1, 2):
        assert after[f'gc_collections_total{{generation="{gen}"}}'] \
            == seen.count(gen) > 0
