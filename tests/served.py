"""What the test files of the served architectures share — a plain
module, not collected (no ``test_*`` function; the contract's methods
live on a class pytest does not take for a test class, and run only
through its subclasses):

* :func:`tiny` — a tiny configuration's ``(config, model, params)``;
* :func:`serve_logits` — ``generation.decode`` driven by hand the way
  the fused step drives it, its two calls compiled once a model;
* :class:`ServedArchContract` — what every served architecture must
  pass, written once; ``tests/test_<arch>.py`` subclasses it with data
  and keeps below it only what is the architecture's own;
* the pack builders of the per-slot mixers' kernel tests, the refusal
  table, and the stub engine of the fleet's host-side tests.

No test module imports another test module
(``tests/test_docs_true.py``): what two of them need lives here.
"""

import contextlib
import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from hetu_tpu import telemetry  # noqa: E402
from hetu_tpu.engine import trace_counts  # noqa: E402
from hetu_tpu.models import generation  # noqa: E402
from hetu_tpu.nn.parallel import SlotStateNotSupported  # noqa: E402
from hetu_tpu.ops.paged_pallas import (  # noqa: E402
    history_tile_count, pack_history_tiles,
)
from hetu_tpu.serving.scheduler import Request, SamplingParams  # noqa: E402

_TINY = {}


def tiny(name, seed, **edits):
    """``tests/benchmark/configs/<name>-tiny.json`` (``edits`` on top)
    -> ``(config, model, params drawn from seed)``, built once a
    process: a worker runs many files, and :func:`serve_logits` keeps
    its compiled calls by the model object."""
    key = (name, seed, json.dumps(edits, sort_keys=True))
    if key not in _TINY:
        from benchmark.runners.serve_arch import load_arch
        with open(os.path.join(ROOT, "tests", "benchmark", "configs",
                               f"{name}-tiny.json")) as f:
            config = {**json.load(f), **edits}
        model = load_arch(config["arch"]).build(config)
        _TINY[key] = config, model, model.init(jax.random.key(seed))
    return _TINY[key]


# -- the by-hand serving driver ------------------------------------------------
_CALLS = {}


def _compiled_calls(model, attn_kernel, tile_rows, traced):
    """The driver's two calls under ``jax.jit`` — the decode rows' and
    the pack's — built once for a model, a lane and a tile map, so that
    every case of a file finds the first one's executables (a trace per
    shape: slots, capacity, arena). ``attn_kernel`` None: a stack
    without an arena, no table anywhere."""
    key = (id(model), attn_kernel, tile_rows, traced)
    if key in _CALLS:
        return _CALLS[key][1:]
    lane = {} if attn_kernel is None else {"attn_kernel": attn_kernel}

    def rows(params, tok, pos, caches, act, bt):
        return generation.decode(
            model, params, tok[:, None], pos[:, None], caches,
            slot_mask=act, block_tables=bt, row_mask=act[:, None], **lane)

    def pack(params, tokens, tpos, caches, bt, tslot, pack):
        pos, tables = tpos[None], {}
        h = model.embed(params, tokens[None], positions=pos)
        if attn_kernel is not None:
            pack = {**pack, "impl": "reference", "slot_tables": bt}
            if tile_rows:
                pack["tiles"] = {**pack["tiles"], "rows": tile_rows}
            tables = {"block_tables": jnp.take(bt, tslot, axis=0)}
        h, caches = model.blocks.decode(
            params["blocks"], h, caches, positions=pos, pack=pack, **lane,
            **tables)
        return generation.head_logits(model, params, h), caches

    # (the model rides along so that its id stays its own)
    _CALLS[key] = model, jax.jit(rows), jax.jit(pack)
    return _CALLS[key][1:]


def serve_logits(model, params, requests, *, slots, chunk, capacity=None,
                 block_size=None, n_blocks=0, max_len=0,
                 attn_kernel="reference", tile_rows=None, traced=()):
    """Drive ``generation.decode`` the way the fused step does — a
    prefill pack of at most ``chunk`` tokens a call (FCFS, runs of
    several requests in one pack), then decode rows, a token a call —
    and collect every position's logits. ``requests``: ``(slot, ids,
    n_decode)`` in admission order; a slot named twice is REUSED once
    its first request is done.

    A pack has ``capacity`` rows (``chunk`` where not given) and the
    chunk is DATA: a case at chunk 7 fills 7 rows of a capacity-10 pack
    and leaves the rest ``valid = False``, as the engine's
    ``serve/pack`` does with a short budget — no new trace.
    ``block_size`` None: an engine without an arena (no table, no
    page). ``tile_rows``: the pack carries the engine's tile map of
    every run (the split read), sized from the capacity. ``traced``:
    whatever else a trace reads that the caller changed (a class
    constant under ``monkeypatch``), so that it gets calls of its
    own."""
    capacity = capacity or chunk
    paged = block_size is not None
    rows_call, pack_call = _compiled_calls(
        model, attn_kernel if paged else None, tile_rows, traced)
    caches = generation.init_paged_caches(
        model, n_blocks, block_size or 1, jnp.float32, slots=slots)
    bt = np.zeros((slots, max_len // block_size), np.int32) if paged \
        else None
    free = list(range(1, n_blocks))
    out = {}
    pending = [dict(i=i, slot=s, ids=np.asarray(ids), off=0, n=n)
               for i, (s, ids, n) in enumerate(requests)]
    busy, prefilling, decoding = set(), [], []
    while pending or prefilling or decoding:
        for r in list(pending):              # admit where the slot is free
            if r["slot"] not in busy:
                busy.add(r["slot"])
                if paged:
                    need = -(-len(r["ids"]) // block_size)
                    bt[r["slot"]] = 0
                    bt[r["slot"], :need] = [free.pop(0)
                                            for _ in range(need)]
                prefilling.append(r)
                pending.remove(r)
                out[r["i"]] = np.zeros((len(r["ids"]), model.cfg.vocab_size),
                                       np.float32)
        btd = jnp.asarray(bt) if paged else None
        if decoding:                         # the decode rows first
            pos = np.zeros(slots, np.int32)
            tok = np.zeros(slots, np.int32)
            act = np.zeros(slots, bool)
            for r in decoding:
                pos[r["slot"]], act[r["slot"]] = r["off"], True
                tok[r["slot"]] = r["ids"][r["off"]]
            lg, caches = rows_call(params, jnp.asarray(tok),
                                   jnp.asarray(pos), caches,
                                   jnp.asarray(act), btd)
            lg = np.asarray(lg)
            for r in list(decoding):
                out[r["i"]][r["off"]] = lg[r["slot"], 0]
                r["off"] += 1
                if r["off"] == len(r["ids"]):
                    decoding.remove(r)
                    busy.discard(r["slot"])
                    if paged:
                        free += [b for b in bt[r["slot"]] if b]
        if prefilling:                       # then one pack
            tokens = np.zeros(capacity, np.int32)
            tpos = np.zeros(capacity, np.int32)
            tslot = np.zeros(capacity, np.int32)
            valid = np.zeros(capacity, bool)
            seg = np.full(capacity, -1, np.int32)
            hist = np.zeros(capacity, np.int32)
            used, fills, runs = 0, [], []
            for r in prefilling:
                if used >= chunk:
                    break
                n = min(chunk - used, len(r["ids"]) - r["n"] - r["off"])
                runs.append((r["slot"], used, n, r["off"]))
                sl = slice(used, used + n)
                tokens[sl] = r["ids"][r["off"]:r["off"] + n]
                tpos[sl] = np.arange(r["off"], r["off"] + n)
                tslot[sl], valid[sl], seg[sl] = r["slot"], True, r["slot"]
                hist[sl] = r["off"]
                fills.append((r, used, n))
                used += n
            pack = {"valid": jnp.asarray(valid), "slot": jnp.asarray(tslot)}
            if paged:
                pack.update(segment_ids=jnp.asarray(seg)[None],
                            hist=jnp.asarray(hist))
            if tile_rows:
                tmap, _ = pack_history_tiles(
                    runs, tile_rows=tile_rows, every_run=True,
                    n_tiles=history_tile_count(capacity, tile_rows, slots))
                pack["tiles"] = {"map": jnp.asarray(tmap),
                                 "tables": btd[tmap[0]]}
            lg, caches = pack_call(params, jnp.asarray(tokens),
                                   jnp.asarray(tpos), caches, btd,
                                   jnp.asarray(tslot), pack)
            lg = np.asarray(lg)
            for r, at, n in fills:
                out[r["i"]][r["off"]:r["off"] + n] = lg[0, at:at + n]
                r["off"] += n
                if r["off"] == len(r["ids"]) - r["n"]:
                    prefilling.remove(r)
                    decoding.append(r)
    return out


# -- what assumes block K/V, and is refused over a slot's state ----------------
REFUSED = [
    ("prefix_cache", dict(prefix_cache=True)),
    ("preempt", dict(preempt=True)),
    ("spill_host_budget_bytes", dict(spill_host_budget_bytes=1e6)),
    ("long_max_len", dict(long_max_len=128)),
    ("spec_depth", dict(spec_depth=2)),
    ("int8", dict(cache_dtype=jnp.int8)),
    ("w8a8", dict(w8a8="on")),
    ("tenancy", dict(tenancy=True)),
    ("prefill_attn='reference'", dict(prefill_attn="reference")),
]

#: what moves a request's pages, and what it is called with
PAGE_MOVERS = {"export_prefix": ([1, 2, 3],), "import_prefix": (None,),
               "configure_replication": (lambda doc: None,),
               "evict_request": (None,), "prefill_only": ([1, 2, 3],)}


def top_token_gaps(logits, prompt_len, tokens):
    """How far below the top logit each emitted token stands:
    ``logits`` the reference's over ``prompt + tokens``,
    teacher-forced."""
    at = np.asarray(logits)[prompt_len - 1:prompt_len - 1 + len(tokens)]
    return at.max(-1) - at[np.arange(len(tokens)), list(tokens)]


@contextlib.contextmanager
def counted(read):
    """Telemetry on around a block: ``read()`` -> numbers of the
    process's counters; yields a list that holds what the block added
    to each once it is left."""
    delta = []
    telemetry.enable(True)
    try:
        before = read()
        yield delta
        delta[:] = [a - b for a, b in zip(read(), before)]
    finally:
        telemetry.enable(False)


def _case_id(value):
    if isinstance(value, tuple) and isinstance(value[0], str):
        return value[0]                      # (name, keywords)
    if isinstance(value, dict):              # a control, a lane
        return "-".join(f"{k}={getattr(v, '__name__', v)}"
                        for k, v in value.items()) or "default"
    return str(value)


class ServedArchContract:
    """What a served architecture must pass, once. A subclass
    (``class TestJamba(ServedArchContract)``) states DATA; a test whose
    data it does not state is not generated for it, and a file's variant
    that asserts more overrides the method (with its own axes under
    ``pytest.mark.parametrize``) or a hook below. The module provides
    the ``tiny`` fixture: ``(config as the reference reads it, model,
    params)``."""

    #: ``benchmark.reference.<arch>``, and keywords of its ``logits``
    reference = None
    ref_kw = {}
    #: forward = the reference: the ids ``(rows, length)`` and the
    #: tolerance on the logits
    forward_ids = None
    tol = None
    #: each planted control moves the reference: the keywords of
    #: ``reference.logits``, the ids, from which position, by how much
    controls = ()
    control_ids = None
    control_from = 0
    control_moves = None
    #: chunked prefill then decode = one forward pass: the chunks,
    #: ``(rng seed, lowest id, highest, ((slot, prompt, decoded), ...))``,
    #: :func:`serve_logits`' sizes, the tolerance (``tol`` if None)
    chunks = ()
    requests = None
    serve = dict(slots=2, capacity=10, block_size=4, n_blocks=24,
                 max_len=32)
    serve_tol = None
    #: the engine serves the reference's tokens: the lanes (keywords of
    #: the engine on top of ``engine``), the prompts' ``(rng seed, lowest
    #: id, highest, lengths)``, tokens a request, the gap allowed
    lanes = ()
    engine = dict(max_len=64, prefill_chunk=8, block_size=4, slots=3,
                  kv_blocks=40, seed=0)
    prompts = (7, 1, 128, (21, 13, 30, 23, 7))
    max_tokens = 6
    token_tol = None
    #: ``read() -> numbers`` of the counters ``engine_served`` is handed
    #: the deltas of (a ``staticmethod``), or None
    counters = None
    #: what is refused at construction, by name, over ``small_engine``;
    #: what is refused when called; the dense cache and CP-prefill
    refused = ()
    page_movers = ()
    small_engine = dict(max_len=64, prefill_chunk=8, block_size=4,
                        slots=2, kv_blocks=40)
    refuses_the_dense_cache = False
    #: modules that importing the package must not load
    new_modules = ()

    _DATA = {
        "test_model_matches_the_reference": "forward_ids",
        "test_each_planted_control_moves_the_reference": "controls",
        "test_chunked_prefill_then_decode_equals_one_forward_pass":
            "chunks",
        "test_engine_serves_tokens_the_reference_puts_on_top": "lanes",
        "test_what_assumes_block_kv_refuses_at_construction_by_name":
            "refused",
        "test_what_moves_a_requests_pages_refuses_by_name": "page_movers",
        "test_dense_cache_and_cp_prefill_refuse_by_name":
            "refuses_the_dense_cache",
        "test_importing_the_package_loads_none_of_the_new_modules":
            "new_modules",
    }
    _AXES = {"control": "controls", "chunk": "chunks", "lanes": "lanes",
             "refusal": "refused", "call": "page_movers"}

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        cls._seen = {}
        for test, data in cls._DATA.items():
            stated = getattr(cls, data)
            absent = not stated if isinstance(stated, (bool, type(None))) \
                else len(stated) == 0
            if absent and test not in vars(cls):
                setattr(cls, test, None)

    def pytest_generate_tests(self, metafunc):
        """The cases come from the class's data (a method a subclass
        overrides with axes of its own names them itself)."""
        for arg, data in self._AXES.items():
            if arg in metafunc.fixturenames and not any(
                    arg in m.args[0] for m in
                    metafunc.definition.iter_markers("parametrize")):
                values = list(getattr(self, data))
                metafunc.parametrize(arg, values,
                                     ids=[_case_id(v) for v in values])

    # -- hooks -----------------------------------------------------------------
    def one_sequence(self, tiny, ids, **control):
        """The reference's logits of ONE sequence."""
        config, _, params = tiny
        return self.reference.logits(params, ids, config, **self.ref_kw,
                                     **control)

    def ref_logits(self, tiny, ids, **control):
        """:meth:`one_sequence`, computed once a class and sequence:
        the reference walks a sequence token by token, and the cases of
        a class ask for the same ones again (the uncontrolled logits at
        every control, a request at every chunk, a served sequence in
        both lanes)."""
        ids = np.asarray(ids)
        key = (id(tiny[2]), ids.dtype.str, ids.tobytes(), repr(control))
        if key not in self._seen:
            self._seen[key] = self.one_sequence(tiny, jnp.asarray(ids),
                                                **control)
        return self._seen[key]

    def close(self, got, want, atol):
        np.testing.assert_allclose(got, want, atol=atol)

    def engine_served(self, eng, model, lanes, counted):
        """What is the architecture's own to assert of the engine that
        served (its leaves, what its kernels counted)."""

    def refusal(self, name):
        """``(exception, match)`` of a refusal at construction."""
        return SlotStateNotSupported, name

    # -- the contract ----------------------------------------------------------
    def test_model_matches_the_reference(self, tiny):
        """The program's whole-sequence forward against the plain
        reference, float32 both sides."""
        _, model, params = tiny
        got = model(params, self.forward_ids)
        for b, ids in enumerate(self.forward_ids):
            self.close(got[b], self.ref_logits(tiny, ids), self.tol)

    def test_each_planted_control_moves_the_reference(self, tiny, control):
        base = self.ref_logits(tiny, self.control_ids)
        moved = self.one_sequence(tiny, self.control_ids, **control)
        assert float(jnp.abs(moved - base)[self.control_from:].max()) \
            > self.control_moves, control

    def draw_requests(self):
        seed, lo, hi, shape = self.requests
        rng = np.random.default_rng(seed)
        return [(slot, rng.integers(lo, hi, n), decoded)
                for slot, n, decoded in shape]

    def test_chunked_prefill_then_decode_equals_one_forward_pass(
            self, tiny, chunk):
        """Logits, not tokens: two slots of different lengths in one
        pack, chunks that cut what the mixers carry across packs (a
        convolution's window, a scan's pieces, a page), and slot 0
        REUSED by a third request — whatever a slot holds must start
        from zeros, its pages be its own."""
        _, model, params = tiny
        reqs = self.draw_requests()
        got = serve_logits(model, params, reqs, chunk=chunk, **self.serve)
        for i, (_, ids, _) in enumerate(reqs):
            self.close(got[i], self.ref_logits(tiny, jnp.asarray(ids)),
                       self.serve_tol or self.tol)

    def draw_prompts(self):
        seed, lo, hi, lengths = self.prompts
        rng = np.random.default_rng(seed)
        return [rng.integers(lo, hi, n).tolist() for n in lengths]

    def test_engine_serves_tokens_the_reference_puts_on_top(self, tiny,
                                                            lanes):
        """The real engine — scheduler, fused step, ONE trace and one
        executable — over more requests than slots: every emitted token
        is the reference's top token, within ``token_tol`` of it
        (float32 both sides)."""
        from hetu_tpu.serving import ServingEngine
        _, model, params = tiny
        prompts = self.draw_prompts()
        traces = trace_counts().get("serving_step", 0)
        with counted(self.counters) if self.counters \
                else contextlib.nullcontext() as delta:
            eng = ServingEngine(model, params, **{**self.engine, **lanes})
            outs = eng.generate_many(
                prompts, SamplingParams(max_tokens=self.max_tokens))
        assert trace_counts()["serving_step"] - traces == 1
        assert eng.step_executables() == 1
        for p, toks in zip(prompts, outs):
            gap = top_token_gaps(
                self.ref_logits(tiny, jnp.asarray(p + list(toks))),
                len(p), toks)
            assert len(toks) == self.max_tokens \
                and gap.max() <= self.token_tol, (len(p), gap)
        self.engine_served(eng, model, lanes, delta)

    def test_what_assumes_block_kv_refuses_at_construction_by_name(
            self, tiny, refusal):
        from hetu_tpu.serving import ServingEngine
        _, model, params = tiny
        name, kw = refusal
        error, match = self.refusal(name)
        with pytest.raises(error, match=match):
            ServingEngine(model, params, **{**self.small_engine, **kw})

    @pytest.fixture(scope="class")
    def small(self, tiny):
        """ONE engine for the cases that differ only in what they ask
        of an engine already built."""
        from hetu_tpu.serving import ServingEngine
        _, model, params = tiny
        return ServingEngine(model, params, **self.small_engine)

    def test_what_moves_a_requests_pages_refuses_by_name(self, small, call):
        with pytest.raises(SlotStateNotSupported, match=call):
            getattr(small, call)(*PAGE_MOVERS[call])

    def test_dense_cache_and_cp_prefill_refuse_by_name(self, tiny):
        _, model, params = tiny
        with pytest.raises(SlotStateNotSupported, match="dense cache"):
            generation.init_kv_caches(model, 1, 16)
        with pytest.raises(SlotStateNotSupported, match="CP-prefill"):
            model.blocks.prefill(params["blocks"], None)

    def test_importing_the_package_loads_none_of_the_new_modules(self):
        code = ("import sys, hetu_tpu, hetu_tpu.serving, hetu_tpu.models; "
                f"bad = [m for m in {tuple(self.new_modules)!r} "
                "if m in sys.modules]; print(bad); sys.exit(bool(bad))")
        r = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                           env={**os.environ, "JAX_PLATFORMS": "cpu"},
                           capture_output=True, text=True)
        assert r.returncode == 0, r.stdout + r.stderr


# -- packs of the per-slot mixers' kernel tests --------------------------------
def pack_runs(runs, C, pad=0.0):
    """``runs``: ``(slot, first position, the operands of the run's
    tokens)`` in pack order -> ``(the operands of C rows — ``pad``
    behind the runs —, (slot, pos, valid))``."""
    n = sum(x[0].shape[0] for _, _, x in runs)
    ops = tuple(
        jnp.concatenate([x[j] for _, _, x in runs] + [jnp.full(
            (C - n,) + runs[0][2][j].shape[1:], pad)])
        for j in range(len(runs[0][2])))
    slot = sum(([s] * x[0].shape[0] for s, _, x in runs), [])
    pos = sum((list(range(p0, p0 + x[0].shape[0])) for _, p0, x in runs),
              [])
    return ops, (jnp.asarray(slot + [0] * (C - n), jnp.int32),
                 jnp.asarray(pos + [0] * (C - n), jnp.int32),
                 jnp.asarray([True] * n + [False] * (C - n)))


def pack_slices(parts, C):
    """``parts``: ``(slot, (first, behind), sequence)`` — positions
    ``first .. behind`` of a whole sequence's operands a run — as
    :func:`pack_runs` packs them, zeros behind."""
    return pack_runs([(s, a, tuple(x[a:b] for x in seq))
                      for s, (a, b), seq in parts], C)


def delta_rule_pack(runs, C, slots, key, draw, rule):
    """A pack of the delta-rule kernels: ``runs`` ``(slot, first
    position, tokens)`` in pack order, each drawn by ``draw(key, n)``;
    the pad rows hold garbage, not zeros; a run from position 0 starts
    from zeros over a state that is not. ``rule(operands, state or
    None) -> (o, state)`` is what the kernels are held to -> ``(ops,
    the states before, (slot, pos, valid), the rule's o, the rule's
    states, rows used)``."""
    state0 = np.asarray(jax.random.normal(jax.random.fold_in(key, 99),
                                          (slots,) + rule.state_shape))
    drawn = [(s, p0, draw(jax.random.fold_in(key, i), n))
             for i, (s, p0, n) in enumerate(runs)]
    want_o, want_s = [], state0.copy()
    for s, p0, x in drawn:
        o, want_s[s] = rule(x, state0[s] if p0 else None)
        want_o.append(o)
    ops, where = pack_runs(drawn, C, pad=7.0)
    return list(ops), jnp.asarray(state0), where, \
        np.concatenate(want_o), want_s, sum(n for _, _, n in runs)


#: Kimi Delta Attention's kernel tests: heads, their width, and
#: operands as the mixer makes them
KDA_H, KDA_D = 2, 16


def kda_draw(key, T, at_bound=False):
    """Unit q, k; g in (-5, 0), or all at the bound."""
    H, D = KDA_H, KDA_D
    ks = jax.random.split(key, 5)
    q, k, v = (jax.random.normal(ks[i], (T, H, D)) for i in range(3))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * D ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    g = jnp.full((T, H, D), -5.0) if at_bound else \
        -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (T, H, D)))
    return q, k, v, g, jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))


def kda_pack(runs, C, slots, key):
    """:func:`delta_rule_pack` of :func:`kda_draw` against
    ``kda.kda_recurrence``."""
    from hetu_tpu.ops import kda

    def rule(x, state):
        return kda.kda_recurrence(*x, state=state)
    rule.state_shape = (KDA_H, KDA_D, KDA_D)
    return delta_rule_pack(runs, C, slots, key, kda_draw, rule)


# -- the fleet's host-side tests -----------------------------------------------
class StubEngine:
    """Echo engine behind a real coordinator: a submitted request
    completes with ``prompt[:max_tokens]`` after ``delay_s`` (a worker
    thread plays the decode loop). Speaks everything the serving verbs
    and the RemoteEngineProxy touch."""

    def __init__(self, delay_s: float = 0.0):
        self.delay_s = delay_s
        self.weight_version = 0
        self.submits = 0
        self._next = 0
        self._requests_by_id: dict[int, Request] = {}
        self._lock = threading.Lock()

        class _Sched:
            depth = 0
            occupancy = 0.0
        self.scheduler = _Sched()

    @property
    def load(self):
        return sum(1 for r in self._requests_by_id.values()
                   if not r.done.is_set())

    def has_work(self):
        return self.load > 0

    def submit(self, prompt, sampling=None, *, resume=None,
               handoff=False, traceparent=None):
        sampling = sampling or SamplingParams()
        with self._lock:
            req = Request(id=self._next,
                          prompt=np.asarray(prompt, np.int32).ravel(),
                          sampling=sampling, submit_s=time.monotonic())
            self._next += 1
            self.submits += 1
        if traceparent:
            tid, _span = telemetry.parse_traceparent(traceparent)
            if tid:
                req.trace_id = tid
                req.traceparent = traceparent
        if resume is not None:
            req.spill = resume
            req.tokens = list(resume.tokens)

        def finish():
            if self.delay_s:
                time.sleep(self.delay_s)
            req.tokens = [int(t) for t in
                          req.prompt[:sampling.max_tokens]]
            req.status = "done"
            req.first_token_s = time.monotonic()
            req.done.set()

        threading.Thread(target=finish, daemon=True).start()
        return req

    def result(self, req, timeout=None):
        if not req.done.wait(timeout):
            return None
        return req.result()

    def cancel_queued(self, ids=None):
        return []

    def evict_request(self, req, *, lock_timeout_s=None):
        return None

    def start(self):
        pass

    def stop(self):
        pass
