"""What ``BENCHMARK.json`` has to say of a cell, asserted by NAME.

The driver lets a later PR append a configuration, a cell and per-layer
entries at the ends of their lists, and a cell's name to the
``workloads`` of an entry that is there; it lets none edit the tests in
this directory. So no test here says WHERE in a list something stands,
how many entries a list has, or which entries list a cell and no others:
each says what its cell NEEDS — the cell with its configuration, traffic
and chips; each metric with its unit, layer and ``moves``, listing the
cell; a reader file behind each — through the helpers below, and
registers the saying (``@cell_needs``). ``test_manifest_by_name.py``
repeats every registered saying on a copy of the file to which such
things were appended (``appended``): what holds there holds for the next
PR's file.
"""

import copy
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.peaks import peaks_for  # noqa: E402

#: the contract's limit on ``per_layer``
PER_LAYER_MAX = 128
ENTRY_KEYS = {"name", "unit", "better", "source", "layer", "moves",
              "workloads"}

STEP = "fused serving step (serving/engine.py)"
KERNELS = "kernels (ops/flash_pallas.py, ops/paged_pallas.py)"
MOE = "expert layer (nn/moe.py)"
KV = "KV manager (serving/kv_pool.py)"
COMPILE = "compile (engine/precompile.py, the engine's jit)"
FRONT = "serving front end (serving/server.py, rpc/stream.py)"
PROCESS = "process beside the loop (telemetry/process.py)"

TOKENS, SETUP = "serve_tokens_per_s", "setup_s"
#: the loop's account (PR 35) and the process's (PR 53), one entry for
#: the chat cell and one for the cells that report ``serve_tokens_per_s``
ACCOUNT = {
    "engine_host_cpu_ms": ("ms", STEP), "engine_host_offcpu_ms": ("ms", STEP),
    "host_dispatch_ms": ("ms", STEP), "wire_cpu_ms": ("ms", FRONT),
    "step_launch_lag_ms": ("ms", STEP), "step_fetch_lag_ms": ("ms", STEP)}
PROCESS_ACCOUNT = {
    "window_compile_s": ("s", COMPILE), "host_other_cpu_ms": ("ms", PROCESS),
    "gc_pause_ms": ("ms", PROCESS), "process_threads_peak": ("count", PROCESS),
    "idle_host_phases_ms": ("ms", STEP)}
#: what every backlog cell of the fused serving step needs, whatever
#: its architecture: metric -> (unit, layer, moves)
BACKLOG_CELL = {
    "setup_compile_s": ("s", COMPILE, SETUP),
    "setup_cold_compile_s": ("s", COMPILE, SETUP),
    "step_decode_ms.backlogs": ("ms", STEP, TOKENS),
    "step_prefill_ms.backlogs": ("ms", STEP, TOKENS),
    **{f"{n}.backlogs": (*v, TOKENS)
       for n, v in {**ACCOUNT, **PROCESS_ACCOUNT}.items()}}
KV_PEAK = {"kv_used_peak_pct": ("%", KV, TOKENS)}
#: the Kimi cell alone never listed its iteration (PR 30)
ENGINE_ITER = {"engine_iter_ms.backlogs": ("ms", STEP, TOKENS)}


def real() -> dict:
    """The file itself, through the harness's own loader."""
    return harness.load_manifest(os.path.join(ROOT, "BENCHMARK.json"))


def rehearsed(path: str) -> set:
    return {x["name"] for x in harness.load_manifest(path)["per_layer"]}


def entry(m: dict, name: str) -> dict:
    got = [x for x in m["end_to_end"] + m["per_layer"] if x["name"] == name]
    assert len(got) == 1, f"{name}: {len(got)} entries"
    return got[0]


def cell_of(m: dict, name: str, *, config: str, traffic: str,
            chips: int = 1, reduced=None) -> tuple:
    """The cell and its configuration's entry, each listed once."""
    cells = [w for w in m["workloads"] if w["name"] == name]
    assert len(cells) == 1, name
    cell = cells[0]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (config, traffic, chips)
    assert 0 < len(cell["why"]) <= 200
    configs = [c for c in m["configs"] if c["name"] == config]
    assert len(configs) == 1, config
    assert os.path.exists(os.path.join(ROOT, configs[0]["file"]))
    if reduced is not None:
        assert configs[0]["reduced"] == reduced
    return cell, configs[0]


def traffic_of(cell: dict) -> dict:
    """The cell's traffic file, as the harness finds it."""
    with open(os.path.join(ROOT, "benchmark/traffic",
                           f"{cell['traffic']}.json")) as f:
        return json.load(f)


def reader_body(m: dict, name: str) -> str:
    """A reader file's text from ``def read`` on."""
    with open(harness.find_reader(ROOT, m, name).__file__) as f:
        text = f.read()
    return text[text.index("def read"):]


def lists(m: dict, metric: str, cell: str) -> bool:
    w = entry(m, metric).get("workloads")
    return w is None or cell in w


def needs(m: dict, cell: str, metrics: dict, *, mirrored_in=None,
          sources=None) -> None:
    """Each of ``metrics`` (name -> (unit, layer, moves)) is an entry
    that lists ``cell`` with that unit, layer and ``moves``; the cell
    reports the end-to-end metric it moves; its reader file's constants
    agree; (``mirrored_in``: a rehearsal manifest) the CPU rehearsal
    reads the same reader; (``sources``) its label is one of these."""
    mirror = rehearsed(mirrored_in) if mirrored_in else None
    for name, (unit, layer, moves) in metrics.items():
        x = entry(m, name)
        assert lists(m, name, cell), f"{name} does not list {cell}"
        assert (x["unit"], x["layer"], x["moves"]) == (unit, layer, moves), \
            name
        assert set(x) <= ENTRY_KEYS and set(x) >= ENTRY_KEYS - {"workloads"}
        assert lists(m, moves, cell), f"{cell} does not report {moves}"
        mod = harness.find_reader(ROOT, m, name)
        assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES) == \
            (name, unit, layer, moves)
        if "roofline" in name:
            assert (x["unit"], x["better"]) == ("%", "higher")
        if mirror is not None:
            assert name in mirror, f"{name} is not rehearsed"
        if sources is not None:
            assert x["source"] in sources, name


def of(names, suffix: str, unit: str, layer: str, moves: str = TOKENS):
    """``{name + suffix: (unit, layer, moves)}`` for ``needs``."""
    return {n + suffix: (unit, layer, moves) for n in names}


def stand_together(m: dict, names: list) -> None:
    """Entries that belong together stand together, in this order
    (wherever in the list that is)."""
    have = [x["name"] for x in m["per_layer"]]
    first = have.index(names[0])
    assert have[first:first + len(names)] == list(names)


def run_without_a_device(config: dict, records=None):
    """A ``harness.Run`` as the CPU gives it: peaks, no device plane."""
    return types.SimpleNamespace(
        config=config, peaks=peaks_for("TPU v5 lite"), trace=None,
        cell={"name": "none"}, records=records or {})


def silent_without_a_device(m: dict, names, config: dict,
                            records=None) -> None:
    """A reader that finds nothing to read returns nothing."""
    run = run_without_a_device(config, records)
    for name in names:
        assert harness.find_reader(ROOT, m, name).read(run) is None, name


def appended(m: dict) -> dict:
    """``m`` as a later PR may leave it: a configuration, a cell and a
    per-layer entry appended at the ends of their lists, the cell's name
    at the end of ``workloads`` of what every backlog cell reports."""
    m = copy.deepcopy(m)
    cell = "next-arch-ep8.some-backlog"
    m["configs"].append({
        "name": "next-arch-ep8", "source": "https://example.invalid/config",
        "file": "benchmark/configs/next-arch-ep8.json", "reduced": [],
        "why": "a configuration a later PR appends"})
    m["workloads"].append({
        "name": cell, "config": "next-arch-ep8", "traffic": "some-backlog",
        "chips": 1, "why": "a cell a later PR appends"})
    for name in (TOKENS, *BACKLOG_CELL, *KV_PEAK, *ENGINE_ITER):
        entry(m, name)["workloads"].append(cell)
    m["per_layer"].append({
        "name": "next_kernel_roofline_pct.next", "unit": "%",
        "better": "higher", "source": "device_trace", "layer": KERNELS,
        "moves": TOKENS, "workloads": [cell]})
    return m


#: every registered saying: ``fn(manifest)`` raises where the manifest
#: lacks what the cell needs
CHECKS = []


def cell_needs(fn):
    CHECKS.append(fn)
    return fn
