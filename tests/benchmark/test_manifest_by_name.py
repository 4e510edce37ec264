"""``BENCHMARK.json`` is read by NAME, and its per-layer list keeps room.

Three guards (ISSUE 58):

* every "the manifest names what the X cell needs" saying of this
  directory (``manifest_checks.CHECKS``) holds on a copy of the file to
  which a configuration, a cell and a per-layer entry were APPENDED — so
  a later PR, which may append and may edit no test here, meets none of
  them;
* the list keeps to the contract's limit, by name, with the free places
  in the message; no reader file waits outside it;
* the table of PR 58's fold: every (entry, cell) pair the ledger read at
  PR 56 under a per-family name is read under the folded name, with the
  same unit, ``better``, layer and ``moves`` — and the same ``source``
  but for five pairs of ``gpt2-large.backlog``, named below.
"""

import glob
import importlib
import json
import os
import re
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

import manifest_checks as mc  # noqa: E402
from benchmark import harness  # noqa: E402

#: the test files that register sayings (``@mc.cell_needs``)
for _name in sorted(os.path.basename(p)[:-3]
                    for p in glob.glob(os.path.join(HERE, "test_*.py"))):
    if _name != "test_manifest_by_name":
        importlib.import_module(_name)

#: a per-family suffix, and the cells its entries listed (PR 56's file)
FAMILY = {
    "backlog": ["gpt2-large.backlog"],
    "mixed": ["command-a-plus-ep8.mixed-backlog"],
    "longdoc": ["kimi-vl-a3b-pp4.longdoc-backlog"],
    "longctx": ["minicpm-sala-pp2.longdoc-32k-backlog"],
    "video": ["ling-3.0-flash-vl-ep8.video-8k-backlog"],
    "blockgen": ["sdar-30b-a3b-ep8.reason-1k-backlog"],
    "retention": ["brumby-14b-pp4.repo-16k-backlog",
                  "jamba2-3b.doc-32k-backlog"]}
LANES = ["backlog", "mixed", "longdoc", "longctx", "video", "blockgen",
         "retention"]
EXPERTS = ["mixed", "longdoc", "video", "blockgen"]
#: quantity -> (the suffixes folded into ``<quantity>.backlogs``, unit,
#: better, source, layer): 49 entries became 12
FOLDED = {
    "engine_iter_ms": (["backlog", "mixed", "longctx", "video", "blockgen",
                        "retention"], "ms", "lower", "host_clock", mc.STEP),
    "step_decode_ms": (LANES, "ms", "lower", "device_trace", mc.STEP),
    "step_prefill_ms": (LANES, "ms", "lower", "device_trace", mc.STEP),
    "step_sample_ms": (["longdoc", "longctx", "video", "retention"], "ms",
                       "lower", "device_trace", mc.STEP),
    "engine_host_ms": (["backlog", "mixed", "longdoc", "longctx"], "ms",
                       "lower", "device_trace", mc.STEP),
    "step_kv_arena_ms": (["backlog", "longctx"], "ms", "lower",
                         "device_trace", mc.KV),
    "step_moe_experts_ms": (EXPERTS, "ms", "lower", "device_trace", mc.MOE),
    "step_moe_shared_ms": (["mixed", "longdoc", "video"], "ms", "lower",
                           "device_trace", mc.MOE),
    "step_moe_route_ms": (EXPERTS, "ms", "lower", "device_trace", mc.MOE),
    "moe_local_imbalance": (EXPERTS, "x", "lower", "host_clock", mc.MOE),
    "moe_experts_roofline_pct": (["longdoc", "video"], "%", "higher",
                                 "device_trace", mc.MOE),
    "mla_decode_roofline_pct": (["longdoc", "video"], "%", "higher",
                                "device_trace", mc.KERNELS)}
#: the five parts whose label differed from every later part's of the
#: same body (PR 24's, written before the count of ``program_*`` labels
#: was frozen): the folded entry carries the later parts' label
RELABELLED = {"engine_iter_ms.backlog": "program_counter",
              "step_decode_ms.backlog": "program_span",
              "step_prefill_ms.backlog": "program_span",
              "step_kv_arena_ms.backlog": "program_span",
              "engine_host_ms.backlog": "program_span"}
OLD = [(q, s) for q, spec in FOLDED.items() for s in spec[0]]


@pytest.mark.parametrize("check", mc.CHECKS, ids=lambda f: f.__name__)
def test_every_saying_holds_where_a_later_pr_has_appended(check):
    m = mc.real()
    later = mc.appended(m)
    assert later["workloads"][:-1] == m["workloads"]
    assert later["configs"][:-1] == m["configs"]
    assert [x["name"] for x in later["per_layer"][:-1]] == \
        [x["name"] for x in m["per_layer"]]
    check(later)


def test_the_sayings_are_all_registered():
    """One saying a serving cell and one each for the accounts, the
    program's trace and the entries that stay apart: the fourteen of PR
    58; a later cell's test file adds its own."""
    names = [f.__name__ for f in mc.CHECKS]
    assert len(names) == len(set(names)) >= 14
    said = " ".join(names)
    for cell in ("mixed", "longdoc", "longctx", "video", "blocks",
                 "retention", "ssm"):
        assert f"the_{cell}_cell" in said


def test_the_per_layer_list_keeps_to_the_contracts_limit():
    n = len(mc.real()["per_layer"])
    assert n <= mc.PER_LAYER_MAX, (
        f"per_layer holds {n} entries of the {mc.PER_LAYER_MAX} the "
        f"contract allows: {mc.PER_LAYER_MAX - n} places free")
    print(f"per_layer: {n} of {mc.PER_LAYER_MAX}, "
          f"{mc.PER_LAYER_MAX - n} places free")


def test_every_reader_file_is_named_by_an_entry_and_every_entry_has_one():
    m = mc.real()
    files = {os.path.basename(p)[:-3] for p in glob.glob(
        os.path.join(ROOT, "benchmark", "layer_metrics", "*.py"))}
    names = [x["name"] for x in m["per_layer"]]
    assert len(names) == len(set(names))
    assert files == set(names)
    e2e = {x["name"] for x in m["end_to_end"]}
    for x in m["per_layer"]:
        assert x["moves"] in e2e
        assert set(x.get("workloads", ())) <= \
            {w["name"] for w in m["workloads"]}


@pytest.mark.parametrize("quantity,suffix", OLD,
                         ids=[f"{q}.{s}" for q, s in OLD])
def test_a_folded_entry_reads_what_its_part_read(quantity, suffix):
    """No (cell, quantity) the ledger held at PR 56 is lost by the
    rename: the folded entry lists the part's cells with the part's
    unit, ``better``, ``source``, layer and ``moves``."""
    m = mc.real()
    old, new = f"{quantity}.{suffix}", f"{quantity}.backlogs"
    _, unit, better, source, layer = FOLDED[quantity]
    names = {x["name"] for x in m["per_layer"]}
    assert old not in names and new in names
    assert not os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", old + ".py"))
    x = mc.entry(m, new)
    assert (x["unit"], x["better"], x["source"], x["layer"], x["moves"]) \
        == (unit, better, source, layer, mc.TOKENS)
    was = RELABELLED.get(old, source)       # the part's label at PR 56
    assert (was == source) == (old not in RELABELLED)
    assert old not in RELABELLED or suffix == "backlog"
    for cell in FAMILY[suffix]:
        mc.needs(m, cell, {new: (unit, layer, mc.TOKENS)})


@pytest.mark.parametrize("quantity", sorted(FOLDED))
def test_a_folded_entry_lists_no_cell_its_parts_did_not(quantity):
    """... and is lent to no cell of PR 56's file that never had the
    quantity: what a PR reads anew in an old cell is that PR's to
    argue (a cell appended since lists itself where its program has
    the scope or counter). In the order of ``workloads``, as every
    entry that lists several cells."""
    m = mc.real()
    order = [w["name"] for w in m["workloads"]]
    then = {c for cells in FAMILY.values() for c in cells}
    parts = {c for s in FOLDED[quantity][0] for c in FAMILY[s]}
    got = mc.entry(m, f"{quantity}.backlogs")["workloads"]
    assert set(got) & then == parts
    assert got == sorted(got, key=order.index)


def test_no_test_here_is_shown_another_view_of_the_file():
    """``tests/conftest.py`` (not a file of the benchmark) still holds
    the views PRs 39-55 showed nine position-pinning tests, behind an
    autouse fixture that picks its tests by node id: no test of that
    name is left here, and none names the rewriters."""
    sys.path.insert(0, os.path.dirname(HERE))
    import conftest
    rewriters = re.compile("|".join(
        ("before_" + "pr53", "later_entries_" + "first", r"\bas_" + r"of\(",
         "manifest_order_for_the_" + "position_pins")))
    pins = []
    for table in ("POSITION_PINS", "ENUMERATING_PINS", "AS_OF_PINS",
                  "AS_OF_LATER_PINS", "AS_OF_BRUMBY_PINS"):
        pins += list(getattr(conftest, table, ()))
    for path in glob.glob(os.path.join(HERE, "*.py")):
        with open(path) as f:
            text = f.read()
        assert not rewriters.search(text), path
        for pin in pins:
            file, test = pin.split("::")
            if os.path.basename(path) == file:
                assert f"def {test}(" not in text, pin


@pytest.mark.parametrize("path", sorted(
    glob.glob(os.path.join(HERE, "manifest*.json"))), ids=os.path.basename)
def test_a_rehearsal_reads_the_real_files_readers_under_their_names(path):
    """A rehearsal's manifest lists tiny cells of its own; a per-layer
    entry of it that ``BENCHMARK.json`` has too is that entry, to the
    letter but for the cells — the CPU rehearses the reader the chip
    runs, under the name the ledger records."""
    m = mc.real()
    real = {x["name"]: x for x in m["per_layer"]}
    mine = harness.load_manifest(path)
    assert json.dumps(mine)                     # it parses
    for x in mine["per_layer"]:
        harness.find_reader(ROOT, mine, x["name"])
        if x["name"] in real:
            assert dict(real[x["name"]], workloads=None) == \
                dict(x, workloads=None), x["name"]
