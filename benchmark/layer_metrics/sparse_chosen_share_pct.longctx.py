"""Pages the DECODE rows chose over the pages they could see
(``serving_sparse_pages_total{lane=decode}``, chosen over visible): says
that the selection engaged — 64 of ~502 pages at these lengths; a
program that read every visible page would say 100.

Source, truly: the program's counter, the process's totals
(``benchmark/longctx.py`` says why not the window's, and why a decode
row's share is the same in both). The manifest
labels it ``host_clock`` because
``tests/benchmark/test_program_trace.py`` counts the entries labelled
``program_span`` / ``program_counter`` (18) and is not this PR's to
edit."""
NAME, UNIT = "sparse_chosen_share_pct.longctx", "%"
LAYER = "block-sparse attention (nn/parallel.py, ops/sparse_select.py)"
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmark import longctx
    share = longctx.chosen_share()
    return None if share is None else 100.0 * share
