"""CPU rehearsal of ``chip_smoke.py``: its phases at ``GPTConfig.tiny()``
in-process (control flow, arguments, comparisons), and the refusal of
``main()`` to run anything without a TPU."""

import os
import sys

import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import chip_smoke  # noqa: E402
from hetu_tpu.models import GPTConfig  # noqa: E402


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    assert chip_smoke.main(["--chips", "4"]) != 0
    assert capsys.readouterr().out == ""      # no result line


@pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.int8],
                         ids=["fp32", "int8"])
def test_train_then_serve_phases_at_tiny_size(cache_dtype):
    cfg = GPTConfig.tiny()
    facts, params = chip_smoke.run_train(
        cfg, seq_len=64, batch_rows=4, steps=6, expect_kernels=False)
    assert facts["losses"][-1] < facts["losses"][0]
    out = chip_smoke.run_serve(
        cfg, params, max_len=64, prompt_lens=(5, 17, 33), max_tokens=6,
        prefill_chunk=16, cache_dtype=cache_dtype, slots=2,
        expect_kernels=False)
    assert out["requests"] == 3
    assert out["identical_to_generate"] == 3, out["near_ties"]


def test_four_chip_phase_on_virtual_devices():
    out = chip_smoke.run_four_chip(
        GPTConfig.tiny(), seq_len=64, batch_rows=4, steps=3,
        expect_kernels=False)
    assert len(out["dp2tp2"]["param_devices"]) == 4
    assert out["dp2tp2"]["collectives"]
