"""The control, kept at a size a CPU test run can hold: the reference in
float8 put in the program's place has to come out as not correct at the
cell's committed limit (``checks/<cell>.json``), over the same served
tokens on which the program comes out correct.  On the chip the same
comparison, at each cell's own size and load, is
``chipbench/tools/readings.py``; PERF.md gives its readings and the limits
set from them."""
import dataclasses

import pytest

import rehearse as RH
from repro.configs import base


@pytest.fixture
def mid_size(monkeypatch):
    """The -smoke configurations widened to d_model 256 and 8 layers, so
    that float8 rounding shows in the logits as it does at full size."""
    real = base.get_config

    def widened(name):
        cfg = real(name)
        if name.endswith("-smoke"):
            cfg = dataclasses.replace(cfg, d_model=256, num_layers=8,
                                      num_heads=4, num_kv_heads=4,
                                      head_dim=64, d_ff=512, vocab_size=1024)
        return cfg
    monkeypatch.setattr(base, "get_config", widened)
    monkeypatch.setattr(RH, "get_config", widened)


@pytest.mark.parametrize("cell", ["phi3-chat", "stablelm-rag"])
def test_control_reads_above_the_program(mid_size, cell):
    out = RH.rehearse(cell, rate=2.0, seconds=6.0, control=True,
                      served_tokens=160)
    g = out["gaps"]
    assert g["served_tokens"] >= 100, g
    assert out["correct"], out["checks"]
    assert not out["control_correct"], g
