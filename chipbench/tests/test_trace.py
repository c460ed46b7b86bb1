"""The trace reduction against a small recorded trace, worked by hand."""
import os

import pytest
from jax.profiler import ProfileData

from chipbench import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.textproto")


@pytest.fixture(scope="module")
def red():
    with open(DATA) as f:
        return T.reduce(ProfileData.from_text_proto(f.read()).planes)


def test_names():
    assert T.op_name("%decode_attention.6 = bf16[6] custom-call()") \
        == "decode_attention"
    assert T.op_name("%copy_bitcast_fusion.7 = bf16[6] fusion()") \
        == "copy_bitcast_fusion"
    assert T.module_name("jit__decode_impl(1386724149)") == "jit__decode_impl"


def test_modules_and_kernels(red):
    assert red["chips"] == 1
    assert red["modules"] == pytest.approx({"jit__decode_impl": 10e-6,
                                            "jit_fn": 10e-6})
    # Pallas kernels: the ops marked tpu_custom_call
    assert red["kernels"] == pytest.approx({"decode_attention": 2e-6,
                                            "flash_attention": 5e-6})
    # the while loop holds the other ops: its time is not counted twice
    assert "while" not in red["ops"]
    assert red["ops"]["fusion"] == pytest.approx(11e-6)


def test_busy_and_idle(red):
    # busy: [0, 10] and [15, 25] us; the 5 us gap falls in the second
    # bench.step, before the prefill program jit_fn starts
    assert red["busy_s"] == pytest.approx(20e-6)
    assert red["idle"] == pytest.approx({"bench.step before jit_fn": 5e-6})
    b = T.breakdown(red)
    assert b["device_ops"][0] == ["fusion", pytest.approx(11e-6)]
    assert b["idle_gaps"] == [["bench.step before jit_fn",
                               pytest.approx(5e-6)]]
