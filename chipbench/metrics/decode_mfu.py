"""Model FLOPs of the traced decode steps (active slots, true contexts) over
the device time of the decode program ``jit__decode_impl`` times the bf16
peak."""
from chipbench import counts as C
from chipbench.metrics._common import mfu


def read(run):
    return mfu(run, "decode", "jit__decode_impl", C.decode_flops)
