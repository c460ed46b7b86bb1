"""Model FLOPs of the traced prefill steps (true prompt lengths) over the
device time of the prefill program ``jit_fn`` times the bf16 peak."""
from chipbench import counts as C
from chipbench.metrics._common import mfu


def read(run):
    return mfu(run, "prefill", "jit_fn",
               lambda m, lengths: C.prefill_flops(m, lengths[0]))
