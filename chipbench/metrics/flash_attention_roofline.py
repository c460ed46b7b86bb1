"""Roofline share of the Pallas kernel ``flash_attention`` in the traced
prefill steps: causal attention over each true prompt length; bound by
compute at these lengths."""
from chipbench import counts as C
from chipbench.metrics._common import roofline


def read(run):
    return roofline(run, "prefill", "flash_attention",
                    lambda m, lengths: C.flash_attention(m, lengths[0]))
