"""Roofline share of the Pallas kernel ``decode_attention`` in the traced
decode steps: keys and values read up to each active slot's position;
bound by HBM."""
from chipbench import counts as C
from chipbench.metrics._common import roofline


def read(run):
    return roofline(run, "decode", "decode_attention", C.decode_attention)
