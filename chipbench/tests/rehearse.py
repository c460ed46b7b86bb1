"""Run a cell's measuring path on the CPU at the program's -smoke sizes:
interpret-mode Pallas, shorter lengths, a short ramp and window.  The
device check is skipped here; ``run.py`` itself still refuses a CPU."""
from __future__ import annotations

import time

from chipbench import run as RUN
from repro.configs.base import get_config

#: rehearsal peaks: only that the arithmetic runs, never a device number
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def smoke_file(config: dict) -> dict:
    """A configuration file's keys at the program's -smoke sizes, as the
    reference the file names writes them."""
    ref = RUN.reference(config["reference"])
    return ref.smoke_file(get_config(config["program_arch"] + "-smoke"))


def small(traffic: dict, served_tokens: int = 40) -> dict:
    """Prompts cut by 8, outputs to a median of at most 40 tokens (so that
    decode still runs some tens of steps and requests finish in the
    window), a 1 s ramp, and a check over ``served_tokens``."""
    t = dict(traffic, ramp_s=1.0, block_s=2.0)
    out_cut = max(1, traffic["output_tokens"]["median"] // 40)
    for k, cut in (("prompt_tokens", 8), ("output_tokens", out_cut)):
        t[k] = dict(t[k], median=max(2, t[k]["median"] // cut),
                    max=max(4, t[k]["max"] // cut))
    t["check"] = dict(t["check"], served_tokens=served_tokens,
                      max_requests=max(3, served_tokens // 12))
    return t


def rehearse(workload: str, *, seconds: float = 3.0, trace: bool = False,
             seed: int = 2 ** 31 + 5, fault=None, control: bool = False,
             rate=None, served_tokens: int = 40):
    """The cell's own correctness limit (``checks/<cell>.json``) holds."""
    cell = RUN.load_cell(workload)
    cell.traffic = small(cell.traffic, served_tokens)
    if rate is not None:
        cell.traffic["rate_per_s"] = rate
    return RUN.measure(cell, seed=seed, seconds=seconds, trace=trace,
                       peak=PEAK, fault=fault, control=control,
                       smoke_config=smoke_file(cell.config),
                       t_start=time.perf_counter())
