#!/usr/bin/env python3
"""The chip benchmark's one entry.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the TPU it is started on: makes
the weights from ``--seed``, warms the cell's shapes from the compile
cache in the checkout, ramps the traffic up, measures ``--seconds``, then
checks what the timed path served against the plain reference.  The last
line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics`` and ``device`` (and, with
``--trace 1``, ``breakdown``).  With no TPU, or fewer chips than the cell
asks for, it exits non-zero and prints no result.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name in ``BENCHMARK.json``:
``configs/<config>.json``, ``traffic/<traffic>.json``,
``checks/<workload>.json`` (the limits of the correctness check) and
``metrics/<metric>.py``; the configuration file names its plain reference,
with its weight rules and model counts, ``references/<reference>.py``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import functools  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from types import ModuleType  # noqa: E402
from typing import Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REFERENCES = os.path.join(HERE, "references")
#: what the harness reads of every reference module (its model counts,
#: such as ``decode_flops``, are read by metric files of its own)
REFERENCE_API = ("dims", "logits_at", "smoke_file", "check_program",
                 "leaf_rules")
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def fail(msg: str, code: int = 2) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    ref: ModuleType


def load_cell(name: str) -> Cell:
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        fail(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(os.path.join(ROOT, conf["file"]))
    ref_name = config.get("reference")
    if not (isinstance(ref_name, str) and ref_name.isidentifier()
            and os.path.isfile(os.path.join(REFERENCES, f"{ref_name}.py"))):
        fail(f"configuration {conf['name']!r} ({conf['file']}) names "
             f"reference {ref_name!r}: there is no chipbench/references/"
             f"{ref_name}.py")
    ref = reference(ref_name)
    missing = [a for a in REFERENCE_API if not hasattr(ref, a)]
    if missing:
        fail(f"reference {ref_name!r} lacks {', '.join(missing)}")

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]
    return Cell(name, w, config,
                load_json(os.path.join(HERE, "traffic", w["traffic"] + ".json")),
                load_json(os.path.join(HERE, "checks", name + ".json")),
                mine(bench["end_to_end"]), mine(bench["per_layer"]), ref)


def _module(path: str, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference(name: str, where: Optional[str] = None) -> ModuleType:
    """The reference module ``<where>/<name>.py`` (``where`` defaults to
    ``REFERENCES``), loaded once a file."""
    return _reference(os.path.abspath(
        os.path.join(where or REFERENCES, name + ".py")))


@functools.lru_cache(maxsize=None)
def _reference(path: str) -> ModuleType:
    name = os.path.splitext(os.path.basename(path))[0]
    return _module(path, "chipbench_reference_" + name)


def reader(metric: str):
    return _module(os.path.join(HERE, "metrics", metric + ".py"),
                   "chipbench_metric_" + metric.replace(".", "_")).read


def device_check(chips: int) -> dict:
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        fail(f"needs a TPU; JAX found platform {d.platform!r} "
             f"({d.device_kind}, {len(devices)} device(s))")
    if len(devices) < chips:
        fail(f"the cell needs {chips} chips; JAX found {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


@dataclass
class Run:
    """What the metric readers see of one run."""
    seconds: float
    window: tuple
    t0: float
    arrivals: list
    reqs: dict
    steps: list
    setup_s: float
    model: dict
    peak: dict
    trace: Optional[dict] = None
    trace_s: float = 0.0
    #: the configuration's reference module, with its model counts
    ref: Optional[ModuleType] = None


def measure(cell: Cell, *, seed: int, seconds: float, trace: bool,
            peak: dict, smoke_config: Optional[dict] = None, fault=None,
            control: bool = False, t_start: Optional[float] = None) -> dict:
    """Set up, run the window, read the metrics and check the output.
    ``smoke_config`` rehearses the cell on the CPU with the program's
    -smoke sizes (its configuration file keys at those sizes); ``fault``
    breaks the engine after warm-up; ``control`` also reads the float8
    control's gap and judges it by the same limit (``control_correct``)."""
    from chipbench import check as CHK
    from chipbench import serve as S
    from chipbench import trace as TRC

    trace_dir = os.path.join(ROOT, "chiprun_out", "chipbench_trace") \
        if trace else None
    if trace_dir and os.path.isdir(trace_dir):
        shutil.rmtree(trace_dir)
    config = smoke_config or cell.config
    res = S.run_serving(cell.config, cell.traffic, seed=seed,
                        seconds=seconds, trace_dir=trace_dir,
                        ref=cell.ref, smoke=smoke_config is not None,
                        fault=fault,
                        t_start=T_START if t_start is None else t_start)
    rec = res["rec"]
    run = Run(seconds=seconds, window=res["window"], t0=rec.t0,
              arrivals=rec.arrivals, reqs=rec.reqs, steps=rec.steps,
              setup_s=res["setup_s"], model=cell.ref.dims(config),
              peak=peak, ref=cell.ref)
    out: dict = {"memory_peak_bytes": res["memory_peak_bytes"],
                 "compiles_in_window": res["compiles_in_window"],
                 "buckets": res["buckets"]}
    t_start = T_START if t_start is None else t_start
    if trace:
        path = S.trace_file(trace_dir)
        if path is None:
            fail("the traced run left no trace", 3)
        S.note(t_start, f"reading the trace ({os.path.getsize(path)} bytes)")
        run.trace = TRC.read(path)
        run.trace_s = rec.trace_span[1] - rec.trace_span[0]
        out["trace"] = {"busy_s": run.trace["busy_s"],
                        "window_s": run.trace_s,
                        "kernels": run.trace["kernels"],
                        "modules": run.trace["modules"],
                        "breakdown": TRC.breakdown(run.trace)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = cell.per_layer if trace else cell.end_to_end
    out["metrics"] = {}
    for m in metrics:
        v = reader(m["name"])(run)
        if v is not None:
            out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
    w0, w1 = run.window
    out["extra"] = {name: reader(name)(run) for name in
                    ("ttft_p80_ms", "ttft_p90_ms", "itl_p95_ms",
                     "output_tok_s", "queue_wait_p90_ms",
                     "decode_step_ms.chat")}
    submitted = [r for r in rec.reqs.values() if r.submit <= w1]
    finished = [r for r in submitted if r.done is not None and r.done <= w1]
    wrong = [r.index for r in finished if len(r.tokens) != r.new_tokens]
    chk = cell.traffic["check"]
    sample = CHK.sample([r for r in finished if r.index not in wrong], seed,
                        chk["served_tokens"], chk["max_requests"])
    S.note(t_start, f"checking {len(sample)} requests against the reference")
    t = time.perf_counter()
    gaps = CHK.logit_gaps(cell.ref, config, seed, sample, control=control) \
        if sample else {"max_logit_gap": float("inf")}
    out["check_s"] = time.perf_counter() - t
    out.update(attempted=len(submitted), finished=len(finished),
               failed=len(wrong), gaps=gaps)
    limit = cell.limits["max_logit_gap"]["limit"]
    out["checks"] = {
        "max_logit_gap": {"value": gaps["max_logit_gap"], "limit": limit},
        "wrong_length_answers": {"value": len(wrong), "limit": 0}}
    out["correct"] = gaps["max_logit_gap"] <= limit and not wrong
    if control:
        out["control_correct"] = gaps.get("control_max_logit_gap",
                                          float("inf")) <= limit
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # a run that hangs prints every thread's stack and ends before the
    # allowance of a checkout's first run, 1200 s, runs out
    faulthandler.dump_traceback_later(1140, exit=True)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail("the program under test (src/repro) is not in this checkout")
    cell = load_cell(args.workload)
    device = device_check(cell.workload["chips"])
    from repro.util import enable_compile_cache
    cache = enable_compile_cache()
    import jax
    # every program, however quick to compile, is kept in the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if device["kind"] not in peaks:
        fail(f"no peaks for device kind {device['kind']!r} in peaks.json")
    print(f"device: {device}; compile cache: {cache}", file=sys.stderr)
    out = measure(cell, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), peak=peaks[device["kind"]])
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": out["metrics"],
              "device": device}
    if args.trace:
        tr = out["trace"]
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = tr["breakdown"]
        print(f"trace kernels: {tr['kernels']}", file=sys.stderr)
        print(f"trace modules: {tr['modules']}", file=sys.stderr)
    print(f"run: finished {out['finished']} of {out['attempted']} submitted; "
          f"buckets {out['buckets']}; compiles in window "
          f"{out['compiles_in_window']}; check took {out['check_s']:.1f}s; "
          f"{out['gaps']}", file=sys.stderr)
    print(f"also: {out['extra']}", file=sys.stderr)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    result["checks"] = out["checks"]
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
