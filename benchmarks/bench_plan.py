"""Planner benchmark: gradient-based capacity planning vs the dense
provisioning grid it replaces.

One provisioning question — the smallest steady/jsq fleet whose exact
p99 meets the SLO — answered two ways on the SAME exact vector
runtime and the SAME SeedSequence spawn tree:

1. **Dense grid**: every integer fleet in the box at full repetition
   count, the way a sweep would answer it.  ``n_grid * reps`` exact
   cell evaluations; the optimum is the smallest fleet whose mean
   objective meets the target.
2. **Gradient planner** (``repro.plan``): Adam through the smoothed
   surrogate, then the integer probe ladder re-verified on the exact
   runtime.  ``PlanResult.cell_evals`` counts every exact cell the
   planner consumed.

The committed record (``BENCH_plan.json``) carries the acceptance
gates: the planner's answer must sit inside the grid optimum's 95% CI
at >=10x fewer cell evaluations, the finite-difference gradient checks
must pass, the best start's loss history must descend, and the
continuous optimum must land within tolerance of the hard-twin
bisection oracle (``analytic_capacity``).  A ``--smoke`` run writes
the gitignored ``BENCH_plan.smoke.json`` at CI scale and ``--check``
exits non-zero if any smoke gate fails.

Usage:
    PYTHONPATH=src python benchmarks/bench_plan.py              # full
    PYTHONPATH=src python benchmarks/bench_plan.py --smoke --check
"""
from __future__ import annotations

import argparse
import os
import sys
import time

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, os.path.join(REPO, "src"))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

from benchmarks._record import write_record  # noqa: E402
from repro.plan import (PlanConfig, PlanSpec, analytic_capacity,  # noqa: E402
                        build_plan_data, plan_loss, run_plan)
from repro.scenarios import get  # noqa: E402
from repro.sweep.spec import spawn_seed  # noqa: E402
from repro.vector import (VectorConfig, compile_experiment,  # noqa: E402
                          has_jax, run_cells)

#: the provisioning question, at full and CI scale
FULL = {"qps": 2600.0, "duration": 12.0, "n_clients": 8, "policy": "jsq",
        "slo": 0.02, "n_grid": 24, "reps": 13,
        "steps": 150, "starts": 3, "samples": 16384, "probe_reps": 5}
SMOKE = {"qps": 2600.0, "duration": 5.0, "n_clients": 8, "policy": "jsq",
         "slo": 0.02, "n_grid": 8, "reps": 3,
         "steps": 50, "starts": 1, "samples": 2048, "probe_reps": 2}

SEED = 0
#: continuous-optimum tolerance vs the bisection oracle (servers)
ANALYTIC_TOL = 0.75
ANALYTIC_REL = 0.25
#: full-run headline requirement: grid cells / planner cells
MIN_CELL_SPEEDUP = 10.0


def _mean_ci95(vals) -> tuple:
    vals = np.asarray(vals, float)
    m = float(vals.mean())
    if vals.size < 2:
        return m, float("nan")
    return m, float(1.96 * vals.std(ddof=1) / np.sqrt(vals.size))


def _overrides(p: dict) -> dict:
    return {"qps": p["qps"], "duration": p["duration"],
            "n_clients": p["n_clients"], "policy": p["policy"]}


def dense_grid(p: dict) -> dict:
    """Answer the question the sweep way: every fleet size, full reps,
    one batched exact run."""
    cfg = VectorConfig()
    progs, seeds, labels = [], [], []
    for n in range(1, p["n_grid"] + 1):
        sc = get("steady", seed=SEED, slo=p["slo"], n_servers=n,
                 **_overrides(p))
        prog = compile_experiment(sc.compile())
        for rep in range(p["reps"]):
            progs.append(prog)
            seeds.append((spawn_seed(SEED, n, rep), rep))
            labels.append(n)
    t0 = time.perf_counter()
    results = run_cells(progs, seeds, cfg)
    wall = time.perf_counter() - t0
    rows = []
    for n in range(1, p["n_grid"] + 1):
        vals = [r.p99 for r, k in zip(results, labels) if k == n]
        mean, ci = _mean_ci95(vals)
        rows.append({"n": n, "p99_mean": mean, "p99_ci95": ci,
                     "meets": bool(mean <= p["slo"])})
    feasible = [r for r in rows if r["meets"]]
    opt = feasible[0] if feasible else None
    return {"cells": len(progs), "wall_s": round(wall, 3),
            "n_opt": None if opt is None else opt["n"],
            "p99_mean": None if opt is None else opt["p99_mean"],
            "p99_ci95": None if opt is None else opt["p99_ci95"],
            "rows": rows}


def fd_checks(p: dict) -> dict:
    """End-to-end d(plan_loss)/d(capacity) vs central differences, in
    float64 — the same gate tests/test_plan.py enforces."""
    import jax
    import jax.numpy as jnp
    from jax import enable_x64

    data = build_plan_data("steady", slo=p["slo"], objective="p99",
                           overrides=_overrides(p),
                           samples=min(p["samples"], 4096), seed=SEED)
    cfg = PlanConfig()
    rows = []
    with enable_x64():
        def loss(x):
            return plan_loss({"capacity": x}, data, cfg)[0]

        for x0 in (2.5, 4.0, 6.0):
            x = jnp.asarray(x0, jnp.float64)
            g = float(jax.grad(loss)(x))
            eps = 1e-4
            fd = (float(loss(x + eps)) - float(loss(x - eps))) / (2 * eps)
            ok = abs(g - fd) <= 2e-2 * max(abs(fd), abs(g)) + 1e-8
            rows.append({"x": x0, "grad": g, "fd": fd, "ok": ok})
    return {"rows": rows, "passed": all(r["ok"] for r in rows)}


def run_planner(p: dict) -> tuple:
    spec = PlanSpec(scenario="steady", objective="p99", slo=p["slo"],
                    overrides=_overrides(p), steps=p["steps"],
                    starts=p["starts"], samples=p["samples"],
                    probe_reps=p["probe_reps"], reps=p["reps"], seed=SEED)
    t0 = time.perf_counter()
    res = run_plan(spec)
    wall = time.perf_counter() - t0
    return res, wall


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale; writes the gitignored smoke record")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero if any gate fails")
    args = ap.parse_args(argv)
    if not has_jax():
        print("bench_plan needs jax (the planner differentiates the "
              "surrogate)", file=sys.stderr)
        return 1
    p = SMOKE if args.smoke else FULL

    fd = fd_checks(p)
    print(f"fd gradient checks: {'PASS' if fd['passed'] else 'FAIL'}")

    grid = dense_grid(p)
    print(f"dense grid: {grid['cells']} cells in {grid['wall_s']}s -> "
          f"n_opt={grid['n_opt']} p99={grid['p99_mean']}")

    res, plan_wall = run_planner(p)
    hist = res.starts[res.best_start]["history"]
    head = max(1, min(5, len(hist) // 4))
    loss_descends = bool(hist[-1] <= hist[0] and
                         np.mean(hist[-head:]) <= np.mean(hist[:head]))

    data = build_plan_data("steady", slo=p["slo"], objective="p99",
                           overrides=_overrides(p), samples=p["samples"],
                           seed=SEED)
    x_a = analytic_capacity(data)
    x = res.params["capacity"]
    analytic_ok = bool(abs(x - x_a) <= max(ANALYTIC_TOL,
                                           ANALYTIC_REL * x_a))

    v = res.verified or {}
    ci_overlap = None
    if grid["n_opt"] is not None and v:
        gap = abs(v["mean"] - grid["p99_mean"])
        allow = grid["p99_ci95"] + (0.0 if np.isnan(v["ci95"])
                                    else v["ci95"])
        ci_overlap = bool(gap <= allow)
    speedup = grid["cells"] / max(res.cell_evals, 1)
    same_fleet = bool(grid["n_opt"] == res.n_star)

    gates = {"fd_checks": fd["passed"],
             "loss_descends": loss_descends,
             "analytic_tolerance": analytic_ok,
             "ci_overlap_vs_grid": ci_overlap,
             "exact_verified_feasible": bool(res.feasible)}
    if not args.smoke:
        gates["cell_speedup_10x"] = bool(speedup >= MIN_CELL_SPEEDUP)

    payload = {
        "benchmark": "bench_plan",
        "scale": "smoke" if args.smoke else "full",
        "problem": {**p, "seed": SEED, "objective": "p99",
                    "scenario": "steady"},
        "fd": fd,
        "grid": grid,
        "planner": {
            "continuous_capacity": x,
            "analytic_capacity": round(x_a, 4),
            "best_start": res.best_start,
            "loss_first": hist[0], "loss_last": hist[-1],
            "n_star": res.n_star,
            "verified": v,
            "probes": res.probes,
            "cell_evals": res.cell_evals,
            "wall_s": round(plan_wall, 3),
        },
        "headline": {
            "grid_cells": grid["cells"],
            "planner_cells": res.cell_evals,
            "cell_speedup": round(speedup, 2),
            "wall_speedup": round(grid["wall_s"] / max(plan_wall, 1e-9),
                                  2),
            "same_fleet_as_grid": same_fleet,
        },
        "gates": gates,
    }
    write_record("plan", payload, smoke=args.smoke)
    print(f"planner: {res.cell_evals} cells in {round(plan_wall, 3)}s -> "
          f"n_star={res.n_star} (grid n_opt={grid['n_opt']}); "
          f"cell speedup {round(speedup, 1)}x")
    for k, ok in gates.items():
        print(f"gate {k}: {'PASS' if ok else 'FAIL' if ok is False else 'n/a'}")
    if args.check:
        return 0 if all(v is not False for v in gates.values()) else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
