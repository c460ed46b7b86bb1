"""Sweep engine: spec expansion, seed derivation, executor determinism,
failure capture, artifact round-trip, and the run_repeated shim."""
import json
from dataclasses import replace
from functools import partial

import pytest

from repro.core.client import ClientConfig, ConstantQPS
from repro.core.harness import Experiment, ServerSpec, run, run_repeated
from repro.sweep import (Axis, ResultFrame, SEEDERS, Sweep,
                         experiment_factory, run_sweep, scenario_factory,
                         spawn_seed)

BASE = Experiment(clients=(ClientConfig(0, ConstantQPS(150), seed=2),
                           ClientConfig(1, ConstantQPS(150), seed=7)),
                  servers=(ServerSpec(0), ServerSpec(1)),
                  app="masstree", duration=2.0, seed=2)


def _grid_sweep(**kw) -> Sweep:
    opts = dict(name="grid", factory=experiment_factory(BASE),
                axes=(Axis("policy", ("round_robin", "jsq")),
                      Axis("duration", (1.0, 2.0))),
                reps=2, base_seed=5,
                metrics=("n", "mean", "p50", "p95", "p99", "dropped"))
    opts.update(kw)
    return Sweep(**opts)


# ---------------------------------------------------------------------------
# Spec expansion
# ---------------------------------------------------------------------------
def test_grid_points_order():
    sw = _grid_sweep()
    pts = sw.point_dicts()
    assert pts == [{"policy": "round_robin", "duration": 1.0},
                   {"policy": "round_robin", "duration": 2.0},
                   {"policy": "jsq", "duration": 1.0},
                   {"policy": "jsq", "duration": 2.0}]
    assert len(sw.tasks()) == 8          # 4 points x 2 reps


def test_zip_and_points_modes():
    sw = _grid_sweep(mode="zip")
    assert sw.point_dicts() == [{"policy": "round_robin", "duration": 1.0},
                                {"policy": "jsq", "duration": 2.0}]
    with pytest.raises(ValueError):
        _grid_sweep(mode="zip",
                    axes=(Axis("a", (1, 2)), Axis("b", (1, 2, 3))))
    sw = Sweep(name="p", factory=experiment_factory(BASE), mode="points",
               points=({"policy": "jsq"},), reps=1)
    assert sw.point_dicts() == [{"policy": "jsq"}]
    # no axes / no points: a legal 1-point (reps-only) sweep
    sw = Sweep(name="r", factory=experiment_factory(BASE), reps=3)
    assert sw.point_dicts() == [{}]
    # points under a non-points mode would be silently dropped: reject
    with pytest.raises(ValueError, match="points"):
        Sweep(name="bad", factory=experiment_factory(BASE),
              points=({"policy": "jsq"},), reps=1)


def test_fixed_params_merge():
    sw = _grid_sweep(fixed={"app": "xapian"})
    assert all(p["app"] == "xapian" for p in sw.point_dicts())


# ---------------------------------------------------------------------------
# Seed derivation
# ---------------------------------------------------------------------------
def test_spawn_seeder_never_collides():
    """The failure mode of seed + 1000*(rep+1): point 0/rep 1 replays
    point 1000/rep 0.  The SeedSequence spawn never collides."""
    seen = {spawn_seed(base, point, rep)
            for base in (0, 1000, 2000) for point in range(20)
            for rep in range(10)}
    assert len(seen) == 3 * 20 * 10
    # the legacy arithmetic DOES collide across base seeds: base 0 at
    # rep 1 replays base 1000 at rep 0, and so on
    legacy = [base + 1000 * (r + 1)
              for base in (0, 1000, 2000) for r in range(10)]
    assert len(set(legacy)) < len(legacy)


def test_named_seeders():
    assert SEEDERS["run-repeated"](7, 3, 2) == (7 + 3000, 2)
    assert SEEDERS["fixed"](7, 3, 2) == (7, 0)
    assert SEEDERS["rep"](7, 3, 2) == (9, 0)
    seed, stream = SEEDERS["spawn"](7, 3, 2)
    assert stream == 2 and seed == spawn_seed(7, 3, 2)
    with pytest.raises(ValueError):
        _grid_sweep(seeder="nope")


# ---------------------------------------------------------------------------
# Executor determinism (the core contract)
# ---------------------------------------------------------------------------
def test_serial_and_process_executors_identical():
    """Same Sweep on serial, 2-worker, and 8-worker executors ->
    identical ResultFrame rows (bit-for-bit, any scheduling order)."""
    sw = _grid_sweep()
    frames = [run_sweep(sw, executor="serial", progress=None),
              run_sweep(sw, executor="process", workers=2, progress=None),
              run_sweep(sw, executor="process", workers=8, progress=None)]
    dumps = [json.dumps([r.to_dict() for r in f.rows]) for f in frames]
    assert dumps[0] == dumps[1] == dumps[2]
    assert all(r.ok for r in frames[0].rows)
    # and the sweep rows replay the exact runs the harness would produce
    row = frames[0].rows[0]
    sim = run(replace(BASE, seed=row.seed, **row.params), rep=row.stream)
    assert sim.recorder.overall().p99 == row.metrics["p99"]


def _report_platform(ctx):
    import jax
    raise RuntimeError(f"platform={jax.default_backend()}")


def _real_engine_point(ctx):
    from repro.scenarios.backends import build_real_engines
    build_real_engines("phi3-mini-3.8b", 1, smoke=True)


@pytest.mark.parametrize("factory,needle", [
    (_report_platform, "platform=cpu"),
    (_real_engine_point, "executor='serial'"),
])
def test_process_workers_stay_off_the_accelerator(factory, needle):
    """Process-executor workers run with JAX pinned to the CPU, and real
    engines refuse to start there instead of contending for the chip."""
    sw = Sweep(name="dev", factory=factory, axes=(Axis("x", (0,)),), reps=1)
    frame = run_sweep(sw, executor="process", workers=1, progress=None)
    (row,) = frame.rows
    assert not row.ok and needle in row.error, row.error


def test_poisoned_point_records_error_row():
    """A raising point must not kill the sweep: it records an error row
    while every other (point, rep) completes."""
    sw = _grid_sweep(axes=(Axis("policy", ("round_robin", "does-not-exist")),))
    for executor in ("serial", "process"):
        frame = run_sweep(sw, executor=executor, progress=None)
        assert len(frame.rows) == 4
        bad = [r for r in frame.rows
               if r.params["policy"] == "does-not-exist"]
        good = [r for r in frame.rows if r.params["policy"] == "round_robin"]
        assert len(bad) == 2 and all(not r.ok and "KeyError" in r.error
                                     for r in bad)
        assert len(good) == 2 and all(r.ok and r.metrics["n"] > 0
                                      for r in good)
    # aggregation survives the failed point (NaN mean, n_failed counted)
    agg = {a["params"]["policy"]: a for a in frame.aggregate("p99")}
    assert agg["does-not-exist"]["n_failed"] == 2
    assert agg["does-not-exist"]["mean"] != agg["does-not-exist"]["mean"]
    assert agg["round_robin"]["n_reps"] == 2


def test_result_frame_json_roundtrip_exact():
    sw = _grid_sweep(telemetry=True, per_client=True, reps=1)
    frame = run_sweep(sw, progress=None)
    rt = ResultFrame.from_json(frame.to_json())
    assert json.dumps(rt.to_dict()) == json.dumps(frame.to_dict())
    # float values survive bit-for-bit, including the telemetry series
    assert rt.rows[0].metrics["p99"] == frame.rows[0].metrics["p99"]
    assert rt.rows[0].series == frame.rows[0].series
    assert rt.rows[0].clients == frame.rows[0].clients


def test_csv_emission(tmp_path):
    sw = _grid_sweep(reps=2)
    frame = run_sweep(sw, progress=None)
    flat = tmp_path / "flat.csv"
    agg = tmp_path / "agg.csv"
    frame.to_csv(str(flat))
    frame.to_csv(str(agg), aggregated="p99")
    lines = flat.read_text().strip().splitlines()
    assert len(lines) == 1 + len(frame.rows)
    assert lines[0].startswith("policy,duration,rep,seed,n,")
    alines = agg.read_text().strip().splitlines()
    assert len(alines) == 1 + len(frame.points())
    assert "ci95" in alines[0]


def test_compare_welch():
    """Per-point Welch compare: a sweep against itself retains H0."""
    sw = _grid_sweep(reps=4, axes=(Axis("policy", ("jsq",)),))
    a = run_sweep(sw, progress=None)
    b = run_sweep(sw, progress=None)
    w = a.compare(b, "p99", policy="jsq")
    assert w.retained and w.n_a == w.n_b == 4 and abs(w.t_stat) < 1e-12


# ---------------------------------------------------------------------------
# Runtime-backend axis + scenario factories
# ---------------------------------------------------------------------------
def test_runtime_axis_runs_both_backends():
    sw = Sweep(name="backends", factory=scenario_factory("steady"),
               axes=(Axis("runtime", ("sim", "engine")),),
               fixed={"duration": 2.0, "qps": 150.0, "n_servers": 1,
                      "n_clients": 2},
               reps=1, metrics=("n", "p99"))
    frame = run_sweep(sw, progress=None)
    by_rt = {r.params["runtime"]: r for r in frame.rows}
    assert by_rt["sim"].ok and by_rt["engine"].ok
    assert by_rt["sim"].metrics["n"] > 0
    # both backends consume identical arrival streams; the engine loop
    # additionally drains requests in flight at the horizon, so it can
    # only complete at least as many
    assert by_rt["engine"].metrics["n"] >= by_rt["sim"].metrics["n"]


def test_runtime_axis_with_experiment_factory():
    """The 'runtime' axis is executor-owned: an Experiment-based factory
    must not choke on it (it is not an Experiment field)."""
    sw = Sweep(name="exp-backends", factory=experiment_factory(BASE),
               axes=(Axis("runtime", ("sim", "engine")),),
               reps=1, metrics=("n", "p99"))
    frame = run_sweep(sw, progress=None)
    assert all(r.ok for r in frame.rows), [r.error for r in frame.rows]
    assert {r.params["runtime"] for r in frame.rows} == {"sim", "engine"}


def test_error_text_csv_quoting(tmp_path):
    """Free-form exception text (commas and all) survives the CSV."""
    import csv as _csv
    sw = _grid_sweep(axes=(Axis("policy", ("round_robin",)),), reps=1,
                     mode="zip")
    frame = run_sweep(sw, progress=None)
    frame.rows[0].error = 'Boom: a, b, and "c"'
    path = tmp_path / "err.csv"
    frame.to_csv(str(path))
    with open(path, newline="") as f:
        recs = list(_csv.DictReader(f))
    assert recs[0]["error"] == 'Boom: a, b, and "c"'


# ---------------------------------------------------------------------------
# run_repeated: thin shim over a 1-point sweep, bit-compatible
# ---------------------------------------------------------------------------
def test_run_repeated_shim_bit_compatible():
    exp = replace(BASE, duration=3.0)
    (mean, ci), vals = run_repeated(exp, reps=4)
    expected = []
    for rep in range(4):
        sim = run(replace(exp, seed=exp.seed + 1000 * (rep + 1)), rep=rep)
        expected.append(sim.recorder.overall().p99)
    assert vals == expected
    assert ci > 0.0


def test_run_repeated_propagates_failures():
    """fail_fast: the shim raises the ORIGINAL exception type at the
    first failing repetition, like the loop it replaced."""
    exp = replace(BASE, policy="does-not-exist")
    with pytest.raises(KeyError, match="does-not-exist"):
        run_repeated(exp, reps=2)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def test_cli_named_sweep(tmp_path, capsys):
    from repro.sweep.__main__ import main
    rc = main(["steady", "--axis", "qps=100,200", "--reps", "1",
               "--set", "duration=1.5", "--quiet",
               "--out", str(tmp_path)])
    assert rc == 0
    out = capsys.readouterr().out
    assert "sweep=steady" in out and "errors=0" in out
    frame = ResultFrame.from_json(str(tmp_path / "steady.json"))
    assert len(frame.rows) == 2 and all(r.ok for r in frame.rows)
    assert (tmp_path / "steady.csv").exists()


def test_cli_file_declaration(tmp_path):
    from repro.sweep.__main__ import main
    decl = {"name": "filedecl", "scenario": "steady", "reps": 1,
            "axes": {"qps": [120.0]}, "fixed": {"duration": 1.5},
            "metrics": ["n", "p99"]}
    path = tmp_path / "sweep.json"
    path.write_text(json.dumps(decl))
    rc = main(["--file", str(path), "--quiet", "--out", str(tmp_path)])
    assert rc == 0
    frame = ResultFrame.from_json(str(tmp_path / "filedecl.json"))
    assert frame.spec["axes"] == {"qps": [120.0]}
    assert frame.rows[0].metrics["n"] > 0


@pytest.mark.parametrize("env", [None, "elsewhere"])
def test_compile_cache_dir(monkeypatch, tmp_path, env):
    """The CLI mains keep JAX's compile cache at one fixed path inside
    the checkout, and an explicit JAX_COMPILATION_CACHE_DIR wins."""
    import os

    import jax

    from repro.util import COMPILE_CACHE_DIR, enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / env))
    try:
        got = enable_compile_cache()
        if env is None:
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            assert got == COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == str(tmp_path / env)
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
