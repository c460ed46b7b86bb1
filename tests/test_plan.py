"""Differentiable capacity planning: gradient correctness of every
smoothed primitive (finite-difference checks in float64), seeded
soft-vs-hard forward agreement on the canonical scenarios, the
rank-plan unification contract, and the planner/sweep integration.

The FD checks run under ``jax.enable_x64`` and avoid jit
so central differences resolve at ``eps ~ 1e-5``; the agreement tests
reuse the vector runtime's reparameterized draws, so hard and soft
modes see the SAME noise and the tolerances below are deterministic
margins, not statistical ones.
"""
from __future__ import annotations

import math

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402
from jax import enable_x64  # noqa: E402

from repro.plan import (DEFAULT_BOXES, OBJECTIVES, PlanConfig, PlanError,
                        PlanSpec, analytic_capacity, build_plan_data,
                        hard_metrics, plan_loss, plan_spec_from_sweep,
                        run_plan, surrogate_metrics)
from repro.scenarios import names
from repro.sweep import Sweep, run_sweep
from repro.sweep.spec import spawn_seed
from repro.vector import VectorConfig, compile_experiment, run_cells
from repro.vector.soft import (RHO_MAX, censor_weight, smooth_min,
                               smooth_rho, soft_erlang_c, soft_quantiles,
                               soft_waterfill, stable_sigmoid)

_BIG = 1e18


def _fd_check(f, x0: float, eps: float = 1e-5, rtol: float = 5e-3,
              atol: float = 1e-8):
    """Central-difference check of ``jax.grad(f)`` at scalar ``x0``,
    in float64 (inside the caller's enable_x64 scope)."""
    x = jnp.asarray(x0, jnp.float64)
    g = float(jax.grad(f)(x))
    fd = (float(f(x + eps)) - float(f(x - eps))) / (2.0 * eps)
    assert abs(g - fd) <= rtol * max(abs(fd), abs(g)) + atol, \
        f"grad {g:.8g} vs FD {fd:.8g} at x={x0}"
    return g


# ---------------------------------------------------------------------------
# Finite-difference gradient checks, one per smoothed primitive
# ---------------------------------------------------------------------------
def test_fd_smooth_min():
    with enable_x64():
        # (the exact tie a == b is a measure-zero AD subgradient point
        # of the min+|a-b| rewrite — skip it, FD can't resolve a choice)
        for a0 in (0.3, 0.95, 1.05, 1.4):
            _fd_check(lambda a: smooth_min(jnp, a, 1.0, 0.1), a0)
        # always a lower bound on the hard min
        assert float(smooth_min(jnp, 0.9, 1.0, 0.1)) <= 0.9


def test_fd_smooth_rho_gradient_survives_saturation():
    with enable_x64():
        for r0 in (0.5, 0.95, 1.0, 1.3):
            g = _fd_check(lambda r: smooth_rho(jnp, r, 0.05), r0)
            assert g > 0.0, f"zero slope at rho={r0}"
        # ceiling still holds (the whole point of the soft clip)
        assert float(smooth_rho(jnp, 5.0, 0.05)) <= RHO_MAX + 1e-6


def test_fd_censor_weight():
    with enable_x64():
        # grad wrt completion time near the horizon and far from it
        for c0 in (7.8, 8.0, 8.5):
            _fd_check(lambda c: censor_weight(jnp, 1.0, c, 8.0,
                                              jnp.inf, 0.1), c0)
        # unfailed server: fail sigmoids saturate to exactly 1
        w_inf = float(censor_weight(jnp, 1.0, 2.0, 8.0, jnp.inf, 0.1))
        w_far = float(stable_sigmoid(jnp, jnp.asarray((8.0 - 2.0) / 0.1)))
        assert w_inf == pytest.approx(w_far, abs=1e-12)


def test_fd_soft_waterfill_and_mass_conservation():
    U = jnp.asarray([[0.2, 0.5, _BIG]])
    with enable_x64():
        U64 = U.astype(jnp.float64)

        def fill0(total):
            return soft_waterfill(jnp, U64, jnp.reshape(total, (1,)),
                                  0.05)[0, 0]

        for t0 in (0.1, 0.4, 1.5):
            _fd_check(fill0, t0)
        # mass conservation is exact at any temperature...
        for tau in (0.01, 0.05, 0.5):
            fill = soft_waterfill(jnp, U64, jnp.asarray([0.7]), tau)
            assert float(jnp.sum(fill)) == pytest.approx(0.7, rel=1e-9)
            # ...and masked lanes get exact zeros
            assert float(fill[0, 2]) == 0.0


def test_fd_soft_erlang_c():
    with enable_x64():
        for c0 in (1.5, 3.4, 7.9):
            _fd_check(lambda c: soft_erlang_c(jnp, c, 0.8, 64, 0.05), c0,
                      rtol=1e-2)
        for r0 in (0.4, 0.9, 1.1):
            _fd_check(lambda r: soft_erlang_c(jnp, 4.0, r, 64, 0.05), r0,
                      rtol=1e-2)


def test_soft_erlang_c_matches_textbook_at_integers():
    """tau -> 0 at integer capacity recovers the exact Erlang-C law."""
    def erlang_c_exact(c: int, rho: float) -> float:
        a = c * rho
        ssum = sum(a ** k / math.factorial(k) for k in range(c))
        top = a ** c / math.factorial(c)
        return top / ((1.0 - rho) * ssum + top)

    for c in (1, 2, 8):
        for rho in (0.3, 0.7, 0.9):
            got = float(soft_erlang_c(np, np.asarray(float(c)),
                                      np.asarray(rho), 64, 1e-4))
            assert got == pytest.approx(erlang_c_exact(c, rho), rel=1e-3)


def test_fd_soft_quantiles_shift_invariance():
    rng = np.random.default_rng((0x9A71, 0, 1))
    lat = np.sort(rng.exponential(size=256))
    with enable_x64():
        base = jnp.asarray(lat, jnp.float64)[None, :]
        w = jnp.ones_like(base)

        def p99(shift):
            return soft_quantiles(base + shift, w, qs=(99.0,),
                                  band_frac=2e-3)[0, 0]

        # a uniform shift moves every quantile by exactly that shift
        g = _fd_check(p99, 0.0, rtol=1e-2)
        assert g == pytest.approx(1.0, rel=1e-3)


def test_soft_quantiles_forward_agreement_unit_weights():
    """Narrow-band soft quantiles on unit weights converge to
    np.percentile's linear interpolation (the hard head's law)."""
    rng = np.random.default_rng((0x9A71, 0, 2))
    lat = rng.exponential(size=2048).astype(np.float32)
    qs = (50.0, 95.0, 99.0)
    soft = np.asarray(soft_quantiles(
        jnp.asarray(lat)[None, :], jnp.ones((1, lat.size)), qs=qs,
        band_frac=1e-6)[0])
    hard = np.percentile(lat, qs)
    np.testing.assert_allclose(soft, hard, rtol=5e-3)


def test_fd_plan_loss_end_to_end():
    """The whole planner gradient: d(plan_loss)/d(capacity) matches
    central differences through fluid scan, Erlang head, censoring and
    the quantile surrogate at once."""
    data = build_plan_data("steady", slo=0.02, objective="p99",
                           overrides={"duration": 4.0, "qps": 2200.0,
                                      "policy": "jsq", "n_clients": 8},
                           samples=2048)
    cfg = PlanConfig()
    with enable_x64():
        def loss(x):
            return plan_loss({"capacity": x}, data, cfg)[0]

        for x0 in (2.0, 3.5, 6.0):
            _fd_check(loss, x0, eps=1e-4, rtol=2e-2)


# ---------------------------------------------------------------------------
# Rank-plan unification: the surrogate consumes the exact kernel's plan
# ---------------------------------------------------------------------------
def test_soft_quantiles_reuses_exact_rank_plan(monkeypatch):
    """``soft_quantiles`` must anchor on ``repro.kernels.ref``'s
    ``quantile_ranks`` — bit-identical (pos, lo, hi), not a lookalike."""
    import repro.kernels.ref as ref

    captured = {}
    real = ref.quantile_ranks

    def spy(n_eff, qs):
        out = real(n_eff, qs)
        captured["plan"] = tuple(np.asarray(o) for o in out)
        return out

    monkeypatch.setattr(ref, "quantile_ranks", spy)
    lat = jnp.linspace(0.0, 1.0, 512)[None, :]
    qs = (50.0, 95.0, 99.0)
    soft_quantiles(lat, jnp.ones_like(lat), qs=qs)
    assert "plan" in captured, "surrogate bypassed the exact rank plan"
    expect = tuple(np.asarray(o) for o in real(jnp.asarray([512.0]), qs))
    for got, want in zip(captured["plan"], expect):
        assert np.array_equal(got, want), (got, want)


# ---------------------------------------------------------------------------
# Soft-vs-hard forward agreement on the canonical scenarios
# ---------------------------------------------------------------------------
_AGREE_DUR = {"steady": 8.0, "flash-crowd": 9.0, "diurnal-fleet": 10.0,
              "server-failure": 8.0, "elastic-autoscale": 10.0,
              "batched-serving": 6.0, "churn-storm": 8.0,
              "retry-storm": 9.0, "correlated-failure": 10.0,
              "gray-failure": 8.0, "flash-crowd-autoscale": 12.0}
#: extra overrides: agreement probes the smoothing, so scenarios that
#: deliberately saturate run at a sub-saturating operating point here
#: (the soft censoring model diverges under sustained rho>1 — that
#: regime is covered by the chaos/bench suites on the exact runtime)
_AGREE_KW = {"flash-crowd-autoscale": {"peak_qps": 2000.0}}
#: relative quantile deviation budget; measured worst case is 6.1%
#: (flash-crowd p99 and flash-crowd-autoscale p99), the rest sit
#: below 4%
_AGREE_RTOL = 0.12

_HEAVY = ("diurnal-fleet", "elastic-autoscale", "churn-storm",
          "correlated-failure", "flash-crowd-autoscale")


def _agreement_params():
    for name in sorted(_AGREE_DUR):
        marks = (pytest.mark.slow,) if name in _HEAVY else ()
        yield pytest.param(name, marks=marks)


@pytest.mark.parametrize("scenario", _agreement_params())
def test_soft_hard_forward_agreement(scenario):
    """soft=True with tau=0.05 keeps the forward pass within a few
    percent of the exact runtime — SAME draws, so the sample counts are
    identical and only the smoothing can move the quantiles."""
    from repro.scenarios import get
    exp = get(scenario, duration=_AGREE_DUR[scenario], seed=3,
              **_AGREE_KW.get(scenario, {})).compile()
    prog = compile_experiment(exp)
    seeds = [(spawn_seed(3, 0, 0), 0)]
    hard = run_cells([prog], seeds, VectorConfig(backend="jax"))[0]
    soft = run_cells([prog], seeds,
                     VectorConfig(backend="jax", soft=True))[0]
    assert soft.n == hard.n, "reparameterized draws must be shared"
    for m in ("p50", "p95", "p99"):
        h, s = getattr(hard, m), getattr(soft, m)
        assert abs(h - s) <= _AGREE_RTOL * max(abs(h), 1e-9), \
            f"{scenario} {m}: hard {h:.6g} vs soft {s:.6g}"
    assert abs(hard.mean - soft.mean) <= 0.05 * max(hard.mean, 1e-9)


def test_agreement_covers_every_canonical_scenario():
    """If a scenario is added, the agreement table must grow with it."""
    assert sorted(_AGREE_DUR) == sorted(names())


# ---------------------------------------------------------------------------
# Plan model contracts
# ---------------------------------------------------------------------------
_STEADY_OV = {"duration": 6.0, "qps": 2600.0, "policy": "jsq",
              "n_clients": 8}


def test_build_plan_data_freezes_draws():
    d1 = build_plan_data("steady", slo=0.02, overrides=_STEADY_OV,
                         samples=1024)
    d2 = build_plan_data("steady", slo=0.02, overrides=_STEADY_OV,
                         samples=1024)
    assert d1.ts.shape == (1024,)
    assert d1.pooled            # jsq routes through the shared queue
    np.testing.assert_array_equal(d1.ts, d2.ts)
    np.testing.assert_array_equal(d1.svc, d2.svc)
    assert d1.target == 0.02    # defaults to the SLO


def test_build_plan_data_rejects_bad_specs():
    with pytest.raises(PlanError):
        build_plan_data("steady", slo=0.02, objective="p42")
    with pytest.raises(PlanError):
        build_plan_data("steady", slo=0.0)
    with pytest.raises(PlanError):    # no smoothed law for batched serving
        build_plan_data("batched-serving", slo=0.5,
                        overrides={"duration": 4.0})


def test_surrogate_matches_hard_twin():
    """tau=0.05 surrogate vs its tau->0 numpy twin at several fleet
    sizes: same draws, so only smoothing separates them."""
    data = build_plan_data("steady", slo=0.02, overrides=_STEADY_OV,
                           samples=8192)
    cfg = PlanConfig()
    for x in (4.0, 6.0, 8.0):
        soft = surrogate_metrics({"capacity": x}, data, cfg)
        hard = hard_metrics({"capacity": x}, data, cfg)
        for m in ("p50", "p95", "p99", "mean"):
            s, h = float(soft[m]), hard[m]
            assert abs(s - h) <= 0.15 * max(abs(h), 1e-9), \
                f"x={x} {m}: soft {s:.6g} vs hard {h:.6g}"
    # deep overload: smooth_rho deliberately departs from the hard clip
    # (that's where the gradient survives) — only the order must hold
    s = float(surrogate_metrics({"capacity": 3.0}, data, cfg)["p99"])
    h = hard_metrics({"capacity": 3.0}, data, cfg)["p99"]
    assert abs(s - h) <= 0.5 * h


def test_analytic_capacity_is_the_feasibility_knee():
    data = build_plan_data("steady", slo=0.02, overrides=_STEADY_OV,
                           samples=8192)
    x_star = analytic_capacity(data)
    below = hard_metrics({"capacity": 0.8 * x_star}, data)["p99"]
    at = hard_metrics({"capacity": x_star}, data)["p99"]
    assert at <= data.target < below


# ---------------------------------------------------------------------------
# Optimizer schedule (the planner's constant-lr mode)
# ---------------------------------------------------------------------------
def test_lr_schedule_constant_vs_cosine():
    from repro.training.optimizer import OptConfig, lr_at
    const = OptConfig(lr=0.1, warmup_steps=10, total_steps=100,
                      schedule="constant")
    cosine = OptConfig(lr=0.1, warmup_steps=10, total_steps=100,
                       schedule="cosine")
    step = jnp.asarray(80, jnp.int32)
    assert float(lr_at(const, step)) == pytest.approx(0.1)
    assert float(lr_at(cosine, step)) < 0.1
    # warmup ramps both
    early = jnp.asarray(5, jnp.int32)
    assert float(lr_at(const, early)) == pytest.approx(0.05)
    with pytest.raises(ValueError):
        lr_at(OptConfig(schedule="linear"), step)


# ---------------------------------------------------------------------------
# Planner driver
# ---------------------------------------------------------------------------
def test_run_plan_converges_to_analytic_optimum():
    """Continuous phase only (verify=False keeps this tier-1 cheap):
    Adam through the surrogate must land near the hard-twin bisection
    optimum, and the recorded loss history must actually descend."""
    spec = PlanSpec(scenario="steady", objective="p99", slo=0.02,
                    overrides=_STEADY_OV, steps=60, starts=2,
                    samples=4096, verify=False)
    res = run_plan(spec)
    data = build_plan_data("steady", slo=0.02, overrides=_STEADY_OV,
                           samples=4096)
    x_a = analytic_capacity(data)
    x = res.params["capacity"]
    assert abs(x - x_a) <= max(0.75, 0.25 * x_a), (x, x_a)
    hist = res.starts[res.best_start]["history"]
    assert hist[-1] < hist[0]
    assert res.verified is None and res.cell_evals == 0
    assert res.spec["target"] == 0.02


def test_run_plan_rejects_bad_specs():
    with pytest.raises(PlanError):
        run_plan(PlanSpec(params={"warp": (1.0, 0.0, 2.0)}))
    with pytest.raises(PlanError):
        run_plan(PlanSpec(params={"scale_threshold": None}))
    with pytest.raises(PlanError):
        run_plan(PlanSpec(objective="p42"))


@pytest.mark.slow
def test_run_plan_integer_ladder_on_exact_runtime():
    """Full pipeline: the rounding ladder must return the smallest
    integer fleet whose exact-runtime p99 meets the target, and every
    exact cell must be counted."""
    spec = PlanSpec(scenario="steady", objective="p99", slo=0.02,
                    overrides=_STEADY_OV, steps=60, starts=1,
                    samples=4096, probe_reps=3, reps=5)
    res = run_plan(spec)
    assert res.n_star is not None and res.feasible
    assert res.verified["mean"] <= res.verified["target"] \
        + res.verified["ci95"]
    probed = {p["n"] for p in res.probes}
    assert res.n_star in probed
    # below the answer must have been probed and found infeasible
    # (unless the box floor stopped the walk)
    if res.n_star - 1 in probed:
        below = [p for p in res.probes if p["n"] == res.n_star - 1]
        assert not below[-1]["meets"]
    assert res.cell_evals == \
        len(res.probes) * spec.probe_reps + spec.reps


# ---------------------------------------------------------------------------
# Sweep integration (mode="optimize")
# ---------------------------------------------------------------------------
def _optimize_sweep(**opt) -> Sweep:
    block = {"scenario": "steady", "slo": 0.02, "steps": 30, "starts": 1,
             "samples": 2048, "verify": False,
             "params": {"capacity": [4.0, 1.0, 24.0]}, **opt}
    return Sweep(name="plan-steady", factory=None, mode="optimize",
                 optimize=block, fixed=dict(_STEADY_OV), reps=3,
                 base_seed=0)


def test_sweep_optimize_mode_roundtrip(tmp_path):
    frame = run_sweep(_optimize_sweep())
    assert "plan" in frame.spec
    phases = {r.params["phase"] for r in frame.rows}
    assert phases == {"optimize"}           # verify=False: no ladder rows
    assert not frame.errors
    path = tmp_path / "plan.json"
    frame.to_json(str(path))
    from repro.sweep.results import ResultFrame
    back = ResultFrame.from_json(str(path))
    assert back.spec["plan"]["params"] == frame.spec["plan"]["params"]


def test_sweep_optimize_spec_validation():
    sweep = _optimize_sweep()
    assert sweep.point_dicts() == []
    spec = plan_spec_from_sweep(sweep)
    assert spec.scenario == "steady" and spec.reps == 3
    assert spec.overrides == _STEADY_OV
    with pytest.raises(PlanError):
        plan_spec_from_sweep(_optimize_sweep(warp=1))
    bad = _optimize_sweep()
    del bad.optimize["slo"]
    with pytest.raises(PlanError):
        plan_spec_from_sweep(bad)
    with pytest.raises(ValueError):
        Sweep(name="x", factory=None, mode="optimize")  # no optimize block


# ---------------------------------------------------------------------------
# Lint: grad-traced bodies are traced scopes
# ---------------------------------------------------------------------------
def test_lint_treats_grad_bodies_as_traced():
    from repro.analysis.lint.engine import lint_text
    text = ("import jax\n"
            "def _loss(p):\n"
            "    if p > 0:\n"
            "        return p\n"
            "    return -p\n"
            "vg = jax.value_and_grad(_loss)\n")
    findings = lint_text(text, rel="plan/x.py")
    assert any(f.rule == "jit-python-branch" for f in findings)
    # the same body with no autodiff call site is plain Python
    free = text.replace("vg = jax.value_and_grad(_loss)\n", "")
    assert not any(f.rule == "jit-python-branch"
                   for f in lint_text(free, rel="plan/x.py"))


def test_objectives_cover_the_vector_summary():
    """Every objective the planner accepts must be extractable from an
    exact VectorResult (the ladder depends on it)."""
    from repro.vector import VectorResult
    fields = set(VectorResult.__dataclass_fields__)
    for obj in OBJECTIVES:
        assert obj == "slo_frac" or obj in fields
    assert set(DEFAULT_BOXES) == {"capacity", "hedge_delay", "admit",
                                  "scale_threshold"}
