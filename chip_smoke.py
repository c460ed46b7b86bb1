#!/usr/bin/env python3
"""Prove that the main path runs on a TPU, through the entry points a user
calls, and that what it returns is right.

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chips  # the sharded vector grid, 4 chips

One chip, three phases, in one process:

* serving: phi3-mini-3.8b at its published widths, weights generated from
  ``--seed``, built by ``scenarios.backends.build_real_engines`` and driven
  open-loop by ``EngineRuntime``.  Every request must come back with its
  token count, and the Pallas prefill and decode logits must match the
  jnp reference on the same params within ``LOGIT_TOL``;
* the Fig. 1 scalar vector grid (117 cells) through ``run_sweep``, once
  with the resolved impl (which must be Pallas) and once with the
  reference: the rows must be bit-identical;
* a batched-serving vector grid, compared the same way.

The compiled engine steps and the vector scan must hold a Mosaic kernel
(``tpu_custom_call``): nothing may fall back to the reference unseen.
``--four-chips`` runs only the Fig. 1 grid sharded over four chips
against the same grid on one, and requires identical rows.

Any failed check exits non-zero.  With no TPU it exits non-zero before
any work and names the platform JAX found.  The last line of a passing
run is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "phi3-mini-3.8b"
MAX_BATCH, PROMPT, NEW_TOKENS, N_REQUESTS, QPS = 4, 128, 16, 12, 4.0
#: bf16 tolerance on logits, as the relative L2 error
#: ||pallas - ref|| / ||ref||.  The model runs in bf16 and the two
#: attention paths round differently; 32 random-weight layers amplify
#: that.  On a TPU v5e (seed 0) the reference itself moves by 0.078 on
#: decode when its attention probabilities stay f32 instead of bf16, and
#: Pallas differs from it by 0.080 (decode) and 0.085 (prefill), while
#: the kernels alone agree to 0.3%.  A mask, head or block mapping error
#: moves logits by a large fraction of their own size.
LOGIT_TOL = 0.1
BATCHED_QPS = (50.0, 100.0, 150.0, 200.0)


def fail(what: str) -> None:
    raise SystemExit(f"chip_smoke: FAILED: {what}")


def require(ok: bool, what: str) -> None:
    if not ok:
        fail(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def device_check(count: int) -> dict:
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        fail(f"needs a TPU; JAX found platform {d.platform!r} "
             f"({d.device_kind}, {len(devices)} device(s))")
    require(len(devices) >= count,
            f"needs {count} TPU chips; JAX found {len(devices)}")
    log(f"device: {d.platform} {d.device_kind} x{len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# ---------------------------------------------------------------------------
# Serving: phi3-mini-3.8b behind EngineRuntime
# ---------------------------------------------------------------------------
def serving_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.client import ClientConfig, ConstantQPS
    from repro.core.runtime import EngineRuntime
    from repro.kernels.ops import resolve_impl
    from repro.models import registry as R
    from repro.scenarios.backends import build_real_engines

    require(resolve_impl("auto") == "pallas",
            f"engine impl resolves to {resolve_impl('auto')!r}, not pallas")
    t0 = time.perf_counter()
    engines, _, vocab = build_real_engines(
        ARCH, 1, max_batch=MAX_BATCH, prompt_len=PROMPT,
        max_new_tokens=NEW_TOKENS, seed=seed)
    eng = engines[0]
    log(f"serving: {ARCH} weights + warm compile "
        f"{time.perf_counter() - t0:.1f}s (max_batch={eng.max_batch}, "
        f"max_len={eng.max_len})")

    # the compiled steps the engine serves with hold the Pallas kernels
    toks = jnp.zeros((1, PROMPT), jnp.int32)
    lens = jnp.full((1,), PROMPT, jnp.int32)
    require(has_kernel(eng._prefill_fn(PROMPT).lower(
        eng.params, toks, lens).compile()), "prefill has no tpu_custom_call")
    require(has_kernel(eng._decode.lower(
        eng.cache, eng.params, eng.tokens, eng.positions).compile()),
        "decode step has no tpu_custom_call")
    log("serving: tpu_custom_call in the prefill and decode programs")

    clients = [ClientConfig(0, ConstantQPS(QPS), total_requests=N_REQUESTS,
                            seed=seed)]
    rt = EngineRuntime(engines, clients, duration=60.0, prompt_len=PROMPT,
                       max_new_tokens=NEW_TOKENS, vocab=vocab, seed=seed)
    t0 = time.perf_counter()
    rt.run()
    wall = time.perf_counter() - t0
    served = [c for c in eng.completed if c.req_id >= 0]
    n = rt.telemetry.overall().n
    require(rt.dropped == 0, f"{rt.dropped} requests dropped")
    require(n == N_REQUESTS and sorted(c.req_id for c in served)
            == list(range(N_REQUESTS)),
            f"{len(served)} of {N_REQUESTS} requests served")
    short = [c.req_id for c in served if len(c.tokens) != NEW_TOKENS]
    require(not short, f"requests {short} returned the wrong token count")
    ttft = np.array([c.ttft for c in served]) * 1e3
    lat = np.array([c.latency for c in served]) * 1e3
    log(f"serving: {n} requests x {NEW_TOKENS} tokens in {wall:.2f}s; "
        f"ttft p50/p99 {np.percentile(ttft, 50):.1f}/"
        f"{np.percentile(ttft, 99):.1f} ms, latency p50/p99 "
        f"{np.percentile(lat, 50):.1f}/{np.percentile(lat, 99):.1f} ms")

    # Pallas vs reference logits on the same params
    cfg, params = eng.cfg, eng.params
    prompt = jax.random.randint(jax.random.PRNGKey(seed), (1, PROMPT), 0,
                                vocab, jnp.int32)

    def prefill(impl):
        return jax.jit(lambda p, t, n: R.prefill(
            cfg, p, {"tokens": t}, eng.max_len, impl=impl, lengths=n))(
                params, prompt, lens)

    def decode(impl, cache, tok, pos):
        return jax.jit(lambda p, c, t, q: R.decode_step(
            cfg, p, c, t, q, cache_layouts=R.decode_layouts(cache),
            impl=impl)[0])(params, cache, tok, pos)

    ref_logits, ref_cache, pos = prefill("ref")
    pal_logits, _, _ = prefill("pallas")
    tok = jnp.argmax(ref_logits, -1).astype(jnp.int32)
    for name, want, got in (
            ("prefill", ref_logits, pal_logits),
            ("decode", decode("ref", ref_cache, tok, pos),
             decode("pallas", ref_cache, tok, pos))):
        want = np.asarray(want, np.float32)
        got = np.asarray(got, np.float32)
        require(np.isfinite(got).all(), f"{name} logits not finite")
        rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        log(f"serving: {name} logits pallas vs ref: relative L2 {rel:.5g} "
            f"(tol {LOGIT_TOL}), max|diff| "
            f"{float(np.abs(got - want).max()):.5g}, max|ref| "
            f"{float(np.abs(want).max()):.5g}")
        require(rel <= LOGIT_TOL,
                f"{name} logits: relative L2 {rel:.5g} > {LOGIT_TOL}")


# ---------------------------------------------------------------------------
# Vector grids
# ---------------------------------------------------------------------------
def watch_scans(texts: list):
    """Record the compiled text of every vector scan program launched
    until the returned ``stop()`` is called."""
    import repro.vector.runtime as vrt
    build = vrt._jax_runner
    seen: set = set()

    def runner(*a, **kw):
        fn = build(*a, **kw)

        def call(*args):
            if id(fn) not in seen:
                seen.add(id(fn))
                texts.append(fn.lower(*args).compile().as_text())
            return fn(*args)
        return call

    def stop():
        vrt._jax_runner = build
    vrt._jax_runner = runner
    return stop


def run_grid(sweep, cfg, label: str) -> list:
    from repro.sweep import run_sweep
    t0 = time.perf_counter()
    frame = run_sweep(sweep, vector_config=cfg, progress=None)
    wall = time.perf_counter() - t0
    require(not frame.errors, f"{label}: {len(frame.errors)} error rows, "
            f"first: {frame.errors[0].error if frame.errors else ''}")
    log(f"grid {sweep.name} [{label}]: {len(frame.rows)} cells in "
        f"{wall:.2f}s")
    return [json.dumps(r.to_dict(), sort_keys=True) for r in frame.rows]


def compare(sweep, a, b, label_a: str, label_b: str) -> None:
    rows_a = run_grid(sweep, a, label_a)
    rows_b = run_grid(sweep, b, label_b)
    same = sum(x == y for x, y in zip(rows_a, rows_b))
    log(f"grid {sweep.name}: {same}/{len(rows_a)} rows bit-identical "
        f"({label_a} vs {label_b})")
    require(same == len(rows_a) == len(rows_b),
            f"{sweep.name}: {label_a} and {label_b} rows differ")


def fig1_grid():
    from benchmarks.bench_vector import build_grid
    return build_grid(smoke=False, runtime="vector")


def vector_phase() -> None:
    from repro.sweep import Axis, Sweep, scenario_factory
    from repro.vector import VectorConfig

    auto = VectorConfig()
    require(auto.resolve_backend() == "jax",
            f"vector backend resolves to {auto.resolve_backend()!r}")
    require(auto.resolve_impl() == "pallas",
            f"vector impl resolves to {auto.resolve_impl()!r}")
    texts: list = []
    stop = watch_scans(texts)
    fig1 = fig1_grid()
    run_grid(fig1, auto, "pallas, cold")
    stop()
    require(bool(texts) and all("tpu_custom_call" in t for t in texts),
            "the Pallas vector scan has no tpu_custom_call")
    log(f"grid: tpu_custom_call in all {len(texts)} Pallas scan programs")
    compare(fig1, auto, VectorConfig(impl="ref"), "pallas", "ref")
    batched = Sweep(name="batched_serving",
                    factory=scenario_factory("batched-serving"),
                    axes=(Axis("qps", BATCHED_QPS),), reps=3, base_seed=1,
                    runtime="vector",
                    metrics=("n", "mean", "p50", "p95", "p99"))
    compare(batched, auto, VectorConfig(impl="ref"), "pallas", "ref")


def four_chip_phase() -> None:
    from repro.vector import VectorConfig
    four, one = VectorConfig(devices=4), VectorConfig(devices=1)
    require(four.resolve_devices() == 4,
            f"sharded grid resolves to {four.resolve_devices()} devices")
    require(four.resolve_impl() == "pallas",
            f"vector impl resolves to {four.resolve_impl()!r}")
    fig1 = fig1_grid()
    run_grid(fig1, four, "4 chips, cold")
    compare(fig1, four, one, "4 chips", "1 chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the Fig. 1 grid sharded over 4 chips "
                         "against the same grid on one")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated weights and prompts")
    args = ap.parse_args(argv)
    device = device_check(4 if args.four_chips else 1)

    for path in (ROOT, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    from repro.util import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase()
    else:
        serving_phase(args.seed)
        vector_phase()
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    log(f"wall {time.perf_counter() - t0:.1f}s; device 0 peak bytes in use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
