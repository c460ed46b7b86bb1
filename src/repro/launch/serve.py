"""Serving launcher: N engine replicas behind the TailBench++ harness.

  PYTHONPATH=src python -m repro.launch.serve --arch phi3-mini-3.8b --smoke \
      --replicas 2 --qps 40 --duration 5 --policy jsq

Real wall-clock serving of a real JAX model driven by open-loop clients —
the end-to-end driver for this paper's kind (latency-critical serving).
Runs on the unified ``EngineRuntime`` backend, so ``--scenario`` can
replay any canonical dynamic scenario against real engines (client churn
and server join/drain/fail are honored; hedging/slowdown injections are
simulator-only and reported as skipped).
"""
from __future__ import annotations

import argparse

from repro.core.client import ClientConfig, ConstantQPS
from repro.core.runtime import EngineRuntime


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    # None = "not supplied": lets --scenario reject flags it would ignore
    ap.add_argument("--replicas", type=int, default=None)
    ap.add_argument("--clients", type=int, default=None)
    ap.add_argument("--qps", type=float, default=None)
    # None = "not supplied": a scenario keeps its canonical duration/policy
    # unless the user explicitly overrides them
    ap.add_argument("--duration", type=float, default=None)
    ap.add_argument("--policy", default=None,
                    choices=["round_robin", "jsq", "p2c", "least_connections"])
    ap.add_argument("--scenario", default=None,
                    help="drive a canonical scenario instead of constant-QPS "
                         "clients (see python -m repro.scenarios --list)")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.util import enable_compile_cache
    enable_compile_cache()

    from repro.scenarios.backends import (build_real_engines,
                                          run_experiment_on_real_engines)

    if args.scenario:
        ignored = [f for f, v in (("--replicas", args.replicas),
                                  ("--clients", args.clients),
                                  ("--qps", args.qps)) if v is not None]
        if ignored:
            ap.error(f"{', '.join(ignored)} cannot be combined with "
                     f"--scenario (the scenario defines fleet and clients)")
        from repro.scenarios import get as get_scenario
        overrides = {k: v for k, v in (("duration", args.duration),
                                       ("policy", args.policy)) if v is not None}
        sc = get_scenario(args.scenario, seed=args.seed, **overrides)
        rt = run_experiment_on_real_engines(
            sc.compile(), arch=args.arch, smoke=args.smoke,
            max_batch=args.max_batch, prompt_len=args.prompt_len,
            max_new_tokens=args.max_new, seed=args.seed)
    else:
        duration = 5.0 if args.duration is None else args.duration
        replicas = 2 if args.replicas is None else args.replicas
        n_clients = 2 if args.clients is None else args.clients
        qps = 20.0 if args.qps is None else args.qps
        engines, _, vocab = build_real_engines(
            args.arch, replicas, smoke=args.smoke,
            max_batch=args.max_batch, prompt_len=args.prompt_len,
            max_new_tokens=args.max_new, seed=args.seed)
        clients = [ClientConfig(i, ConstantQPS(qps / n_clients),
                                end_time=duration, seed=args.seed + i)
                   for i in range(n_clients)]
        rt = EngineRuntime(engines, clients, policy=args.policy or "jsq",
                           duration=duration,
                           prompt_len=args.prompt_len,
                           max_new_tokens=args.max_new,
                           vocab=vocab, seed=args.seed)
        rt.run()
    for inj in rt.unsupported:
        print(f"note: injection {inj.kind}@{inj.at:g}s is simulator-only "
              f"(skipped on the engine backend)")
    s = rt.telemetry.overall()
    print(f"served n={s.n}  mean={s.mean*1e3:.1f}ms  p50={s.p50*1e3:.1f}ms  "
          f"p95={s.p95*1e3:.1f}ms  p99={s.p99*1e3:.1f}ms")
    for cid in rt.telemetry.clients():
        cs = rt.telemetry.client(cid)
        print(f"  client {cid}: n={cs.n} p99={cs.p99*1e3:.1f}ms")
    return s


if __name__ == "__main__":
    main()
