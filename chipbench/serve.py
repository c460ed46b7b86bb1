"""One serving cell: the program's ``InferenceEngine`` behind its
``EngineRuntime``, offered an open-loop schedule, measured over a window.

The engine is handed to the runtime inside ``Recorder``, a thin proxy that
times every ``submit`` and ``step`` on the host clock and notes each
request's tokens as the host receives them.  The runtime's own loop never
ends while work is queued, so the proxy (and the runtime's sleep) raise
``WindowClosed`` at the window's end: whatever is unfinished then counts
as not done.
"""
from __future__ import annotations

import gc
import glob
import os
import sys
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from chipbench import traffic as TR

clock = time.perf_counter


class WindowClosed(Exception):
    """The measured window has ended."""


@dataclass
class Req:
    index: int
    sched: float                  # scheduled arrival, host clock
    prompt_len: int
    new_tokens: int
    submit: Optional[float] = None
    prompt: Optional[np.ndarray] = None
    prefill_start: Optional[float] = None
    bucket: Optional[int] = None
    token_times: list = field(default_factory=list)
    tokens: Optional[list] = None  # as the engine returned them
    done: Optional[float] = None


@dataclass
class Step:
    kind: str                     # "prefill" | "decode"
    start: float
    end: float
    lengths: tuple                # prefill: (prompt,), decode: context of
    bucket: int = 0               # each active slot
    traced: bool = False


class Replay:
    """Client generator that replays a fixed schedule to the runtime."""

    def __init__(self, cfg, arrivals):
        self.cfg = cfg
        self._it = iter(arrivals)
        self.last_sizes = (0, 0)

    def next_arrival(self):
        a = next(self._it, None)
        if a is None:
            return None
        self.last_sizes = (a.prompt_len, a.new_tokens)
        return a.at, 0.0


class Recorder:
    """Engine proxy: forwards to the engine and records host spans."""

    def __init__(self, engine, arrivals, ramp_s: float, seconds: float,
                 warmed=(), trace_dir: Optional[str] = None):
        self.inner = engine
        self.arrivals = arrivals
        self.ramp_s, self.seconds = ramp_s, seconds
        self.t0: Optional[float] = None
        self.reqs: dict[int, Req] = {}
        self.steps: list[Step] = []
        self.trace_dir = trace_dir
        self.trace_span: Optional[tuple] = None
        self._tracing = False
        self.warmed = set(warmed)
        self._bucket = watch_buckets(engine)

    # the runtime's clock: its first call inside run() is the run's start
    def clock(self) -> float:
        t = clock()
        if self.t0 is None:
            self.t0 = t
        return t

    @property
    def window(self) -> tuple:
        w0 = self.t0 + self.ramp_s
        return w0, w0 + self.seconds

    def sleep(self, dt: float) -> None:
        end = self.window[1]
        time.sleep(max(0.0, min(dt, end - clock())))
        if clock() >= end:
            raise WindowClosed

    # ---- engine protocol -------------------------------------------------
    @property
    def max_batch(self):
        return self.inner.max_batch

    def pending(self):
        return self.inner.pending()

    def n_active(self):
        return self.inner.n_active()

    def idle(self):
        return self.inner.idle()

    def submit(self, prompt, max_new_tokens, req_id):
        a = self.arrivals[req_id]
        if (len(prompt), max_new_tokens) != (a.prompt_len, a.new_tokens):
            raise RuntimeError(f"request {req_id} is not the scheduled one")
        self.reqs[req_id] = Req(req_id, self.t0 + a.at, a.prompt_len,
                                a.new_tokens, submit=clock(),
                                prompt=np.asarray(prompt, np.int32))
        self.inner.submit(prompt, max_new_tokens, req_id)

    def step(self):
        """One engine step, labelled by what it was seen to do: the
        engine's prefill and decode counters, the queue, and each request's
        tokens, read before and after.  A step whose observed effect does
        not fit one prefill or one decode raises, so a change to the
        engine's scheduling fails here instead of mislabelling spans."""
        now = clock()
        if now >= self.window[1]:
            raise WindowClosed
        self._trace_boundary(now)
        eng = self.inner
        queued = [r.req_id for r in eng.queue]
        held = {r.req_id: len(r.tokens_out) for r in eng.active
                if r is not None}
        n_prefill, n_decode = eng.prefill_count, eng.decode_steps
        self._bucket.clear()
        t_a = clock()
        if self._tracing:
            import jax
            with jax.profiler.TraceAnnotation("bench.step"):
                out = eng.step()
        else:
            out = eng.step()
        t_b = clock()
        now_held = {r.req_id: len(r.tokens_out) for r in eng.active
                    if r is not None}
        now_held.update((c.req_id, len(c.tokens)) for c in out)
        gained = {i: now_held[i] - held.get(i, 0) for i in now_held
                  if now_held[i] != held.get(i, 0)}
        d_prefill = eng.prefill_count - n_prefill
        d_decode = eng.decode_steps - n_decode
        if (d_prefill, d_decode) == (1, 0):
            left = set(queued) - {r.req_id for r in eng.queue}
            if len(left) != 1 or len(self._bucket) != 1:
                raise RuntimeError(f"a prefill step admitted {sorted(left)} "
                                   f"with buckets {self._bucket}")
            head = self.reqs[left.pop()]
            bucket = self._bucket[0]
            if bucket < head.prompt_len or bucket not in self.warmed:
                raise RuntimeError(f"prefill of {head.prompt_len} tokens ran "
                                   f"at bucket {bucket}; warmed "
                                   f"{sorted(self.warmed)}")
            if gained != {head.index: 1}:
                raise RuntimeError(f"a prefill step changed tokens {gained}")
            head.prefill_start, head.bucket = t_a, bucket
            step = Step("prefill", t_a, t_b, (head.prompt_len,), bucket)
            active = [head]
        elif (d_prefill, d_decode) == (0, 1):
            if gained != {i: 1 for i in held}:
                raise RuntimeError(f"a decode step changed tokens {gained}, "
                                   f"held {held}")
            active = [self.reqs[i] for i in held]
            # decode reads the cache up to and including the new token
            step = Step("decode", t_a, t_b, tuple(
                r.prompt_len + len(r.token_times) for r in active))
        elif (d_prefill, d_decode) == (0, 0) and not gained and not held:
            return out
        else:
            raise RuntimeError(f"one step made {d_prefill} prefills and "
                               f"{d_decode} decode steps")
        step.traced = self._tracing
        self.steps.append(step)
        for r in active:
            r.token_times.append(t_b)
        for c in out:
            r = self.reqs[c.req_id]
            r.tokens, r.done = list(c.tokens), t_b
        return out

    # ---- tracing ---------------------------------------------------------
    def _trace_boundary(self, now: float) -> None:
        """Start the profiler at the first step boundary half-way through
        the ramp; ``stop_trace`` ends it once the window has closed.  Both
        block the host for a while, so neither falls inside the window."""
        if self.trace_dir is None or self.trace_span is not None:
            return
        if now >= self.t0 + 0.5 * self.ramp_s:
            import jax
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=trace_options())
            self._tracing = True
            self.trace_span = (clock(), None)

    def stop_trace(self) -> None:
        if self._tracing:
            import jax
            end = clock()
            jax.profiler.stop_trace()
            self._tracing = False
            self.trace_span = (self.trace_span[0], end)


def trace_options():
    """Device ops and the host's annotations, without the Python tracer:
    it records every Python call of the serving loop, which slows the
    traced window and makes a trace too large to read back in time."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def note(t_start: float, what: str) -> None:
    """One line on standard error: seconds since the process started."""
    print(f"chipbench: {clock() - t_start:8.1f}s {what}", file=sys.stderr,
          flush=True)


def watch_buckets(engine) -> list:
    """Note the bucket of every prefill the engine compiles or runs.  The
    engine's prefill programs are its per-bucket ``_prefill_fn``: if that
    hook goes, the list stays empty and ``Recorder.step`` raises."""
    if hasattr(engine._prefill_fn, "seen"):
        return engine._prefill_fn.seen
    seen: list = []
    inner = engine._prefill_fn

    def prefill_fn(bucket):
        seen.append(bucket)
        return inner(bucket)
    prefill_fn.seen = seen
    engine._prefill_fn = prefill_fn
    return seen


def warm_buckets(engine, arrivals, bucket_of) -> list:
    """Compile every prefill bucket the schedule reaches, the decode step
    and the admit, by serving one two-token request per bucket.  ``bucket_of``
    predicts the engine's bucket of a prompt length; -> the buckets the
    engine was seen to run, which the window's prefills must keep to."""
    seen = watch_buckets(engine)
    firsts: dict = {}
    for a in arrivals:
        firsts.setdefault(bucket_of(a.prompt_len), a.prompt_len)
    for b, n in sorted(firsts.items()):
        engine.submit(np.arange(n) % 7, 2, -1 - b)
        engine.run_until_idle()
    engine.completed.clear()
    return sorted(set(seen))


def free(engine) -> None:
    """Release the engine's device state before the reference runs."""
    import jax
    for leaf in jax.tree_util.tree_leaves(
            (engine.params, engine.cache, engine.tokens, engine.positions)):
        leaf.delete()
    gc.collect()


def build(config: dict, traffic: dict, *, seed: int, ref,
          smoke: bool = False):
    """Weights from the seed (one jitted call on the device, by the
    reference module ``ref``'s weight rules), the engine sized for the
    traffic, and every shape the traffic uses compiled or loaded from the
    compile cache.  -> (engine, bucket_of)."""
    import jax

    from chipbench import weights as W
    from repro.configs.base import get_config
    from repro.models import registry as R
    from repro.serving import engine as E

    cfg = get_config(config["program_arch"] + ("-smoke" if smoke else ""))
    if not smoke:
        bad = ref.check_program(cfg, config)
        if bad:
            raise SystemExit(f"program config {cfg.name} differs from the "
                             f"configuration file: {bad}")
    p_max, n_max = TR.max_lengths(traffic)
    max_len = p_max + n_max + 32
    params = W.make_tree(R.abstract_params(cfg), seed, rules=ref.leaf_rules)
    jax.block_until_ready(params)
    eng = E.InferenceEngine(cfg, params,
                            max_batch=config["assumed"]["max_batch"],
                            max_len=max_len, impl="pallas")
    return eng, lambda n: min(E._bucket(n), max_len)


def reset(engine) -> None:
    """Empty the engine's queue and slots (the cache is overwritten on the
    next admit) so that one engine can serve several windows."""
    engine.queue.clear()
    engine.active = [None] * engine.max_batch
    engine.completed.clear()


def serve_window(engine, traffic: dict, *, seed: int, seconds: float,
                 warmed, trace_dir: Optional[str] = None,
                 rate: Optional[float] = None):
    """Offer the traffic's schedule through ``EngineRuntime`` until the
    window closes.  -> (Recorder, programs compiled inside the window)."""
    from repro.core.client import ClientConfig, ConstantQPS
    from repro.core.runtime import EngineRuntime

    if rate is not None:
        traffic = dict(traffic, rate_per_s=rate)
    ramp = traffic["ramp_s"]
    arrivals = TR.schedule(traffic, ramp + seconds + 1.0)
    rec = Recorder(engine, arrivals, ramp, seconds, warmed,
                   trace_dir=trace_dir)
    rt = EngineRuntime([rec], [ClientConfig(0, ConstantQPS(
        traffic["rate_per_s"]), seed=seed % 2 ** 32)],
        duration=ramp + seconds + 60.0, vocab=engine.cfg.vocab_size,
        seed=seed, clock=rec.clock, sleep=rec.sleep)
    rt._gens[0] = Replay(rt._gens[0].cfg, arrivals)
    compiles = CompileCounter()
    try:
        rt.run()
    except WindowClosed:
        pass
    else:
        raise RuntimeError("the schedule ran out before the window closed")
    finally:
        rec.stop_trace()
        compiles.close()
    w0, w1 = rec.window
    return rec, compiles.count_between(w0, w1)


def run_serving(config: dict, traffic: dict, *, seed: int,
                seconds: float, trace_dir: Optional[str], ref,
                smoke: bool = False, fault=None, t_start: float) -> dict:
    """Set up, run the window, and hand back everything the metric readers
    and the check need.  The engine's state is freed before returning."""
    import jax

    note(t_start, "building the engine")
    eng, bucket_of = build(config, traffic, seed=seed, ref=ref, smoke=smoke)
    note(t_start, "weights made; warming up")
    ramp = traffic["ramp_s"]
    buckets = warm_buckets(eng, TR.schedule(traffic, ramp + seconds + 1.0),
                           bucket_of)
    note(t_start, f"warmed buckets {buckets}; serving")
    if fault is not None:
        fault(eng)
    rec, n_compiles = serve_window(eng, traffic, seed=seed, seconds=seconds,
                                   warmed=buckets, trace_dir=trace_dir)
    w0, w1 = rec.window
    note(t_start, "window closed")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    free(eng)
    return {"rec": rec, "window": (w0, w1), "setup_s": w0 - t_start,
            "buckets": buckets, "memory_peak_bytes": peak,
            "compiles_in_window": n_compiles}


class CompileCounter:
    """Counts XLA compilations by host time, to show none falls inside the
    window."""

    def __init__(self):
        import jax
        self.times: list = []
        self._fn = self._on_event
        jax.monitoring.register_event_duration_secs_listener(self._fn)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.times.append(clock())

    def count_between(self, a: float, b: float) -> int:
        return sum(a <= t <= b for t in self.times)

    def close(self) -> None:
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._fn)


def trace_file(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None
