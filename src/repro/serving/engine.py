"""Batched inference engine with continuous batching.

Slot-based: ``max_batch`` sequences decode together; free slots are refilled
by prefilling queued prompts (prompt lengths are bucket-padded to bound jit
recompiles).  Step-driven so the TailBench++ harness can drive it in real
time: each ``step()`` performs one prefill (if a request is waiting and a
slot is free) or one batched decode step, and returns completion events.

This is the "ModelBackend" service the paper's clients hit; per-request
latency decomposes into queue wait (admission) + service (prefill+decode).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ATTN_SWA, MAMBA, ArchConfig
from repro.core import spans
from repro.core.profiles import apply_service_noise
from repro.models import param as P
from repro.models import registry as R


@dataclass
class Request:
    req_id: int
    prompt: np.ndarray             # (L,) int32
    max_new_tokens: int
    submitted_at: float = 0.0
    queued_at: float = 0.0         # on the spans' clock
    prefilled_at: Optional[float] = None
    tokens_out: list = field(default_factory=list)


@dataclass
class Completion:
    req_id: int
    tokens: list
    ttft: float                    # time to first token (from submit)
    latency: float                 # total sojourn


class StubEngine:
    """Engine-protocol stand-in: no model, just timed service slots.

    Serves each request after a profile-sampled service time on one of
    ``workers`` parallel slots — the wall-clock analogue of ``SimServer``.
    Lets ``EngineRuntime``, the scenario CLI and the parity tests exercise
    the real-time path without weights or a JIT compile.  With a clock
    that exposes ``advance_to`` (``repro.core.runtime.VirtualClock``),
    ``step()`` jumps virtual time to the next completion the way a real
    engine's blocking decode step consumes wall time.
    """

    def __init__(self, profile, *, workers: int = 1, speed: float = 1.0,
                 service_noise: float = 0.0, seed: int = 0,
                 clock: Callable[[], float] = time.monotonic):
        self.profile = profile
        self.max_batch = workers
        self.speed = speed
        # multiplicative log-normal execution noise, same semantics as
        # SimServer.service_noise (a scenario configuring it gets noisy
        # service on both backends, not just the simulator)
        self.service_noise = service_noise
        self.clock = clock
        self._rng = np.random.default_rng((9176, 0x57AB, seed))
        self.queue: deque[tuple] = deque()      # (req_id, submitted_at)
        self.active: dict[int, tuple] = {}      # req_id -> (finish, start, submit)
        self.total_served = 0
        self.busy_time = 0.0                    # accrued service seconds

    def submit(self, prompt, max_new_tokens: int, req_id: int) -> None:
        self.queue.append((req_id, self.clock()))

    def pending(self) -> int:
        return len(self.queue)

    def n_active(self) -> int:
        return len(self.active)

    def idle(self) -> bool:
        return not self.queue and not self.active

    def step(self) -> list[Completion]:
        now = self.clock()
        done = []
        for rid, (finish, start, submit) in list(self.active.items()):
            if finish <= now:
                del self.active[rid]
                done.append(Completion(rid, [], ttft=start - submit,
                                       latency=finish - submit))
                self.total_served += 1
        while self.queue and len(self.active) < self.max_batch:
            rid, submit = self.queue.popleft()
            dur = apply_service_noise(
                self.profile.sample(self._rng) / self.speed,
                self.service_noise, self._rng)
            self.busy_time += dur
            self.active[rid] = (now + dur, now, submit)
        if not done and self.active and hasattr(self.clock, "advance_to"):
            # mimic a blocking decode step: consume (virtual) time up to
            # the earliest in-flight completion
            self.clock.advance_to(min(f for f, _, _ in self.active.values()))
        return done


class BatchedStubEngine:
    """Engine-protocol stand-in with *real* continuous-batching dynamics.

    Where ``StubEngine`` times each request on an independent slot, this
    drives the shared ``BatchScheduler`` op sequencer against a
    ``BatchedService`` cost model — the same code the simulator's batched
    ``SimServer`` serve loop executes in virtual time.  Per-op costs are
    ``max(compute x batch, memory)`` for a decode step and
    prompt-proportional for a prefill, so throughput saturates with
    occupancy exactly like ``InferenceEngine`` — and exactly like the
    simulator predicts, by construction.

    With a clock exposing ``advance_to`` (``VirtualClock``), ``step()``
    consumes virtual time up to the in-flight op's end the way a real
    engine's blocking decode step consumes wall time.
    """

    serializes_ops = True        # one op at a time: util normalizes per
                                 # engine, not per batch slot

    def __init__(self, service, *, max_batch: int = 8, speed: float = 1.0,
                 service_noise: float = 0.0, seed: int = 0,
                 clock: Callable[[], float] = time.monotonic):
        from repro.core.profiles import BatchScheduler
        self.service = service
        self.max_batch = max_batch
        self.speed = speed
        # per-op multiplicative log-normal noise, mirroring the batched
        # SimServer._kick — without it a noisy scenario would silently
        # run noise-free on the engine backend only
        self.service_noise = service_noise
        self.clock = clock
        self._rng = np.random.default_rng((9176, 0xBA7C, seed))
        self.core = BatchScheduler(service, max_batch)
        self._submit_at: dict[int, float] = {}
        self._prefilled: dict[int, float] = {}
        self._op_end: Optional[float] = None
        # the engine's own timeline: ops chain back-to-back on it even
        # when step() polls late (e.g. a shared VirtualClock advanced by
        # a sibling replica) — otherwise every poll gap would be billed
        # as idle service time and the replica would lose throughput
        self._t = clock()
        self.total_served = 0
        self.busy_time = 0.0                    # accrued op seconds

    @property
    def tokens_done(self) -> int:
        return self.core.tokens_done

    def submit(self, prompt, max_new_tokens: int, req_id: int) -> None:
        self._submit_at[req_id] = self.clock()
        self.core.submit(req_id, len(prompt), max_new_tokens)

    def pending(self) -> int:
        return self.core.pending()

    def n_active(self) -> int:
        return self.core.occupancy()

    def idle(self) -> bool:
        return self._op_end is None and self.core.idle()

    def step(self) -> list[Completion]:
        now = self.clock()
        done: list[Completion] = []
        # replay the engine's background execution up to ``now``: finish
        # due ops and chain the next one at the op boundary (never at the
        # poll instant), admitting only requests already submitted by
        # that boundary — op timing is therefore identical to the
        # simulator's calendar-queue serve loop
        while True:
            if self._op_end is not None:
                if self._op_end > now:
                    break
                end = self._op_end
                self._op_end = None
                self._t = end
                if self.core.op[0] == "prefill":
                    self._prefilled[self.core.op[1].key] = end
                for rid in self.core.finish_op():
                    sub = self._submit_at.pop(rid)
                    first = self._prefilled.pop(rid, end)
                    done.append(Completion(rid, [], ttft=first - sub,
                                           latency=end - sub))
                    self.total_served += 1
            t_op = self._t
            if not self.core.active and self.core.waiting:
                # idle engine: the next op starts when its head arrived
                t_op = max(t_op, self._submit_at[self.core.waiting[0].key])
            dur = self.core.start_op(
                ready=lambda rid: self._submit_at[rid] <= t_op)
            if dur is None:
                break
            dur = apply_service_noise(dur / self.speed, self.service_noise,
                                      self._rng)
            self.busy_time += dur
            self._t = t_op
            self._op_end = t_op + dur
        if not done and self._op_end is not None \
                and hasattr(self.clock, "advance_to"):
            # mimic a blocking engine op: consume (virtual) time up to
            # its end so the runtime's poll loop makes progress
            self.clock.advance_to(self._op_end)
        return done


def make_warmed_engine(cfg: ArchConfig, params, *, max_batch: int = 4,
                       prompt_len: int = 16,
                       max_new_tokens: int = 4) -> "InferenceEngine":
    """Build an InferenceEngine sized for the harness's request shape and
    warm its prefill/decode compile caches, so measured latency is
    serving, not compilation.  Shared by the serving launcher and the
    scenario CLI's real-engine backend."""
    eng = InferenceEngine(cfg, params, max_batch=max_batch,
                          max_len=prompt_len + max_new_tokens + 32)
    eng.submit(np.arange(prompt_len) % cfg.vocab_size, 2, -1)
    eng.run_until_idle()
    return eng


def _bucket(n: int, buckets=(32, 64, 128, 256, 512, 1024, 2048, 4096)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return ((n + 4095) // 4096) * 4096


class InferenceEngine:
    def __init__(self, cfg: ArchConfig, params, *, max_batch: int = 8,
                 max_len: int = 512, impl: str = "auto",
                 moe_impl: str = "dispatch", clock: Callable[[], float] = time.monotonic):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.clock = clock
        self._impl, self._moe_impl = impl, moe_impl
        # batched decode cache (leading dims: groups, batch)
        enc_len = 64 if cfg.enc_dec else None
        self.cache = P.init_tree(
            R.cache_specs(cfg, max_batch, max_len, enc_len=enc_len),
            jax.random.PRNGKey(0))  # repro: noqa[seed-convention] —
        # fixed key: cache init allocates zeroed buffers, never samples
        # the decode step updates the cache in place, in these layouts
        self._cache_layouts = R.decode_layouts(self.cache)
        self.positions = jnp.zeros((max_batch,), jnp.int32)
        self.tokens = jnp.zeros((max_batch,), jnp.int32)
        self.active: list[Optional[Request]] = [None] * max_batch
        self.queue: list[Request] = []
        self._decode = jax.jit(self._decode_impl, donate_argnums=(0,))
        self._prefills: dict[int, Callable] = {}
        # mamba state / SWA ring caches need exact-length prefill (no pads)
        self._exact_prefill = any(k in (MAMBA, ATTN_SWA)
                                  for k in cfg.resolved_pattern)
        self.completed: list[Completion] = []
        self.decode_steps = 0
        self.prefill_count = 0

    # ------------------------------------------------------------------ api
    def submit(self, prompt: np.ndarray, max_new_tokens: int, req_id: int):
        req = Request(req_id, np.asarray(prompt, np.int32), max_new_tokens,
                      submitted_at=self.clock(), queued_at=spans.now())
        self.queue.append(req)

    def pending(self) -> int:
        return len(self.queue)

    def n_active(self) -> int:
        return sum(r is not None for r in self.active)

    def idle(self) -> bool:
        return not self.queue and self.n_active() == 0

    def step(self) -> list[Completion]:
        """One scheduler iteration. Prefill-priority continuous batching."""
        done: list[Completion] = []
        n = self.n_active()
        if self.queue and None in self.active:
            with spans.span("engine.step", kind="prefill", active=n):
                self._admit(self.queue.pop(0), self.active.index(None))
        elif n:
            with spans.span("engine.step", kind="decode", active=n):
                done = self._decode_once(n)
        return done

    def run_until_idle(self, max_steps: int = 100_000) -> list[Completion]:
        out = []
        for _ in range(max_steps):
            if self.idle():
                break
            out.extend(self.step())
        return out

    # ------------------------------------------------------------- internals
    def _prefill_fn(self, bucket: int):
        if bucket not in self._prefills:
            def fn(params, tokens, lengths):
                return R.prefill(self.cfg, params, {"tokens": tokens},
                                 self.max_len, impl=self._impl,
                                 moe_impl=self._moe_impl, lengths=lengths)
            self._prefills[bucket] = jax.jit(fn)
        return self._prefills[bucket]

    def _admit(self, req: Request, slot: int):
        L = len(req.prompt)
        bucket = L if self._exact_prefill else min(_bucket(L), self.max_len)
        spans.mark("engine.queue", req.queued_at, spans.now(), req=req.req_id)
        with spans.span("engine.admit", req=req.req_id, tokens=L,
                        bucket=bucket):
            toks = np.zeros((1, bucket), np.int32)
            toks[0, :L] = req.prompt       # right-pad; pads masked via positions
            with spans.span("engine.prefill.dispatch"):
                logits, cache1, pos1 = self._prefill_fn(bucket)(
                    self.params, jnp.asarray(toks), jnp.asarray([L], np.int32))
            with spans.span("engine.prefill.sync"):
                first = int(jnp.argmax(logits[0]))
            req.tokens_out.append(first)
            req.prefilled_at = self.clock()
            with spans.span("engine.admit.insert"):
                self.cache = jax.tree_util.tree_map(
                    lambda c, p: c.at[:, slot].set(p[:, 0].astype(c.dtype)),
                    self.cache, cache1)
                self.positions = self.positions.at[slot].set(int(pos1[0]))
                self.tokens = self.tokens.at[slot].set(first)
            self.active[slot] = req
            self.prefill_count += 1
            self._maybe_finish(slot)

    def _decode_impl(self, cache, params, tokens, positions):
        logits, new_cache = R.decode_step(self.cfg, params, cache, tokens,
                                          positions,
                                          cache_layouts=self._cache_layouts,
                                          impl=self._impl,
                                          moe_impl=self._moe_impl)
        return jnp.argmax(logits, -1).astype(jnp.int32), new_cache

    def _decode_once(self, n_active: int) -> list[Completion]:
        with spans.span("engine.decode", active=n_active):
            with spans.span("engine.decode.dispatch"):
                next_tokens, self.cache = self._decode(
                    self.cache, self.params, self.tokens, self.positions)
            self.positions = self.positions + 1
            self.tokens = next_tokens
            self.decode_steps += 1
            with spans.span("engine.decode.sync"):
                toks = np.asarray(next_tokens)
            done = []
            with spans.span("engine.decode.emit"):
                for slot, req in enumerate(self.active):
                    if req is None:
                        continue
                    req.tokens_out.append(int(toks[slot]))
                    c = self._maybe_finish(slot)
                    if c:
                        done.append(c)
        return done

    def _maybe_finish(self, slot: int) -> Optional[Completion]:
        req = self.active[slot]
        if req and len(req.tokens_out) >= req.max_new_tokens:
            now = self.clock()
            c = Completion(req.req_id, req.tokens_out,
                           ttft=req.prefilled_at - req.submitted_at,
                           latency=now - req.submitted_at)
            self.completed.append(c)
            self.active[slot] = None
            return c
        return None
