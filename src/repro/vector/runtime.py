"""The vector execution engine: fixed-step queueing dynamics for every
grid cell at once.

One ``lax.scan`` (or NumPy slot loop) advances the whole grid's state
``(backlog, queue length, load EMA)`` with axes ``[cell, server]``
through ``n_slots`` fixed steps of width ``dt``:

* per-slot Poisson arrival counts and CLT-aggregated service work are
  pre-drawn per cell from its own seeded ``Generator`` (so a cell's
  numbers do not depend on which grid it runs in);
* connection-routed work lands on its replayed server; request-routed
  work (jsq/p2c) is water-filled onto the least-backlogged accepting
  servers — the fluid limit of join-shortest-queue;
* waiting follows the unfinished-work law: an arrival that must queue
  waits ``backlog / (c * speed)``; the probability it queues blends the
  Erlang-C delay probability at the smoothed offered load with a
  backlog-memory term (exact for c=1 by PASTA);
* batched cells advance the roofline step law per slot: occupancy
  ``b = clip(L, 1, max_batch)``, decode throughput ``b / step_time(b)``
  tokens/sec, prefill seconds served with priority — the same
  ``BatchedService`` cost model the event engine executes op by op.

Latency percentiles come from per-request samples (slot drawn from the
realized arrival weights, own service drawn from the exact law, wait
from the slot's state), censored at the horizon and at server-failure
instants exactly like the event engine's recorder, and extracted for
the whole grid chunk in ONE fused quantile pass.

On the jax backend the scan body dispatches through
``repro.kernels.ops``: ``impl="pallas"`` runs each slot advance as one
``pl.pallas_call`` over ``[cell, server]`` tiles (interpret mode off
TPU), ``impl="ref"`` the plain-jnp step, ``"auto"`` picks per
``jax.default_backend()`` with the ``REPRO_FORCE_IMPL`` env override.
Cells are grouped into geometric (T, S) shape buckets (one jit trace
per bucket, not per exact shape) and the cell axis is laid across the
local devices via ``shard_map``.  All three choices are
bit-preserving: every reduction in the step math runs over the server
axis, so ref / pallas-interpret / sharded execution produce identical
rows for identical seeds.
"""
from __future__ import annotations

import math
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Optional, Sequence

import numpy as np

from repro.vector.compile import VectorProgram, compile_experiment

_BIG = 1e18
_EPS = 1e-12
#: offered load above which the stationary wait is diffusion-bounded
_NEAR_CRITICAL = 0.9


def has_jax() -> bool:
    try:
        import jax  # noqa: F401
        return True
    except ImportError:
        # ONLY an absent jax means "fall back to numpy" — a broken
        # install must raise, not silently switch backends
        return False


@dataclass
class VectorConfig:
    dt: float = 0.005               # slot width (seconds)
    samples: int = 32768            # latency-sample budget per cell
    backend: str = "auto"           # auto | jax | numpy
    impl: str = "auto"              # auto | pallas | ref (jax backend;
                                    # REPRO_FORCE_IMPL overrides "auto")
    jit: bool = True                # wrap the jax scan in jax.jit
    devices: int = 0                # cell-axis sharding: 0 = every local
                                    # device (auto), N >= 1 pins the mesh
                                    # size (1 still runs the shard layer)
    bucket: bool = True             # geometric (T, S) shape-bucketing
    max_slot_elems: int = 64_000_000   # chunk cells when T*C*S exceeds this
    jit_cache_size: int = 8         # compiled-runner LRU entries (eviction
                                    # only costs a recompile, never bits)
    pipeline: bool = True           # double-buffer chunks: the device scan
                                    # of chunk k+1 overlaps host finishing
                                    # (quantiles, cache writes) of chunk k
    soft: bool = False              # differentiable mode: smoothed
                                    # water-filling / Erlang-C / censoring
                                    # and the soft quantile head (jax
                                    # backend only; forces impl="ref")
    tau: float = 0.05               # soft-mode temperature (relative)
    band_frac: float = 5e-4         # soft quantile-head bandwidth, as a
                                    # fraction of the effective count

    def resolve_backend(self) -> str:
        if self.backend == "auto":
            return "jax" if has_jax() else "numpy"
        if self.backend == "jax" and not has_jax():
            raise RuntimeError("backend='jax' requested but jax is not "
                               "importable (use 'numpy' or 'auto')")
        return self.backend

    def resolve_impl(self) -> str:
        """Resolved scan-step impl for the jax backend.  Soft mode pins
        the jnp reference path: the Pallas kernels implement only the
        hard step math."""
        if self.soft:
            return "ref"
        from repro.kernels.ops import resolve_impl
        return resolve_impl(self.impl)

    def resolve_devices(self) -> int:
        import jax
        avail = len(jax.local_devices())
        if self.devices <= 0:
            return avail
        return max(1, min(self.devices, avail))


# ---------------------------------------------------------------------------
# Per-cell result
# ---------------------------------------------------------------------------
@dataclass
class VectorResult:
    """Extracted results for one (point, rep) cell."""
    n: int
    mean: float
    p50: float
    p95: float
    p99: float
    dropped: int
    interval: float
    slo: Optional[float]
    server_ids: list
    samples: np.ndarray             # kept latency samples (uniform over
                                    # completed requests)
    sample_ivl: np.ndarray          # completion interval per kept sample
    n_ivl: np.ndarray               # [n_ivls] completions per interval
    util_ivl: np.ndarray            # [n_ivls, S] utilization
    occ_ivl: np.ndarray             # [n_ivls, S] occupancy
    qdepth_ivl: np.ndarray          # [n_ivls, S] queue depth at boundary
    tokens_ivl: Optional[np.ndarray] = None   # [n_ivls, S] tokens/sec
    shed_ivl: Optional[np.ndarray] = None     # [n_ivls] admission-shed
                                              # requests (fluid expectation)


# ---------------------------------------------------------------------------
# The scan step (shared math, numpy or jax namespace)
# ---------------------------------------------------------------------------
def _waterfill(xp, U_eff, total):
    """Distribute ``total`` [C] of work over the least-loaded lanes of
    ``U_eff`` [C, S] (masked lanes carry ``_BIG``): fill to a common
    level.  -> per-lane fill amounts [C, S].

    Sort-free formulation (Pallas kernel bodies cannot sort): lane k
    proposes the level reached if exactly the lanes at-or-below it
    share the work, ``L_k = (total + sum_{U_i <= U_k} U_i) /
    |{U_i <= U_k}|``.  Every proposal upper-bounds the true level
    (``sum_A (L_k - U_i) = total = sum_i (L* - U_i)^+ >=
    sum_A (L* - U_i)``), and the true active set attains it — so the
    level is exactly ``min_k L_k``, no bracket test needed.  O(S^2)
    broadcasts over the server axis only, so cell-axis tiling and
    sharding cannot change bits."""
    mine = U_eff[..., :, None]                    # proposing lane k
    other = U_eff[..., None, :]                   # every lane i
    le = other <= mine
    cnt = xp.sum(xp.where(le, 1.0, 0.0), axis=-1)
    wsum = xp.sum(xp.where(le, other, 0.0), axis=-1)
    level = (total[..., None] + wsum) / xp.maximum(cnt, 1.0)
    L = xp.min(level, axis=-1, keepdims=True)
    return xp.clip(L - U_eff, 0.0, None)


def _lgamma(c: np.ndarray) -> np.ndarray:
    """lgamma(c + 1) for small-integer capacity arrays via a lookup
    table (np.vectorize(math.lgamma) over a [slots, cells] array costs
    more than the scan itself)."""
    hi = int(np.max(c)) + 1 if c.size else 1
    table = np.array([math.lgamma(k + 1.0) for k in range(hi + 1)])
    return table[np.clip(c.astype(np.int64), 0, hi)]


def _erlang_c(c, lgamma_c, rho, cmax: int):
    """Erlang-C delay probability (P(arrival must queue) in M/M/c),
    vectorized with per-server integer capacity ``c`` <= cmax.
    Precomputed in numpy from the deterministic per-slot offered load —
    it never enters the scan."""
    rho = np.clip(rho, 1e-9, 0.999)
    a = c * rho
    top = np.exp(c * np.log(a) - lgamma_c)
    term = np.ones_like(a)
    ssum = np.zeros_like(a)
    for k in range(cmax):
        ssum = ssum + np.where(k < c, term, 0.0)
        term = term * a / (k + 1.0)
    denom = (1.0 - rho) * ssum + top
    return top / np.maximum(denom, _EPS)


def _episode_age(rho: np.ndarray, t_idx: np.ndarray, dt: float,
                 band: float = _NEAR_CRITICAL) -> np.ndarray:
    """Seconds since each lane's offered load last sat below ``band``
    — the age of the current near-critical episode (>= dt).  Lanes hot
    from t=0 age from the run start."""
    idx = t_idx.reshape((-1,) + (1,) * (rho.ndim - 1)).astype(float)
    last_low = np.maximum.accumulate(np.where(rho < band, idx, -1.0),
                                     axis=0)
    return np.maximum(idx - last_low, 1.0) * dt


def _make_waterfill(xp, consts):
    """The step's water-fill operator: hard level-fill, or the
    temperature-controlled relaxation when the consts carry a soft-mode
    ``tau``.  The choice is structural (dict key presence), so it is
    trace-time static and never branches on a traced value."""
    tau = consts.get("tau")
    if tau is None:
        def wfill(U_eff, total):
            return _waterfill(xp, U_eff, total)
    else:
        from repro.vector.soft import soft_waterfill

        def wfill(U_eff, total):
            return soft_waterfill(xp, U_eff, total, tau)
    return wfill


def _scalar_step(xp, consts):
    c = consts["c"]
    fail_slot = consts["fail_slot"]
    dt = consts["dt"]
    wfill = _make_waterfill(xp, consts)

    def step(carry, xs):
        U, Q, drops = carry
        t, Nc, Wc, Nf, Wf, act, acc, spd = xs
        # failure instant: the resident queue and in-flight work vanish
        is_fail = (t == fail_slot)
        drops = drops + xp.sum(xp.where(is_fail, Q, 0.0), axis=-1)
        U = xp.where(is_fail, 0.0, U)
        Q = xp.where(is_fail, 0.0, Q)
        # request-routed work: water-fill the accepting servers
        n_acc = xp.sum(acc, axis=-1)
        ok = n_acc > 0
        drops = drops + xp.where(ok, 0.0, Nf)
        Wf = xp.where(ok, Wf, 0.0)
        Nf = xp.where(ok, Nf, 0.0)
        U_eff = xp.where(acc > 0, U, _BIG)
        w_free = wfill(U_eff, Wf)
        share = w_free / xp.maximum(
            xp.sum(w_free, axis=-1, keepdims=True), _EPS)
        n_free = Nf[..., None] * share
        W_arr = Wc + w_free
        N_arr = Nc + n_free
        # backlog wait an arrival inherits (transients and overload; the
        # stationary within-slot term is added analytically outside);
        # request-routed arrivals land at the water-fill level: they
        # inherit the LEAST backlog any accepting server offers
        wait_U = U / xp.maximum(c * spd, _EPS)
        wait_free = xp.min(xp.where(acc > 0, wait_U, _BIG), axis=-1)
        # serve
        cw = c * spd * act * dt
        drained = xp.minimum(U + W_arr, cw)
        wpr = (U + W_arr) / xp.maximum(Q + N_arr, _EPS)   # work per request
        n_served = xp.minimum(Q + N_arr, drained / xp.maximum(wpr, _EPS))
        U = U + W_arr - drained
        Q = Q + N_arr - n_served
        return (U, Q, drops), (wait_U, wait_free, n_served, drained, Q)
    return step


def _batched_step(xp, consts):
    B = consts["c"]                      # batch slots
    fail_slot = consts["fail_slot"]; dt = consts["dt"]
    tm = consts["tm"]; tc = consts["tc"]
    new_mean = consts["new_mean"]
    wfill = _make_waterfill(xp, consts)

    def step(carry, xs):
        P, T, L, drops = carry           # prefill s, tokens, requests
        t, Nc, Wpc, Wtc, Nf, Wpf, Wtf, act, acc, spd = xs
        is_fail = (t == fail_slot)
        drops = drops + xp.sum(xp.where(is_fail, L, 0.0), axis=-1)
        P = xp.where(is_fail, 0.0, P)
        T = xp.where(is_fail, 0.0, T)
        L = xp.where(is_fail, 0.0, L)
        # free arrivals: water-fill by queue length (jsq over load())
        n_acc = xp.sum(acc, axis=-1)
        ok = n_acc > 0
        drops = drops + xp.where(ok, 0.0, Nf)
        Nf = xp.where(ok, Nf, 0.0)
        L_eff = xp.where(acc > 0, L, _BIG)
        n_free = wfill(L_eff, Nf)
        share = n_free / xp.maximum(
            xp.sum(n_free, axis=-1, keepdims=True), _EPS)
        Wp_arr = Wpc + Wpf[..., None] * share
        Wt_arr = Wtc + Wtf[..., None] * share
        N_arr = Nc + n_free
        # roofline step law at the slot's occupancy
        b = xp.clip(L, 1.0, B)
        st = xp.maximum(tc * b, tm)
        tok_rate = b / st
        avail = act * spd * dt
        p_served = xp.minimum(P + Wp_arr, avail)
        rem = avail - p_served
        tok_served = xp.minimum(T + Wt_arr, rem * tok_rate)
        dec_used = tok_served / xp.maximum(tok_rate, _EPS)
        busy_used = p_served + dec_used
        n_served = xp.minimum(L + N_arr, tok_served / new_mean)
        P = P + Wp_arr - p_served
        T = T + Wt_arr - tok_served
        L = L + N_arr - n_served
        # admission wait: drain-time share ahead of a new arrival
        D = (P + T * st / xp.maximum(b, 1.0)) / xp.maximum(spd, _EPS)
        wait_adm = D * xp.clip((L - B) / xp.maximum(L, 1.0), 0.0, 1.0)
        b_hat = xp.clip(L + 1.0, 1.0, B)
        st_hat = xp.maximum(tc * b_hat, tm)
        return (P, T, L, drops), (wait_adm, st_hat, N_arr, n_served,
                                  busy_used, L, tok_served)
    return step


# ---------------------------------------------------------------------------
# Scan drivers
# ---------------------------------------------------------------------------
def _scan_numpy(step, carry, xs_seq, n_slots: int):
    outs = None
    for t in range(n_slots):
        xs = tuple(x[t] for x in xs_seq)
        carry, ys = step(carry, xs)
        if outs is None:
            outs = tuple(np.empty((n_slots,) + np.shape(y), dtype=float)
                         for y in ys)
        for buf, y in zip(outs, ys):
            buf[t] = y
    return carry, outs


#: (step_builder, jit, impl, shard, padded shapes) -> compiled runner.
#: consts enter as traced pytree arguments, so one entry serves every
#: grid with the same signature; shape-bucketing keeps the key set
#: small, and the LRU cap bounds the resident compile footprint across
#: long sessions (eviction only costs a recompile, never bits).
_JIT_CACHE: OrderedDict = OrderedDict()
_JIT_CACHE_CAP = 8


def _jax_runner(step_builder, jit: bool, impl: str, shard: int,
                shape_key: tuple, cap: int = _JIT_CACHE_CAP):
    key = (step_builder, jit, impl, shard, shape_key)
    fn = _JIT_CACHE.get(key)
    if fn is not None:
        _JIT_CACHE.move_to_end(key)
        return fn
    import jax
    import jax.numpy as jnp

    family = "batched" if step_builder is _batched_step else "scalar"
    if impl == "ref":
        def make_step(consts):
            return step_builder(jnp, consts)
    else:
        from repro.kernels import ops as kernel_ops

        def make_step(consts):
            def step(carry, xs):
                return kernel_ops.vector_slot_advance(
                    family, consts, carry, xs, impl=impl)
            return step

    def run(consts, carry, xs):
        return jax.lax.scan(make_step(consts), carry, xs)

    if shard:
        run = _shard_cells(run, family, shard)
    if jit:
        # donate the carry: the scan consumes it and the caller only
        # reads the returned one, so XLA may reuse the buffers in
        # place.  CPU jax cannot donate (it would only warn), so the
        # hint is gated on the backend.
        donate = (1,) if jax.default_backend() != "cpu" else ()
        fn = jax.jit(run, donate_argnums=donate)
    else:
        fn = run
    _JIT_CACHE[key] = fn
    while len(_JIT_CACHE) > max(1, cap):
        _JIT_CACHE.popitem(last=False)
    return fn


def _shard_cells(run, family: str, n_dev: int):
    """Lay the cell axis across ``n_dev`` local devices via
    ``shard_map``.  Every reduction in the step math runs over the
    server axis, so the sharded program is bit-identical to the
    single-device one (a test pins this)."""
    import jax
    from jax.sharding import Mesh, PartitionSpec

    cell = PartitionSpec("cells")          # [C, ...] leading cell axis
    seq = PartitionSpec(None, "cells")     # [T, C, ...] scan sequences
    none = PartitionSpec()
    if family == "scalar":
        const_spec = {"c": cell, "fail_slot": cell, "dt": none}
        n_carry, n_xs, n_ys = 3, 8, 5
    else:
        const_spec = {"c": cell, "fail_slot": cell, "dt": none,
                      "tm": cell, "tc": cell, "new_mean": cell}
        n_carry, n_xs, n_ys = 4, 10, 7
    in_specs = (const_spec, (cell,) * n_carry,
                (none,) + (seq,) * (n_xs - 1))
    out_specs = ((cell,) * n_carry, (seq,) * n_ys)
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("cells",))
    return jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


#: cell-padding fills that keep padded (dead) cells NaN-free: no
#: failure slot, unit roofline times; everything else zero
_CELL_PAD_FILL = {"fail_slot": -1, "tm": 1.0, "tc": 1.0, "new_mean": 1.0}


def _pad_cell_axis(a: np.ndarray, pad: int, axis: int, fill=0.0):
    width = [(0, 0)] * a.ndim
    width[axis] = (0, pad)
    return np.pad(a, width, constant_values=fill)


def _scan_jax_launch(step_builder, consts, carry, xs_seq,
                     cfg: VectorConfig):
    """Dispatch the chunk's scan and return immediately (jax dispatch is
    async: the device computes while the host moves on).  Pair with
    ``_scan_jax_finish``, which blocks on the transfer."""
    import jax.numpy as jnp

    impl = cfg.resolve_impl()
    n_dev = cfg.resolve_devices()
    # soft consts carry the extra "tau" leaf the shard specs don't
    # declare; soft grids are small, so they skip the shard layer
    use_shard = (n_dev > 1 or cfg.devices >= 1) and not cfg.soft
    if impl == "pallas":
        from repro.kernels.vector_step import CELL_TILE as tile
    else:
        tile = 1
    # pad the cell axis so each device shard is kernel-tile aligned;
    # padded cells are inert and sliced away after the scan
    C = carry[-1].shape[0]
    unit = tile * (n_dev if use_shard else 1)
    pad = (-C) % unit
    if pad:
        consts = {k: (_pad_cell_axis(v, pad, 0,
                                     _CELL_PAD_FILL.get(k, 0.0))
                      if isinstance(v, np.ndarray) else v)
                  for k, v in consts.items()}
        carry = tuple(_pad_cell_axis(c, pad, 0) for c in carry)
        xs_seq = (xs_seq[0],) + tuple(_pad_cell_axis(x, pad, 1)
                                      for x in xs_seq[1:])

    consts_j = {k: (jnp.asarray(v, jnp.float32)
                    if isinstance(v, np.ndarray) else
                    jnp.float32(v))
                for k, v in consts.items()}
    # fail_slot compares against integer slot indices
    consts_j["fail_slot"] = jnp.asarray(consts["fail_slot"], jnp.int32)
    carry_j = tuple(jnp.asarray(c, jnp.float32) for c in carry)
    xs_j = tuple(jnp.asarray(x, jnp.int32 if i == 0 else jnp.float32)
                 for i, x in enumerate(xs_seq))
    shape_key = (xs_j[0].shape[0],) + carry_j[0].shape
    runner = _jax_runner(step_builder, cfg.jit, impl,
                         n_dev if use_shard else 0, shape_key,
                         cap=cfg.jit_cache_size)
    return runner(consts_j, carry_j, xs_j), C


def _scan_jax_finish(raw):
    """Block on a launched chunk and widen host-side to f64."""
    import jax
    (out_carry, outs), C = raw
    # ONE device->host sync for the whole chunk: the previous per-array
    # np.asarray form issued ~10 blocking transfers per chunk, which is
    # what left the warm jax path behind the NumPy fallback on small
    # grids.  The f64 widening stays host-side so rows keep their bits.
    out_carry, outs = jax.device_get((out_carry, outs))
    return (tuple(np.asarray(c, np.float64)[:C] for c in out_carry),
            tuple(np.asarray(o, np.float64)[:, :C] for o in outs))


def _scan_jax(step_builder, consts, carry, xs_seq, cfg: VectorConfig):
    return _scan_jax_finish(
        _scan_jax_launch(step_builder, consts, carry, xs_seq, cfg))


# ---------------------------------------------------------------------------
# Grid execution
# ---------------------------------------------------------------------------
def _cell_rng(seed: int, stream: int) -> np.random.Generator:
    """The cell's private RNG: seeded by the sweep-derived (seed,
    stream), domain-separated from every scalar-path stream."""
    return np.random.default_rng((0x7EC7, int(seed), int(stream)))


def _draw_cell(prog: VectorProgram, rng: np.random.Generator) -> dict:
    """Pre-scan draws for one cell, in a FIXED order (the same numbers
    whether the cell runs alone or inside any grid)."""
    dt = prog.dt
    Nc = rng.poisson(prog.rate_conn * dt).astype(float)
    Nf = rng.poisson(prog.rate_free * dt).astype(float)
    if not prog.batched:
        # the scalar backlog is a pure fluid: expected work per slot.
        # Stochastic queueing below saturation is carried entirely by
        # the analytic stationary term (Erlang-C x exponential) — work
        # or count noise here would double-count it — so the fluid
        # captures exactly what the stationary law cannot: transient
        # buildup and overload growth.  Poisson counts still drive the
        # sampling weights and the completion counts.
        m = prog.work_mean                            # [S]
        return {"Nc": Nc, "Wc": prog.rate_conn * dt * m, "Nf": Nf,
                "Wf": prog.rate_free * dt * float(m.mean())}
    zc = rng.standard_normal(Nc.shape)
    zf = rng.standard_normal(Nf.shape)
    zc2 = rng.standard_normal(Nc.shape)
    zf2 = rng.standard_normal(Nf.shape)
    pm, pv = prog.prefill_mean, prog.prefill_var
    nm, nv = prog.new_mean, prog.new_var
    Wpc = np.maximum(Nc * pm + np.sqrt(Nc * pv) * zc, 0.05 * Nc * pm)
    Wtc = np.maximum(Nc * nm + np.sqrt(Nc * nv) * zc2, 0.05 * Nc * nm)
    Wpf = np.maximum(Nf * pm + np.sqrt(Nf * pv) * zf, 0.05 * Nf * pm)
    Wtf = np.maximum(Nf * nm + np.sqrt(Nf * nv) * zf2, 0.05 * Nf * nm)
    return {"Nc": Nc, "Wpc": Wpc, "Wtc": Wtc, "Nf": Nf,
            "Wpf": Wpf, "Wtf": Wtf}


def _pad(a: np.ndarray, T: int, S: int) -> np.ndarray:
    """Zero-pad a per-cell [T_i(, S_i)] array to the group shape."""
    if a.ndim == 1:
        out = np.zeros(T)
        out[:a.shape[0]] = a
        return out
    out = np.zeros((T, S))
    out[:a.shape[0], :a.shape[1]] = a
    return out


#: geometric bucket resolution: sizes per octave (<= 1/quantum relative
#: padding waste; tiny dims stay exact)
_BUCKET_QUANTUM = 8


def _bucket_dim(n: int, quantum: int = _BUCKET_QUANTUM) -> int:
    """Round ``n`` up to the next geometric bucket so heterogeneous
    grids collapse onto a few stable pad shapes (one jit trace per
    bucket, not per exact shape)."""
    n = int(n)
    if n <= quantum:
        return n
    step = max(1, (1 << ((n - 1).bit_length() - 1)) // quantum)
    return -(-n // step) * step


def _plan_groups(programs: Sequence[VectorProgram],
                 cfg: VectorConfig) -> list:
    """Group cell indices by (family, padded (T, S) shape).

    With ``cfg.bucket`` each cell's own (n_slots, n_servers) rounds up
    to its geometric bucket; without, each family pads to its max (the
    pre-bucketing behavior).  Either way padding is masking, never
    truncation: a cell's draws use its true shape and extraction
    slices it back out, so rows are bit-identical across groupings (a
    test pins bucketed == unbucketed)."""
    groups: dict = {}
    for i, p in enumerate(programs):
        shape = (_bucket_dim(p.n_slots), _bucket_dim(p.n_servers)) \
            if cfg.bucket else None
        groups.setdefault((p.batched, shape), []).append(i)
    out = []
    for (batched, shape), idxs in sorted(
            groups.items(), key=lambda kv: (kv[0][0], kv[0][1] or ())):
        if shape is None:
            shape = (max(programs[i].n_slots for i in idxs),
                     max(programs[i].n_servers for i in idxs))
        out.append((batched, shape, idxs))
    return out


def run_cells(programs: Sequence[VectorProgram],
              seeds: Sequence[tuple],
              config: Optional[VectorConfig] = None,
              cache=None) -> list[VectorResult]:
    """Execute one cell per (program, (seed, stream)) pair — the whole
    grid as one batched array program per (family, shape bucket),
    chunked to bound scan memory.

    With a ``ResultCache``, cached cells are filtered out BEFORE
    ``_plan_groups``: only cold cells enter the batched scan, so a
    re-run of a 117-cell grid with 3 edited points launches 3 cells.
    Each cell's draws come from its own seeded Generator, so which
    cells happen to be cold can never change any cell's bits.

    Chunks are double-buffered when ``cfg.pipeline``: the device scan
    of chunk k+1 is dispatched (async) before chunk k's host finishing
    (device fetch, sampling, quantiles, cache writes) runs, overlapping
    the two.  ``pipeline=False`` restores strictly serial
    launch-then-finish; both orders produce identical rows because a
    cell's numbers depend only on its own program, seed, and config.
    """
    cfg = config or VectorConfig()
    backend = cfg.resolve_backend()
    if cfg.soft and backend != "jax":
        raise RuntimeError("VectorConfig.soft=True needs the jax "
                           "backend: the soft quantile head runs "
                           "through jnp (use backend='jax' or 'auto')")
    results: list[Optional[VectorResult]] = [None] * len(programs)
    keys: list[Optional[str]] = [None] * len(programs)
    if cache is not None:
        cold = []
        for i, (p, s) in enumerate(zip(programs, seeds)):
            keys[i] = cache.cell_key(p, s, cfg)
            hit = cache.get_cell(keys[i]) if keys[i] is not None else None
            if hit is not None:
                results[i] = hit
            else:
                cold.append(i)
    else:
        cold = list(range(len(programs)))
    if not cold:
        return results  # type: ignore[return-value]

    cold_progs = [programs[i] for i in cold]
    chunks = []                     # (batched, shape, indices into cold)
    for batched, shape, idxs in _plan_groups(cold_progs, cfg):
        # chunk cells so T*C*S stays within the memory budget
        per_cell = max(shape[0] * shape[1], 1)
        chunk = max(1, cfg.max_slot_elems // per_cell)
        for lo in range(0, len(idxs), chunk):
            chunks.append((batched, shape, idxs[lo:lo + chunk]))

    def finish(state, part):
        for j, res in zip(part, _finish_family(state)):
            i = cold[j]
            results[i] = res
            if cache is not None and keys[i] is not None:
                cache.put_cell(keys[i], res)

    pending = None
    for batched, shape, part in chunks:
        state = _launch_family([cold_progs[j] for j in part],
                               [seeds[cold[j]] for j in part],
                               batched, backend, cfg, shape)
        if not cfg.pipeline:
            finish(state, part)
            continue
        if pending is not None:
            finish(*pending)
        pending = (state, part)
    if pending is not None:
        finish(*pending)
    return results  # type: ignore[return-value]


def _launch_family(progs: list, seeds: list, batched: bool, backend: str,
                   cfg: VectorConfig, shape: tuple) -> dict:
    """Draw, assemble, and DISPATCH one (family, shape) chunk.

    On the jax backend the scan is launched asynchronously and this
    returns before it completes; the host-side analytic aux (Erlang-C,
    pooled laws, stretch) is computed after dispatch so it overlaps the
    device scan.  ``_finish_family`` consumes the returned state."""
    C = len(progs)
    T, S = shape
    dt = progs[0].dt
    rngs = [_cell_rng(s, st) for s, st in seeds]
    draws = [_draw_cell(p, r) for p, r in zip(progs, rngs)]

    def stack(key: str) -> np.ndarray:
        return np.stack([_pad(d[key], T, S) for d in draws], axis=1)

    def stackp(attr: str) -> np.ndarray:
        return np.stack([_pad(getattr(p, attr), T, S) for p in progs],
                        axis=1)

    act = stackp("active")
    acc = stackp("accepting")
    spd = stackp("speed")
    c = np.stack([np.pad(p.workers, (0, S - p.n_servers)) for p in progs])
    fail = np.stack([np.pad(p.fail_slot, (0, S - p.n_servers),
                            constant_values=-1) for p in progs])
    t_idx = np.arange(T, dtype=np.int64)

    if not batched:
        consts = {"c": c, "fail_slot": fail, "dt": dt}
        xs = (t_idx, stack("Nc"), stack("Wc"), stack("Nf"), stack("Wf"),
              act, acc, spd)
        carry = tuple(np.zeros((C, S)) for _ in range(2)) + (np.zeros(C),)
        builder = _scalar_step
    else:
        tm = np.array([p.service.t_memory for p in progs])[:, None]
        tc = np.array([p.service.t_compute_per_seq for p in progs])[:, None]
        nm = np.array([p.new_mean for p in progs])[:, None]
        consts = {"c": c, "fail_slot": fail, "dt": dt, "tm": tm, "tc": tc,
                  "new_mean": nm}
        xs = (t_idx, stack("Nc"), stack("Wpc"), stack("Wtc"), stack("Nf"),
              stack("Wpf"), stack("Wtf"), act, acc, spd)
        carry = tuple(np.zeros((C, S)) for _ in range(3)) + (np.zeros(C),)
        builder = _batched_step
    if cfg.soft:
        consts["tau"] = float(cfg.tau)

    state = {"progs": progs, "rngs": rngs, "draws": draws,
             "batched": batched, "backend": backend, "cfg": cfg, "C": C}
    if backend == "jax":
        state["raw"] = _scan_jax_launch(builder, consts, carry, xs, cfg)
    else:
        step = builder(np, dict(consts))
        state["host"] = _scan_numpy(step, carry, xs, T)

    # ---- host-side analytic aux (overlaps the dispatched scan) ---------
    aux: dict = {}
    if not batched:
        m_w = np.stack([np.pad(p.work_mean, (0, S - p.n_servers),
                               constant_values=1.0) for p in progs])
        v_w = np.stack([np.pad(p.work_var, (0, S - p.n_servers))
                        for p in progs])
        # ---- analytic stationary wait (outside the scan) ----------------
        # deterministic per-slot offered load, with request-routed rate
        # spread capacity-proportionally over the accepting servers
        rate_c = np.stack([_pad(p.rate_conn, T, S) for p in progs], axis=1)
        rate_f = np.stack([_pad(p.rate_free, T, S) for p in progs], axis=1)
        cap_share = acc * (c * spd)
        share = cap_share / np.maximum(
            cap_share.sum(axis=-1, keepdims=True), _EPS)
        lam_w = (rate_c + rate_f[..., None] * share) * m_w[None]
        rho_det = np.where(act > 0,
                           lam_w / np.maximum(c * spd, _EPS), 0.0)
        lgamma_c = _lgamma(c)
        cmax = int(c.max()) if c.size else 1
        if cfg.soft:
            from repro.vector import soft as _soft
            aux["pC"] = _soft.soft_erlang_c(np, c[None].astype(float),
                                            rho_det, cmax, cfg.tau)
            headroom = 1.0 - _soft.smooth_rho(np, rho_det, cfg.tau)
        else:
            aux["pC"] = _erlang_c(c[None], lgamma_c[None], rho_det, cmax)
            headroom = 1.0 - np.clip(rho_det, 0.0, 0.999)
        # conditional wait given queueing: residual service work over
        # the free capacity (exact Pollaczek-Khinchine mean for c=1),
        # bounded near/above criticality by the diffusion growth law
        # E[U(t)] ~ sigma * sqrt(2 t / pi) — a finite run at rho -> 1
        # only builds the queue the random walk had time to build
        e2 = v_w + m_w * m_w
        resid = e2 / np.maximum(2.0 * m_w, _EPS)
        w_stat = resid[None] / np.maximum(c[None] * spd * headroom, _EPS)
        lam_srv = rho_det * c[None] * spd / np.maximum(m_w[None], _EPS)
        # the diffusion clock runs from the start of the CURRENT
        # near-critical episode, not the run: cyclic loads (diurnal)
        # cross criticality many times, and each crossing only has its
        # own age of random walk behind it
        t_since = _episode_age(rho_det, t_idx, dt)
        growth = np.sqrt(2.0 / math.pi * lam_srv * e2[None] * t_since) \
            / np.maximum(c[None] * spd, _EPS)
        # the diffusion bound only exists near/above criticality —
        # below the band the stationary law stands alone
        aux["w_cond"] = np.where(rho_det < _NEAR_CRITICAL, w_stat,
                                 np.minimum(w_stat, growth))
        # ---- pooled law for request-routed arrivals ---------------------
        # jsq/p2c pool the fleet: an arrival queues only when EVERY
        # accepting server is busy — Erlang-C over the pooled capacity,
        # not independent per-server queues
        m_bar = np.array([float(p.work_mean.mean()) for p in progs])
        e2_bar = np.array([float((p.work_var + p.work_mean ** 2).mean())
                           for p in progs])
        resid_bar = e2_bar / np.maximum(2.0 * m_bar, _EPS)
        cap_pool = (acc * c[None] * spd).sum(axis=-1)          # [T, C]
        work_rate = (rate_c * m_w[None]).sum(axis=-1) \
            + rate_f * m_bar[None]
        rho_pool = np.where(cap_pool > 0,
                            work_rate / np.maximum(cap_pool, _EPS), 0.0)
        c_pool = np.minimum(np.maximum((acc * c[None]).sum(axis=-1), 1.0),
                            64.0)
        if cfg.soft:
            aux["pC_free"] = _soft.soft_erlang_c(np, c_pool, rho_pool,
                                                 int(c_pool.max()),
                                                 cfg.tau)
            headroom_f = 1.0 - _soft.smooth_rho(np, rho_pool, cfg.tau)
        else:
            aux["pC_free"] = _erlang_c(c_pool, _lgamma(c_pool), rho_pool,
                                       int(c_pool.max()))
            headroom_f = 1.0 - np.clip(rho_pool, 0.0, 0.999)
        w_stat_f = resid_bar[None] / np.maximum(cap_pool * headroom_f,
                                                _EPS)
        lam_pool = rho_pool * cap_pool / np.maximum(m_bar[None], _EPS)
        t_since_f = _episode_age(rho_pool, t_idx, dt)
        growth_f = np.sqrt(2.0 / math.pi * lam_pool * e2_bar[None]
                           * t_since_f) / np.maximum(cap_pool, _EPS)
        aux["w_cond_free"] = np.where(rho_pool < _NEAR_CRITICAL, w_stat_f,
                                      np.minimum(w_stat_f, growth_f))
        aux["free_ok"] = (acc.sum(axis=-1) > 0).astype(float)
        aux["spd_free"] = np.where(
            acc.sum(axis=-1) > 0,
            (acc * c[None] * spd).sum(axis=-1)
            / np.maximum((acc * c[None]).sum(axis=-1), _EPS), 1.0)
    else:
        # a resident's wall-clock pace per own token stretches by the
        # prefill ops interleaved with decode (the engine serializes one
        # op at a time) — deterministic expected prefill time-share
        rate_c = np.stack([_pad(p.rate_conn, T, S) for p in progs], axis=1)
        rate_f = np.stack([_pad(p.rate_free, T, S) for p in progs], axis=1)
        share_even = acc / np.maximum(acc.sum(axis=-1, keepdims=True),
                                      _EPS)
        pf_mean = np.array([p.prefill_mean for p in progs])
        pf_share = np.clip((rate_c + rate_f[..., None] * share_even)
                           * pf_mean[None, :, None]
                           / np.maximum(spd, _EPS), 0.0, 0.8)
        aux["stretch"] = 1.0 / (1.0 - pf_share)
    state["aux"] = aux
    return state


def _finish_family(state: dict) -> list[VectorResult]:
    """Fetch a launched chunk's scan outputs and extract every cell's
    results (sampling, censoring, fused-grid percentiles)."""
    progs, rngs, draws = state["progs"], state["rngs"], state["draws"]
    batched, backend, cfg = (state["batched"], state["backend"],
                             state["cfg"])
    C, aux = state["C"], state["aux"]
    if backend == "jax":
        carry, outs = _scan_jax_finish(state["raw"])
    else:
        carry, outs = state["host"]

    cells = [_sample_cell(progs[i], rngs[i], i, batched, carry, outs, aux,
                          draws[i], cfg)
             for i in range(C)]
    if cfg.soft:
        quants = _grid_quantiles([cell["lat_all"] for cell in cells], cfg,
                                 backend,
                                 weights=[cell["w_all"] for cell in cells])
    else:
        quants = _grid_quantiles([cell["lat"] for cell in cells], cfg,
                                 backend)
    return [_finish_cell(progs[i], batched, cells[i], quants[i])
            for i in range(C)]


def _run_family(progs: list, seeds: list, batched: bool, backend: str,
                cfg: VectorConfig, shape: tuple) -> list[VectorResult]:
    return _finish_family(_launch_family(progs, seeds, batched, backend,
                                         cfg, shape))


# ---------------------------------------------------------------------------
# Per-cell extraction: sampling, censoring, fused-grid percentiles
# ---------------------------------------------------------------------------
def _sample_cell(prog: VectorProgram, rng: np.random.Generator, i: int,
                 batched: bool, carry, outs, aux: dict, draws: dict,
                 cfg: VectorConfig) -> dict:
    """Draw this cell's request sample from the slot series (uniform over
    realized arrivals, event-engine censoring) — everything per-cell
    EXCEPT the percentiles, which `_grid_quantiles` computes for the
    whole chunk in one fused launch."""
    T, S = prog.n_slots, prog.n_servers
    dt = prog.dt
    if not batched:
        wait_U = outs[0][:T, i, :S]
        wait_free = outs[1][:T, i]
        n_served = outs[2][:T, i, :S]
        drained = outs[3][:T, i, :S]
        Qs = outs[4][:T, i, :S]
        pC = aux["pC"][:T, i, :S]
        w_cond = aux["w_cond"][:T, i, :S]
        pC_f = aux["pC_free"][:T, i]
        w_cond_f = aux["w_cond_free"][:T, i]
        free_ok = aux["free_ok"][:T, i]
        spd_f = aux["spd_free"][:T, i]
    else:
        wait_adm, st_hat, N_arr, n_served, drained, Qs, tok_served = \
            (o[:T, i, :S] for o in outs)
    drops = float(carry[-1][i])

    centers = (np.arange(T) + 0.5) * dt
    speed = prog.speed

    # ---- request sampling (uniform over realized arrivals) -----------------
    # scalar cells keep connection-routed and request-routed arrivals in
    # separate weight blocks: conn samples see their server's stationary
    # law, free samples the POOLED fleet law (jsq pools the servers)
    if not batched:
        w = np.concatenate([draws["Nc"].ravel(), draws["Nf"] * free_ok])
    else:
        w = N_arr.ravel()
    total = w.sum()
    K = int(min(cfg.samples, math.ceil(total))) if total > 0 else 0
    if K > 0:
        cum = np.cumsum(w)
        u = rng.random(K) * cum[-1]
        flat = np.searchsorted(cum, u, side="right")
        flat = np.minimum(flat, w.size - 1)
        if not batched:
            is_free = flat >= T * S
            ts = np.where(is_free, flat - T * S, flat // S)
            ss = np.where(is_free, 0, flat % S)
            demand = prog.profile.sample_batch(rng, K)
            if prog.noise_sigma.any():
                sig = np.where(is_free, float(prog.noise_sigma.mean()),
                               prog.noise_sigma[ss])
                demand = demand * np.exp(sig * rng.standard_normal(K))
            spd_i = np.where(is_free, spd_f[ts], speed[ts, ss])
            svc = demand / np.maximum(spd_i, _EPS)
            # wait = inherited backlog (always, PASTA) + the stationary
            # within-slot queue: Bernoulli(Erlang-C) x Exp(conditional).
            # Soft mode reuses the SAME uniform/exponential draws and
            # only smooths the indicator (reparameterization), so the
            # two modes sample the same underlying requests.
            pC_i = np.where(is_free, pC_f[ts], pC[ts, ss])
            u_q = rng.random(K)
            e_q = rng.standard_exponential(K)
            if cfg.soft:
                from repro.vector.soft import stable_sigmoid
                queued = stable_sigmoid(np, (pC_i - u_q) / cfg.tau)
            else:
                queued = u_q < pC_i
            station = queued * e_q \
                * np.where(is_free, w_cond_f[ts], w_cond[ts, ss])
            lat = np.where(is_free, wait_free[ts], wait_U[ts, ss]) \
                + station + svc
            # request-routed arrivals never target a dead server; conn
            # arrivals caught by their server's failure are lost
            fail_t = np.where(is_free | (prog.fail_slot[ss] < 0), np.inf,
                              prog.fail_slot[ss] * dt)
        else:
            ts, ss = np.divmod(flat, S)
            spd_i = speed[ts, ss]
            ptoks, ntoks = prog.lengths.sample_batch(rng, K)
            pf = prog.service.prefill_time_array(ptoks)
            stretch = aux["stretch"][:T, i, :S][ts, ss]
            lat = wait_adm[ts, ss] + \
                (pf + ntoks * st_hat[ts, ss] * stretch) \
                / np.maximum(spd_i, _EPS)
            fail_t = np.where(prog.fail_slot[ss] >= 0,
                              prog.fail_slot[ss] * dt, np.inf)
        completion = centers[ts] + lat
        # censor like the event engine's recorder: completions past the
        # horizon are never recorded, and a request caught on a failing
        # server (arrived in its fail slot, or completing after the fail
        # instant) is lost.  Soft mode additionally keeps the FULL
        # sample with smooth keep-weights for the soft quantile head
        # (the stored samples stay hard-censored for telemetry).
        if cfg.soft:
            from repro.vector.soft import censor_weight
            lat_all = lat
            w_all = censor_weight(np, centers[ts], completion,
                                  prog.duration, fail_t,
                                  80.0 * dt * cfg.tau)
        keep = (completion <= prog.duration) & (centers[ts] < fail_t) \
            & (completion <= fail_t)
        lat = lat[keep]
        completion = completion[keep]
    else:
        lat = np.empty(0)
        completion = np.empty(0)
        lat_all = np.empty(0)
        w_all = np.empty(0)

    out = {"lat": lat, "completion": completion, "n_served": n_served,
           "drained": drained, "Qs": Qs, "drops": drops,
           "tok_served": tok_served if batched else None}
    if cfg.soft:
        out["lat_all"] = lat_all
        out["w_all"] = w_all
    return out


def _grid_quantiles(lats: list, cfg: VectorConfig, backend: str,
                    weights: Optional[list] = None):
    """p50/p95/p99 for every cell of a chunk -> [C, 3] (NaN rows when a
    cell has no samples).

    numpy backend: hoisted-plan partition per row, f64.  jax backend:
    ONE fused launch over a [C, K] +inf-padded f32 matrix — the jnp
    sort oracle (impl="ref") and the Pallas radix-select kernel select
    the same order statistics bit-for-bit, so the impl knob never
    changes a row.  Means are NOT computed here: the row mean stays
    host-side f64 so it cannot depend on the pad width K.

    ``weights`` (soft mode) switches to the differentiable head: the
    full per-cell sample with smooth censor keep-weights, one
    ``soft_quantiles`` launch for the chunk (zero-weight padding).
    """
    C = len(lats)
    counts = np.array([lat.size for lat in lats], np.int64)
    K = int(counts.max()) if C else 0
    if weights is not None:
        from repro.vector.soft import soft_quantiles
        if K == 0:
            return np.full((C, 3), float("nan"))
        import jax.numpy as jnp
        mat = np.full((C, K), np.inf, np.float32)
        wmat = np.zeros((C, K), np.float32)
        for i, (lat, w) in enumerate(zip(lats, weights)):
            mat[i, :lat.size] = lat
            wmat[i, :w.size] = w
        out = soft_quantiles(jnp.asarray(mat), jnp.asarray(wmat),
                             band_frac=cfg.band_frac)
        return np.asarray(out, np.float64)
    if backend != "jax":
        from repro.core.stats import quantiles_partition_batched
        mat = np.zeros((C, max(K, 1)))
        for i, lat in enumerate(lats):
            mat[i, :lat.size] = lat
        return quantiles_partition_batched(mat, counts, (50.0, 95.0, 99.0))
    if K == 0:
        return np.full((C, 3), float("nan"))
    import jax.numpy as jnp

    from repro.kernels import ops as kernel_ops
    mat = np.full((C, K), np.inf, np.float32)
    for i, lat in enumerate(lats):
        mat[i, :lat.size] = lat
    # eager launch (no jit): the kernel pads K internally to the lane
    # tile, so per-(C, K) retraces would defeat the bucketing anyway
    out = kernel_ops.vector_quantiles(jnp.asarray(mat),
                                      jnp.asarray(counts, jnp.int32),
                                      impl=cfg.resolve_impl())
    return np.asarray(out, np.float64)


def _finish_cell(prog: VectorProgram, batched: bool, cell: dict,
                 q3) -> VectorResult:
    T, S = prog.n_slots, prog.n_servers
    dt = prog.dt
    speed = prog.speed
    lat = cell["lat"]
    completion = cell["completion"]
    n_served = cell["n_served"]
    drained = cell["drained"]
    Qs = cell["Qs"]
    tok_served = cell["tok_served"]
    drops = cell["drops"]

    n = int(round(float(n_served.sum())))
    if lat.size:
        p50, p95, p99 = (float(v) for v in q3)
        mean = float(lat.mean())
    else:
        p50 = p95 = p99 = mean = float("nan")

    # ---- interval series ---------------------------------------------------
    spi = max(1, int(round(prog.interval / dt)))     # slots per interval
    n_ivls = int(math.ceil(T / spi))
    pad_to = n_ivls * spi
    def ivl_sum(a):                                   # [T, S] -> [n_ivls, S]
        buf = np.zeros((pad_to, a.shape[1]))
        buf[:T] = a
        return buf.reshape(n_ivls, spi, a.shape[1]).sum(axis=1)

    n_ivl = ivl_sum(n_served).sum(axis=1)
    busy_seconds = (drained / np.maximum(speed, _EPS)) if not batched \
        else drained
    util_cap = prog.workers[None, :] * prog.interval if not batched \
        else np.full((1, S), prog.interval)
    util_ivl = np.minimum(ivl_sum(busy_seconds) / np.maximum(util_cap,
                                                             _EPS), 1.0)
    # queue depth / occupancy at interval boundaries (last slot of each)
    ends = np.minimum(np.arange(1, n_ivls + 1) * spi - 1, T - 1)
    qdepth_ivl = Qs[ends]
    if batched:
        occ_ivl = np.minimum(Qs[ends] / np.maximum(prog.workers[None, :],
                                                   1.0), 1.0)
        tokens_ivl = ivl_sum(tok_served) / prog.interval
    else:
        occ_ivl = util_ivl
        tokens_ivl = None
    sample_ivl = np.minimum(completion / prog.interval,
                            n_ivls - 1 + 1e-9).astype(np.int64) \
        if completion.size else np.empty(0, np.int64)

    # admission shedding (fluid expectation): per-interval shed counts
    # ride the same reshape-sum as the served series, and sheds count
    # into ``dropped`` so they are never silently missing from totals
    if prog.shed_rate is not None:
        shed_slot = np.zeros(pad_to)
        shed_slot[:T] = prog.shed_rate * dt
        shed_ivl = shed_slot.reshape(n_ivls, spi).sum(axis=1)
        shed_total = float(shed_ivl.sum())
    else:
        shed_ivl = None
        shed_total = 0.0

    return VectorResult(
        n=n, mean=mean, p50=float(p50), p95=float(p95), p99=float(p99),
        dropped=int(round(drops + shed_total)) + prog.refused_clients,
        interval=prog.interval, slo=prog.slo, server_ids=prog.server_ids,
        samples=lat, sample_ivl=sample_ivl, n_ivl=n_ivl,
        util_ivl=util_ivl, occ_ivl=occ_ivl, qdepth_ivl=qdepth_ivl,
        tokens_ivl=tokens_ivl, shed_ivl=shed_ivl)


# ---------------------------------------------------------------------------
# Runtime adapter (single cell — scenario CLI / run_task parity)
# ---------------------------------------------------------------------------
class VectorRuntime:
    """``Runtime``-shaped adapter over one (experiment, rep) cell.

    Produces exactly the numbers the grid path produces for the same
    (seed, stream): per-cell RNG derivation makes a cell's results
    independent of the grid it runs in.
    """

    recorder = None                     # no raw-sample recorder: sampled

    def __init__(self, experiment, rep: int = 0,
                 config: Optional[VectorConfig] = None, cache=None):
        from repro.vector.telemetry import VectorTelemetry
        self.experiment = experiment
        self.config = config or VectorConfig()
        self.cache = cache
        self.program = compile_experiment(experiment, dt=self.config.dt)
        self.seed = (experiment.seed, rep)
        self.unsupported = self.program.unsupported
        self.telemetry: Optional[VectorTelemetry] = None
        self.result: Optional[VectorResult] = None

    @property
    def dropped(self) -> int:
        return self.result.dropped if self.result is not None else 0

    @property
    def shed(self) -> int:
        r = self.result
        if r is None or r.shed_ivl is None:
            return 0
        return int(round(float(r.shed_ivl.sum())))

    @property
    def control_log(self) -> list:
        return self.program.control_actions

    def run(self):
        from repro.vector.telemetry import VectorTelemetry
        self.result = run_cells([self.program], [self.seed],
                                self.config, cache=self.cache)[0]
        self.telemetry = VectorTelemetry(self.result)
        return self.telemetry
