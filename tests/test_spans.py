"""Host spans (``repro.core.spans``): kept only while a profiler session is
active or after ``enable()``, linked to their parents, bounded, and
emitted by the serving engine and the wall-clock runtime."""
import threading
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import spans
from repro.core.client import ClientConfig, ConstantQPS
from repro.core.profiles import BatchedService
from repro.core.runtime import EngineRuntime, VirtualClock
from repro.models import registry as R
from repro.serving.engine import BatchedStubEngine, InferenceEngine

SMOKE = "phi3-mini-3.8b-smoke"


@pytest.fixture(autouse=True)
def fresh():
    spans.enable(False)
    spans.clear()
    yield
    spans.enable(False)
    spans.clear()


def _names(records):
    return [r.name for r in records]


def test_profiler_class_is_the_annotation():
    # spans appear on the trace's host plane as jax.profiler annotations do
    assert issubclass(jax.profiler.TraceAnnotation, spans.TraceMe)


def test_nothing_recorded_without_a_session():
    with spans.span("engine.step", kind="decode") as s:
        spans.mark("engine.queue", 1.0, 2.0, req=3)
        jnp.ones(4).block_until_ready()
    assert s.name == "engine.step"
    assert spans.snapshot() == []


def test_recorded_under_a_profiler_session(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with spans.span("runtime.step"):
            with spans.span("engine.step", kind="decode"):
                pass
    with spans.span("runtime.step"):
        pass
    assert _names(spans.snapshot()) == ["engine.step", "runtime.step"]


def test_recorded_after_enable():
    spans.enable()
    t = spans.now()
    with spans.span("engine.admit", req=7, tokens=5, bucket=32):
        pass
    spans.mark("engine.queue", t - 1.0, t, req=7)
    (admit, queue) = spans.snapshot()
    assert admit.attrs == {"req": 7, "tokens": 5, "bucket": 32}
    assert t <= admit.start <= admit.end
    assert (queue.start, queue.end, queue.attrs) == (t - 1.0, t, {"req": 7})
    spans.enable(False)
    with spans.span("engine.admit"):
        pass
    assert len(spans.snapshot()) == 2


def test_parents_and_request_ids():
    spans.enable()
    with spans.span("engine.step") as step:
        with spans.span("engine.admit", req=1) as admit:
            with spans.span("engine.prefill.sync") as sync:
                pass
            spans.mark("engine.queue", 0.0, 1.0, req=1)
        with spans.span("engine.decode") as decode:
            pass
    by = {r.name: r for r in spans.snapshot()}
    assert by["engine.step"].parent is None
    assert by["engine.admit"].parent == step._id
    assert by["engine.prefill.sync"].parent == admit._id
    assert by["engine.queue"].parent == admit._id
    assert by["engine.decode"].parent == step._id
    assert len({step._id, admit._id, sync._id, decode._id}) == 4
    assert by["engine.admit"].attrs["req"] == by["engine.queue"].attrs["req"]
    # a span that raises is still recorded, and the stack unwinds
    with pytest.raises(KeyError):
        with spans.span("runtime.sleep"):
            raise KeyError
    with spans.span("runtime.tick"):
        pass
    assert spans.snapshot()[-1].parent is None


def test_ring_is_bounded(monkeypatch):
    assert spans._ring.maxlen == spans.CAPACITY == 1 << 20
    monkeypatch.setattr(spans, "_ring", deque(maxlen=4))
    spans.enable()
    for i in range(10):
        spans.mark("engine.queue", float(i), float(i) + 1, req=i)
    assert [r.attrs["req"] for r in spans.snapshot()] == [6, 7, 8, 9]


def test_each_thread_nests_its_own_spans():
    spans.enable()
    inner = {}

    def work():
        with spans.span("runtime.step") as s:
            spans.mark("engine.queue", 0.0, 1.0, req=2)
        inner["id"] = s._id

    with spans.span("runtime.tick") as outer:
        t = threading.Thread(target=work)
        t.start()
        t.join()
        with spans.span("runtime.sleep"):
            pass
    by = {r.name: r for r in spans.snapshot()}
    # a span opened on another thread is not a child of this one's
    assert by["runtime.step"].parent is None
    assert by["engine.queue"].parent == inner["id"]
    assert by["runtime.sleep"].parent == outer._id


class _Seen:
    """Engine wrapper that notes the clock at each submit."""

    def __init__(self, engine, clock):
        self.engine, self.clock, self.at = engine, clock, []

    def __getattr__(self, name):
        return getattr(self.engine, name)

    def submit(self, prompt, max_new_tokens, req_id):
        self.at.append((req_id, self.clock()))
        self.engine.submit(prompt, max_new_tokens, req_id)


def test_runtime_spans_and_submit_lateness():
    svc = BatchedService("toy", t_memory=1e-3, t_compute_per_seq=2e-4,
                         t_prefill_per_token=1e-5)
    clock = VirtualClock()

    def late_sleep(dt):         # a loop that oversleeps by 3 ms
        clock.sleep(dt + 3e-3)

    def runtime(clock, sleep):
        eng = _Seen(BatchedStubEngine(svc, max_batch=4, clock=clock), clock)
        return EngineRuntime([eng], [ClientConfig(0, ConstantQPS(20), seed=5,
                                                  total_requests=40)],
                             duration=10.0, clock=clock, sleep=sleep), eng

    # the arrivals the loop is due to submit, drawn from a twin runtime
    twin, _ = runtime(VirtualClock(), None)
    due = []
    while (nxt := twin._gens[0].next_arrival()) is not None:
        due.append(nxt[0])
    spans.enable()
    rt, eng = runtime(clock, late_sleep)
    rt.run()
    recs = spans.snapshot()
    submits = sorted((r for r in recs if r.name == "runtime.submit"),
                     key=lambda r: r.attrs["req"])
    assert [r.attrs["req"] for r in submits] == [rid for rid, _ in eng.at]
    late = [r.attrs["late_s"] for r in submits]
    assert late == pytest.approx([t - d for (_, t), d in zip(eng.at, due)],
                                 abs=1e-12)
    assert max(late) == pytest.approx(3e-3) and min(late) >= 0.0
    names = set(_names(recs))
    assert {"runtime.tick", "runtime.step", "runtime.complete",
            "runtime.sleep"} <= names


def test_no_spans_kept_after_a_run_without_a_session():
    svc = BatchedService("toy", t_memory=1e-3, t_compute_per_seq=2e-4,
                         t_prefill_per_token=1e-5)
    clock = VirtualClock()
    rt = EngineRuntime([BatchedStubEngine(svc, max_batch=4, clock=clock)],
                       [ClientConfig(0, ConstantQPS(20), seed=5,
                                     total_requests=20)],
                       duration=5.0, clock=clock, sleep=clock.sleep)
    rt.run()
    assert rt.telemetry.overall().n == 20
    assert spans.snapshot() == []


@pytest.fixture(scope="module")
def smoke_engine():
    cfg = get_config(SMOKE)
    params = R.init_params(cfg, jax.random.PRNGKey(0))
    return InferenceEngine(cfg, params, max_batch=2, max_len=96)


def test_engine_program_names(smoke_engine):
    """The profiler names a program's trace events after its module: the
    prefill is ``jit_fn`` and the decode step ``jit__decode_impl``."""
    eng = smoke_engine
    prefill = eng._prefill_fn(32).lower(
        eng.params, jnp.zeros((1, 32), jnp.int32), jnp.asarray([5], jnp.int32))
    decode = eng._decode.lower(eng.cache, eng.params, eng.tokens,
                               eng.positions)
    assert "module @jit_fn " in prefill.as_text()
    assert "module @jit__decode_impl " in decode.as_text()


def test_engine_span_order(smoke_engine):
    eng = smoke_engine
    rng = np.random.default_rng(3)
    spans.enable()
    eng.submit(rng.integers(0, eng.cfg.vocab_size, size=5), 3, 10)
    eng.submit(rng.integers(0, eng.cfg.vocab_size, size=40), 2, 11)
    done = eng.run_until_idle()
    assert sorted(c.req_id for c in done) == [10, 11]
    recs = spans.snapshot()
    by_id = {r.id: r for r in recs}
    steps = sorted((r for r in recs if r.name == "engine.step"),
                   key=lambda r: r.start)
    assert [s.attrs["kind"] for s in steps] == \
        ["prefill", "prefill", "decode", "decode"]
    assert [s.attrs["active"] for s in steps] == [0, 1, 2, 1]

    def children(parent):
        return [r.name for r in sorted(recs, key=lambda r: r.start)
                if r.parent == parent.id]
    admits = [r for r in recs if r.name == "engine.admit"]
    assert [(a.attrs["req"], a.attrs["tokens"], a.attrs["bucket"])
            for a in sorted(admits, key=lambda r: r.start)] == \
        [(10, 5, 32), (11, 40, 64)]
    for a in admits:
        assert by_id[a.parent].attrs["kind"] == "prefill"
        assert children(a) == ["engine.prefill.dispatch",
                               "engine.prefill.sync", "engine.admit.insert"]
    queues = [r for r in recs if r.name == "engine.queue"]
    assert sorted(q.attrs["req"] for q in queues) == [10, 11]
    for q in queues:
        admit = next(a for a in admits if a.attrs["req"] == q.attrs["req"])
        assert q.start <= q.end <= admit.start
    decodes = [r for r in recs if r.name == "engine.decode"]
    assert [d.attrs["active"] for d in decodes] == [2, 1]
    for d in decodes:
        assert by_id[d.parent].attrs["kind"] == "decode"
        assert children(d) == ["engine.decode.dispatch", "engine.decode.sync",
                               "engine.decode.emit"]
