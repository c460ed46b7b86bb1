"""The readers of the program's own host spans, and the idle gaps labelled
by the innermost span, against a made-up nested trace and CPU rehearsals
of every cell."""
import os

import pytest
from jax.profiler import ProfileData

import repro.core
from chipbench import idle as I
from chipbench import run as RUN
from chipbench import trace as T
from rehearse import rehearse

DATA = os.path.join(os.path.dirname(__file__), "data")
#: the readers of the program's spans, by the cell that reports them
READERS = {
    "phi3-chat": ["engine_decode_ms.chat"],
    "phi3-chat-sat": ["engine_decode_ms.sat", "decode_host_ms.sat"],
    "stablelm-rag": ["admit_host_ms", "engine_queue_wait_p90_ms",
                     "submit_lateness_p90_ms", "bucket_pad_share"],
}


def planes(name):
    with open(os.path.join(DATA, name + ".textproto")) as f:
        return ProfileData.from_text_proto(f.read()).planes


def test_innermost_span_labels_each_gap():
    assert I.label(planes("nested_trace")) == pytest.approx({
        "engine.decode.emit before jit_add": 2e-6,
        "runtime.tick before jit__decode_impl": 2e-6,
        # between two of its children, and of two spans that start
        # together, the shorter
        "engine.decode before jit_fn": 1e-6,
        "engine.prefill.dispatch before jit_fn": 5e-6,
        "outside engine steps before jit__decode_impl": 4e-6})
    # named by the program running at its end, the gap between two ops of
    # one decode program delays that program
    assert I.label(planes("nested_trace"), holding=True)[
        "engine.decode before jit__decode_impl"] == pytest.approx(1e-6)
    # only that gap lies inside one program's event
    assert [inside for _, _, inside in I.gaps(planes("nested_trace"))] == \
        [False, False, True, False, False]


@pytest.mark.parametrize("trace", ["small_trace", "nested_trace"])
def test_bench_spans_alone_label_as_the_reduction_does(trace):
    want = T.reduce(planes(trace))["idle"]
    assert I.label(planes(trace), ("bench.",)) == want
    if trace == "small_trace":
        assert I.label(planes(trace)) == want
    else:
        assert want == pytest.approx({
            "bench.step before jit_add": 2e-6,
            "outside engine steps before jit__decode_impl": 6e-6,
            "bench.step before jit_fn": 6e-6})


def test_readers_find_nothing_without_the_program_spans(monkeypatch):
    monkeypatch.delattr(repro.core, "spans", raising=False)
    monkeypatch.setitem(__import__("sys").modules, "repro.core.spans", None)
    run = RUN.Run(seconds=1.0, window=(0.0, 1e9), t0=0.0, arrivals=[],
                  reqs={}, steps=[], setup_s=0.0, model={}, peak={})
    for name in sum(READERS.values(), []):
        assert RUN.reader(name)(run) is None


def test_queue_wait_ignores_an_earlier_runs_marks(monkeypatch):
    from repro.core import spans
    # request 0 of an earlier run in this process waited 0.5 s; request 0
    # of this run (from t0 = 10) is still queued when the window closes
    recs = [spans.Record(1, "engine.queue", 1.0, 1.5, None, {"req": 0}),
            spans.Record(2, "runtime.submit", 12.0, 12.0, None,
                         {"req": 0, "late_s": 0.0}),
            spans.Record(3, "engine.queue", 12.0, 12.0, None, {"req": 9})]
    monkeypatch.setattr(spans, "snapshot", lambda: list(recs))
    run = RUN.Run(seconds=4.0, window=(11.0, 15.0), t0=10.0, arrivals=[],
                  reqs={}, steps=[], setup_s=0.0, model={}, peak={})
    assert RUN.reader("engine_queue_wait_p90_ms")(run) == \
        pytest.approx(3e3)


@pytest.mark.parametrize("cell", list(READERS))
def test_span_readers_on_a_traced_rehearsal(cell):
    out = rehearse(cell, rate=2.0, trace=True)
    got = out["metrics"]
    assert out["correct"], out["checks"]
    for name in READERS[cell]:
        assert name in got, (name, sorted(got))
    v = {k: m["value"] for k, m in got.items()}
    if cell == "stablelm-rag":
        # the engine's own count of bucket and prompt tokens is the one
        # the proxy saw
        assert v["bucket_pad_share"] == pytest.approx(v["prefill_pad_share"])
        assert 0.0 < v["admit_host_ms"]
        assert 0.0 <= v["engine_queue_wait_p90_ms"] <= 1e3 * 3.0
        assert 0.0 <= v["submit_lateness_p90_ms"] <= 1e3 * 3.0
    else:
        tag = cell.split("-")[-1]
        inner, outer = v[f"engine_decode_ms.{tag}"], v[f"decode_step_ms.{tag}"]
        # the engine's decode span lies inside the proxy's step
        assert 0.5 * outer < inner <= outer * 1.01
        if tag == "sat":
            assert 0.0 < v["decode_host_ms.sat"] < inner
