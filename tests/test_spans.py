"""The program's span recorder (repro.core.spans) and the serving
engine's spans: off by default at no clock read, nesting, the bounded
ring, spans stamped by the caller, the profiler's host plane, and the
engine's admission and decode phases read from the same stamps as its
counters."""

import glob

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import spans
from repro.models import model as M
from repro.serving import ServingEngine


@pytest.fixture(autouse=True)
def fresh_recorder():
    spans.disable()
    spans.reset()
    yield
    spans.disable()
    spans.reset()


def test_off_is_one_shared_noop_and_records_nothing():
    a, b = spans.span("repro.a", rid=1), spans.span("repro.b")
    assert a is b
    with a, spans.span("repro.c"):
        pass
    spans.record("repro.q", 1, 2, rid=0)
    with spans.timed("repro.t") as t:    # stamped for its caller, still
        pass
    assert t.end_ns >= t.start_ns
    assert spans.snapshot() == []


def test_nesting_records_the_parent_and_attrs():
    spans.enable()
    with spans.span("repro.outer", step=7):
        with spans.span("repro.inner", rid=3):
            pass
        with spans.timed("repro.inner2") as t:
            pass
    inner, inner2, outer = spans.snapshot()      # each added as it closed
    assert (inner.name, inner.parent, inner.attrs) == (
        "repro.inner", "repro.outer", {"rid": 3})
    assert (inner2.name, inner2.parent) == ("repro.inner2", "repro.outer")
    assert (inner2.start_ns, inner2.end_ns) == (t.start_ns, t.end_ns)
    assert (outer.name, outer.parent, outer.attrs) == (
        "repro.outer", None, {"step": 7})
    assert outer.start_ns <= inner.start_ns <= inner.end_ns \
        <= inner2.start_ns <= inner2.end_ns <= outer.end_ns


def test_record_adds_a_span_stamped_earlier():
    spans.enable()
    with spans.span("repro.open"):
        spans.record("repro.queue", 100, 250, rid=5)
    q, _ = spans.snapshot()
    assert q == spans.Span("repro.queue", 100, 250, "repro.open",
                           {"rid": 5})
    assert q.seconds == pytest.approx(150e-9)


def test_ring_is_bounded_and_drops_the_oldest():
    spans.enable()
    for i in range(spans.RING_SIZE + 10):
        spans.record("repro.x", i, i + 1)
    ring = spans.snapshot()
    assert len(ring) == spans.RING_SIZE
    assert ring[0].start_ns == 10 and ring[-1].start_ns == spans.RING_SIZE + 9
    spans.reset()
    assert spans.snapshot() == []


def test_profiler_places_spans_on_its_host_plane(tmp_path):
    from jax.profiler import ProfileData
    spans.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with spans.span("repro.outer", rid=4):
            with spans.span("repro.inner"):
                jax.block_until_ready(jax.jit(lambda x: x + 1)(1.0))
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        found[e.name] = (e.start_ns, e.duration_ns,
                                         dict(e.stats))
    assert set(found) == {"repro.outer", "repro.inner"}
    (so, do, attrs), (si, di, _) = found["repro.outer"], found["repro.inner"]
    assert attrs == {"rid": 4}
    assert so <= si and si + di <= so + do


# ----------------------------------------------------------------------
# the serving engine
# ----------------------------------------------------------------------

CHUNK = 8
LENGTHS = (13, 21, 16)         # remainders 5, 5 and 0 tokens past a bucket


@pytest.fixture(scope="module")
def model():
    cfg = get_config("qwen3-0.6b", reduced=True)
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _serve(model, record: bool):
    """Three requests over two slots (the third waits in the queue for
    a free slot); returns the engine, its requests and the ring."""
    cfg, params = model
    rng = np.random.default_rng(7)
    eng = ServingEngine(cfg, params, max_slots=2, max_len=48,
                        prefill_chunk=CHUNK)
    if record:
        spans.enable()
    reqs = [eng.submit(rng.integers(0, cfg.vocab, (n,)).astype(np.int32), 4)
            for n in LENGTHS]
    eng.run()
    spans.disable()
    return eng, reqs, spans.snapshot()


@pytest.fixture(scope="module")
def served(model):
    spans.disable()
    spans.reset()
    out = _serve(model, record=True)
    spans.reset()
    return out


def _named(ring, name):
    return [s for s in ring if s.name == name]


def _children(ring, parent):
    return sorted((s for s in ring if s.parent == parent.name
                   and parent.start_ns <= s.start_ns
                   and s.end_ns <= parent.end_ns),
                  key=lambda s: s.start_ns)


def test_admission_phases_and_token_steps(served):
    eng, reqs, ring = served
    admits = _named(ring, "repro.engine.admit")
    assert sorted(a.attrs["rid"] for a in admits) == [r.rid for r in reqs]
    for a in admits:
        n = a.attrs["prompt_len"]
        assert a.parent == "repro.engine.step"
        assert a.attrs["bucket"] == n - n % CHUNK
        assert a.attrs["token_steps"] == n % CHUNK
        assert [c.name for c in _children(ring, a)] == [
            "repro.engine.admit.prefill", "repro.engine.admit.token_steps",
            "repro.engine.admit.slot_copy", "repro.engine.admit.first_token"]
    total = 0.0                # in admission order, as the engine adds
    for a in admits:
        total += a.seconds
    assert total == eng.prefill_time
    # the remainder tokens ran through their own jitted step, at batch 1,
    # which a profile names jit_admit_token_step
    assert eng._admit_step._cache_size() == 1
    assert eng._step._cache_size() == 1
    sub = M.init_cache(eng.cfg, 1, eng.max_len)
    text = eng._admit_step.lower(eng.params, np.zeros((1, 1), np.int32),
                                 np.int32(0), sub).as_text()
    assert text.startswith("module @jit_admit_token_step")


def test_each_decode_step_has_its_three_phases(served):
    eng, _, ring = served
    decodes = sorted(_named(ring, "repro.engine.decode"),
                     key=lambda s: s.start_ns)
    assert [d.attrs["step"] for d in decodes] == list(range(eng.decode_steps))
    assert len(eng._step_times) == len(decodes)
    for d, dt in zip(decodes, eng._step_times):
        assert d.parent == "repro.engine.step"
        kids = _children(ring, d)
        assert [c.name for c in kids] == [
            "repro.engine.decode.dispatch", "repro.engine.decode.logits_read",
            "repro.engine.decode.sample"]
        assert (kids[1].end_ns - kids[0].start_ns) / 1e9 == dt
    steps = _named(ring, "repro.engine.step")
    assert all(s.parent is None for s in steps)
    assert len(_named(ring, "repro.engine.schedule")) >= len(steps)


def test_a_queue_span_per_admitted_request(served):
    _, reqs, ring = served
    queue = {q.attrs["rid"]: q for q in _named(ring, "repro.engine.queue")}
    admits = {a.attrs["rid"]: a for a in _named(ring, "repro.engine.admit")}
    assert sorted(queue) == [r.rid for r in reqs]
    for r in reqs:
        assert queue[r.rid].end_ns == admits[r.rid].start_ns
        assert queue[r.rid].seconds == pytest.approx(r.t_admitted - r.t_due,
                                                     abs=1e-6)
    # the third request waited for a slot to free
    assert queue[reqs[2].rid].seconds > queue[reqs[0].rid].seconds


def test_greedy_tokens_do_not_depend_on_recording(model, served):
    _, on, _ = served
    _, off, ring = _serve(model, record=False)
    assert ring == []
    assert [r.generated for r in off] == [r.generated for r in on]
