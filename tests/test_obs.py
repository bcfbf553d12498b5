"""Observability subsystem: tracer, metrics registry, trace analysis.

The cardinal rule under test is PURE OBSERVATION: enabling the tracer and
recording metrics must not change a single computed token (greedy serve
traced vs untraced is asserted bit-identical).  The rest pins down the
contracts the tooling stands on: span nesting across threads, Perfetto
``trace_event`` schema validity, P² streaming-quantile accuracy vs numpy,
the label-cardinality guard, exact-percentile agreement with
``np.percentile``, interval arithmetic for the overlap report, and the
instrumentation-point catalog staying in sync with docs/OBSERVABILITY.md.
"""
import json
import os
import threading

import numpy as np
import pytest

from repro.obs import analysis, metrics, points, trace


@pytest.fixture(autouse=True)
def _isolate_obs():
    """Every test gets a fresh registry and no global tracer."""
    metrics.reset()
    trace.disable()
    yield
    metrics.reset()
    trace.disable()


# ---------------------------------------------------------------------------
# tracer

class TestTracer:
    def test_span_nesting_parents(self):
        tr = trace.enable()
        with trace.span("outer"):
            with trace.span("mid"):
                with trace.span("inner"):
                    pass
            with trace.span("sibling"):
                pass
        trace.disable()
        by_name = {e.name: e for e in tr.events}
        assert by_name["outer"].parent is None
        assert by_name["mid"].parent == by_name["outer"].id
        assert by_name["inner"].parent == by_name["mid"].id
        assert by_name["sibling"].parent == by_name["outer"].id
        # children are contained in their parent's [t0, t0+dur) window
        o, i = by_name["outer"], by_name["inner"]
        assert o.ts_us <= i.ts_us
        assert i.ts_us + i.dur_us <= o.ts_us + o.dur_us + 1e-3

    def test_thread_safety_and_per_thread_stacks(self):
        tr = trace.enable()
        n_threads, n_spans = 8, 200
        # hold every thread at a barrier so all 8 are alive concurrently —
        # CPython recycles thread idents of exited threads, so sequential
        # completion would legitimately collapse the tid mapping
        gate = threading.Barrier(n_threads)

        def work(k):
            gate.wait()
            for i in range(n_spans):
                with trace.span("w", idx=i, thread=k):
                    pass

        threads = [threading.Thread(target=work, args=(k,), name=f"w{k}")
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        trace.disable()
        events = tr.events
        assert len(events) == n_threads * n_spans
        # ids unique; every span rooted (no cross-thread parent leakage
        # since each thread's stack is thread-local and spans don't nest)
        assert len({e.id for e in events}) == len(events)
        assert all(e.parent is None for e in events)
        assert len({e.tid for e in events}) == n_threads

    def test_disabled_span_is_noop_and_cheap(self):
        assert not trace.enabled()
        cm = trace.span("anything", layer=3)
        assert cm is trace.span("other")     # shared singleton
        with cm:
            pass
        trace.instant("nothing")             # no tracer: silently dropped

    def test_chrome_trace_schema(self, tmp_path):
        tr = trace.enable()
        with trace.span("a", cat="serve", layer=1):
            with trace.span("b", cat="decode"):
                pass
        trace.instant("mark", cat="resident", layer=2)
        trace.disable()
        path = os.fspath(tmp_path / "t.json")
        n = tr.save(path)
        assert n == 3                        # 2 spans + 1 instant
        with open(path) as f:
            doc = json.load(f)
        events = doc["traceEvents"]
        assert isinstance(events, list)
        phases = {e["ph"] for e in events}
        assert phases <= {"X", "M", "i"}
        for e in events:
            assert {"name", "ph", "pid", "tid"} <= set(e)
            if e["ph"] == "X":
                assert e["dur"] >= 0 and "ts" in e
        names = {e["args"]["name"] for e in events if e["ph"] == "M"
                 and e["name"] == "thread_name"}
        assert names                          # thread metadata present
        xs = [e for e in events if e["ph"] == "X"]
        assert {e["name"] for e in xs} == {"a", "b"}
        assert [e for e in events if e["ph"] == "i"][0]["args"]["layer"] == 2

    def test_event_cap_drops_not_grows(self, monkeypatch):
        monkeypatch.setattr(trace, "MAX_EVENTS", 10)
        tr = trace.enable()
        for i in range(50):
            with trace.span("s", i=i):
                pass
        trace.disable()
        assert len(tr.events) == 10
        assert tr.dropped == 40

    def test_span_tree_renders(self):
        tr = trace.enable()
        with trace.span("outer"):
            with trace.span("inner", layer=7):
                pass
        trace.disable()
        txt = tr.span_tree()
        assert "outer" in txt and "inner" in txt and "layer=7" in txt
        assert txt.index("outer") < txt.index("inner")

    def test_sync_enabled_contract(self):
        assert not trace.sync_enabled()
        trace.enable(sync=False)
        assert not trace.sync_enabled()
        trace.enable(sync=True)
        assert trace.sync_enabled()
        trace.disable()
        assert not trace.sync_enabled()


# ---------------------------------------------------------------------------
# metrics

class TestPercentile:
    def test_matches_numpy(self):
        rng = np.random.default_rng(0)
        for n in (1, 2, 3, 10, 101):
            xs = rng.normal(size=n).tolist()
            for p in (0, 25, 50, 90, 99, 100):
                assert metrics.percentile(xs, p) == pytest.approx(
                    float(np.percentile(xs, p)), abs=1e-9)

    def test_empty_and_bounds(self):
        assert np.isnan(metrics.percentile([], 50))
        with pytest.raises(ValueError):
            metrics.percentile([1.0], 101)
        with pytest.raises(ValueError):
            metrics.percentile([1.0], -1)

    def test_unbiased_vs_old_index_rule(self):
        # the bug this replaced: sorted[int(n*0.99)] clamps to max for small n
        xs = list(range(16))
        old = sorted(xs)[min(len(xs) - 1, int(len(xs) * 0.99))]
        assert old == 15                       # the max, not a p99
        assert metrics.percentile(xs, 99) == pytest.approx(14.85)


class TestP2Quantile:
    def test_accuracy_vs_numpy(self):
        rng = np.random.default_rng(7)
        xs = rng.normal(10.0, 2.0, size=20_000)
        for q in (0.5, 0.9, 0.99):
            est = metrics.P2Quantile(q)
            for x in xs:
                est.observe(float(x))
            exact = float(np.quantile(xs, q))
            spread = float(np.quantile(xs, 0.999) - np.quantile(xs, 0.001))
            assert abs(est.value - exact) / spread < 0.01, (q, est.value, exact)

    def test_exact_small_n(self):
        est = metrics.P2Quantile(0.5)
        for x in (3.0, 1.0, 2.0):
            est.observe(x)
        assert est.value == pytest.approx(2.0)
        assert np.isnan(metrics.P2Quantile(0.5).value)


class TestRegistry:
    def test_counter_gauge_histogram_roundtrip(self):
        r = metrics.Registry()
        r.counter("c").inc(2, mode="x")
        r.counter("c").inc(3, mode="x")
        r.gauge("g").set(1.5)
        for v in (0.1, 0.2, 0.3):
            r.histogram("h").observe(v)
        assert r.counter("c").value(mode="x") == 5
        assert r.gauge("g").value() == 1.5
        assert r.histogram("h").count() == 3
        rows = r.snapshot()
        by = {(row["name"], row["kind"]): row for row in rows}
        assert by[("c", "counter")]["value"] == 5
        assert by[("h", "histogram")]["count"] == 3
        assert "p99" in by[("h", "histogram")]

    def test_counter_rejects_negative_and_kind_drift(self):
        r = metrics.Registry()
        with pytest.raises(ValueError):
            r.counter("c").inc(-1)
        r.counter("dup")
        with pytest.raises(TypeError):
            r.gauge("dup")

    def test_cardinality_guard(self):
        r = metrics.Registry()
        c = r.counter("runaway")
        for i in range(metrics.MAX_LABEL_SETS):
            c.inc(rid=i)
        with pytest.raises(metrics.CardinalityError):
            c.inc(rid=metrics.MAX_LABEL_SETS)

    def test_jsonl_export_strict_json(self, tmp_path):
        r = metrics.Registry()
        r.gauge("g").set(float("nan"))       # must serialize as null
        r.counter("c").inc()
        lc = r.lifecycle(1, outcome="length")
        lc.event("queued", 1.0)
        lc.event("done", 2.0)
        path = os.fspath(tmp_path / "m.jsonl")
        n = r.write_jsonl(path)
        rows = [json.loads(line) for line in open(path)]
        assert len(rows) == n == 3
        kinds = {row["kind"] for row in rows}
        assert kinds == {"gauge", "counter", "lifecycle"}
        g = next(row for row in rows if row["kind"] == "gauge")
        assert g["value"] is None             # NaN -> null
        life = next(row for row in rows if row["kind"] == "lifecycle")
        assert life["events"] == [["queued", 1.0], ["done", 2.0]]

    def test_default_registry_reset_isolates(self):
        metrics.counter("x").inc()
        assert metrics.default_registry().counter("x").total() == 1
        metrics.reset()
        assert metrics.default_registry().counter("x").total() == 0

    def test_legacy_view_freezes_at_construction(self):
        r = metrics.Registry()
        r.gauge("serve.decode_tok_per_s").set(10.0)
        view = metrics.LegacyMetricsView(
            r, {"tok_per_s": "serve.decode_tok_per_s",
                "decode_tok_per_s": "serve.decode_tok_per_s"},
            extra={"decode_backend": "numpy"})
        r.gauge("serve.decode_tok_per_s").set(99.0)   # a later serve
        assert view["tok_per_s"] == view["decode_tok_per_s"] == 10.0
        assert view["decode_backend"] == "numpy"
        assert set(view) == {"tok_per_s", "decode_tok_per_s",
                             "decode_backend"}
        assert view.get("missing") is None


# ---------------------------------------------------------------------------
# analysis (interval arithmetic + overlap report)

class TestAnalysis:
    def test_interval_algebra(self):
        assert analysis.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
        assert analysis.subtract([(0, 10)], [(2, 4), (6, 8)]) == \
            [(0, 2), (4, 6), (8, 10)]
        assert analysis.total([(0, 2), (1, 3)]) == 3
        assert analysis.intersect_total([(0, 5)], [(3, 8)]) == 2

    def test_overlap_report_synthetic(self):
        def span(name, ts, dur, tid=0):
            return dict(name=name, ph="X", ts=ts, dur=dur, pid=1, tid=tid)
        # step [0, 100); wait [40, 60); decode [30, 80) on the worker:
        # busy = [0,40) + [60,100); hidden decode = [30,40)+[60,80) = 30
        events = [span("serve.decode_step", 0, 100),
                  span("resident.consume_wait", 40, 20),
                  span("resident.decode", 30, 50, tid=1)]
        rep = analysis.overlap_report(events)
        assert rep["decode_s"] == pytest.approx(50e-6)
        assert rep["stall_s"] == pytest.approx(20e-6)
        assert rep["overlap_fraction"] == pytest.approx(30 / 50)
        assert rep["n_decode_spans"] == 1

    def test_overlap_report_empty(self):
        rep = analysis.overlap_report([])
        assert np.isnan(rep["overlap_fraction"])
        assert rep["decode_s"] == 0

    def test_load_trace_events_roundtrip(self, tmp_path):
        tr = trace.enable()
        with trace.span("x"):
            pass
        trace.disable()
        p = os.fspath(tmp_path / "t.json")
        tr.save(p)
        events = analysis.load_trace_events(p)
        assert analysis.span_intervals(events, "x")


# ---------------------------------------------------------------------------
# instrumentation points catalog <-> docs

def test_points_catalog_documented():
    """Every span/metric the catalog requires must appear by name in
    docs/OBSERVABILITY.md — the doc IS the user-facing contract."""
    doc = os.path.join(os.path.dirname(__file__), "..", "docs",
                       "OBSERVABILITY.md")
    with open(doc) as f:
        text = f.read()
    missing = [name
               for mode in points.EXPECTED_POINTS.values()
               for group in ("spans", "metrics")
               for name in mode[group]
               if name not in text]
    assert not missing, f"undocumented instrumentation points: {missing}"


# ---------------------------------------------------------------------------
# pure observation: tracing must not change computed tokens

def test_bit_identity_trace_on_vs_off():
    import jax
    from repro.configs import registry
    from repro.models import api
    from repro.serving import engine as serving_engine

    cfg = registry.reduced(registry.get("qwen3-1.7b"))
    params = api.build(cfg).init(cfg, jax.random.PRNGKey(0))
    sc = serving_engine.ServeConfig(max_len=16)
    eng = serving_engine.Engine(cfg, params, sc)
    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab, (2, 8)).astype(np.int32)
    import jax.numpy as jnp
    prompt = jnp.asarray(prompt)

    out_off = np.asarray(eng.generate(prompt, 6))
    tr = trace.enable(sync=True)        # sync fencing must also be pure
    out_on = np.asarray(eng.generate(prompt, 6))
    trace.disable()
    assert np.array_equal(out_off, out_on)
    assert any(e.name == "serve.decode_step" for e in tr.events)
    # and the registry recorded the serve without being asked
    assert metrics.histogram("serve.decode_step_s").count() > 0
    assert metrics.counter("serve.tokens").total() > 0


# ---------------------------------------------------------------------------
# ContinuousEngine.step's spans: profiler mode, host syncs, pure observation

STEP_CHILDREN = ("serve.admit", "serve.admit_chunk", "serve.first_token",
                 "serve.decode_dispatch", "serve.decode_sync", "serve.commit")


@pytest.fixture(scope="module")
def tiny_continuous():
    """A reduced dense model and one ``ServeSteps`` shared by every engine
    of the module, so its programs compile once."""
    import jax
    from repro.configs import registry
    from repro.models import api
    from repro.serving import engine as serving_engine

    cfg = registry.reduced(registry.get("qwen3-1.7b"))
    params = api.build(cfg).init(cfg, jax.random.PRNGKey(0))
    sc = serving_engine.ServeConfig(max_len=32)
    return cfg, params, sc, serving_engine.ServeSteps(cfg, sc)


def _serve_continuous(tiny, lens=(5, 11, 19), new=(4, 3, 5)):
    """Three greedy requests through two slots (one queues), prefill in
    8-token chunks, one chunk a step; returns the engine and the outputs."""
    from repro.serving.batching import ContinuousEngine
    cfg, params, sc, steps = tiny
    ce = ContinuousEngine(cfg, params, sc, n_slots=2, prefill_chunk=8,
                          admit_chunks_per_step=1, steps=steps)
    rng = np.random.default_rng(0)
    reqs = [ce.submit(rng.integers(0, cfg.vocab, (n,)).astype(np.int32), m)
            for n, m in zip(lens, new)]
    ce.run()
    return ce, [list(r.output) for r in reqs]


def test_profiler_mode_puts_step_spans_on_the_profilers_host_plane(
        tiny_continuous, tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData

    _serve_continuous(tiny_continuous)          # compile outside the trace
    jax.profiler.start_trace(os.fspath(tmp_path))
    tr = trace.enable(profiler=True)
    try:
        ce, _ = _serve_continuous(tiny_continuous)
    finally:
        trace.disable()
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.fspath(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    by_name = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    by_name.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns))
    assert set(STEP_CHILDREN) | {"serve.step"} <= set(by_name)
    steps = by_name["serve.step"]
    for name in STEP_CHILDREN:
        for a, b in by_name[name]:
            assert any(s <= a and b <= e for s, e in steps), name
    assert len(by_name["serve.decode_sync"]) == ce.n_decode_steps
    assert len(by_name["serve.admit"]) == len(by_name["serve.first_token"]) \
        == 3
    # the in-memory record is kept alongside: same spans, parents, args
    recs = tr.events
    step_ids = {e.id for e in recs if e.name == "serve.step"}
    assert len(step_ids) == len(steps)
    for e in recs:
        if e.name in STEP_CHILDREN:
            assert e.parent in step_ids, e.name
    assert sorted(e.args["rid"] for e in recs if e.name == "serve.admit") \
        == sorted(e.args["rid"] for e in recs
                  if e.name == "serve.first_token")


def test_step_spans_count_decode_steps_and_admissions(tiny_continuous):
    """The spans alone count the step's blocking reads: one
    ``serve.decode_sync`` a decode step, one ``serve.first_token`` an
    admission; ``active`` sums to the tokens the decode steps made."""
    tr = trace.enable()
    try:
        ce, outs = _serve_continuous(tiny_continuous)
    finally:
        trace.disable()
    assert [len(o) for o in outs] == [4, 3, 5]
    by_name = {}
    for e in tr.events:
        by_name.setdefault(e.name, []).append(e)
    for name in ("serve.decode_dispatch", "serve.decode_sync",
                 "serve.commit"):
        assert len(by_name[name]) == ce.n_decode_steps, name
    assert len(by_name["serve.admit"]) == \
        len(by_name["serve.first_token"]) == 3
    assert sum(e.args["active"] for e in by_name["serve.decode_dispatch"]) \
        == sum(map(len, outs)) - 3
    assert {e.args["rid"] for e in by_name["serve.admit_chunk"]} == \
        {e.args["rid"] for e in by_name["serve.admit"]}


def test_continuous_bit_identity_trace_off_vs_profiler_mode(tiny_continuous):
    _, off = _serve_continuous(tiny_continuous)
    tr = trace.enable(profiler=True)
    _, on = _serve_continuous(tiny_continuous)
    trace.disable()
    assert on == off
    assert any(e.name == "serve.decode_sync" for e in tr.events)


def test_disabled_tracing_builds_no_trace_annotation(tiny_continuous,
                                                     monkeypatch):
    import jax

    class Refused:
        def __init__(self, *a, **k):
            raise AssertionError("TraceAnnotation built")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refused)
    assert trace.span("serve.step") is trace.span("serve.commit")
    _serve_continuous(tiny_continuous)          # tracing off
    trace.enable()                              # on, not in profiler mode
    _serve_continuous(tiny_continuous)
    trace.enable(profiler=True)                 # the patch does bite here
    with pytest.raises(AssertionError, match="TraceAnnotation built"):
        with trace.span("serve.step"):
            pass
