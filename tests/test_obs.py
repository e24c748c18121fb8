"""Unified telemetry layer tests (repro.obs + its instrumentation).

Pins the contracts the observability PR introduced:

- metrics registry math: counters/gauges, fixed-bucket histogram percentile
  interpolation (exact values, not ranges),
- tracer semantics: per-thread rings, bounded overflow with drop counts,
  nesting, cross-process ingest with clock-offset correction,
- Chrome trace-event export schema (the shape Perfetto loads): "M" metadata
  + "X" complete events, microsecond ts/dur, per-process pid tracks, rid
  args passthrough — and that a disabled run emits nothing,
- trainer integration: a traced in-process run records spans from both the
  step loop and the prefetch thread without enabling attribution; a traced
  mp run shows >= 3 processes on one timeline with client rounds and worker
  serve spans correlated by rid,
- the worker stats conservation law ``shm_replies + pickle_replies ==
  batches`` on both serve paths (slab and pipe-pickle fallback), and the
  diagnostic context (worker_id / rid / stats) riding on EngineWorkerError.
"""
import contextlib
import json
import signal
import threading

import numpy as np
import pytest

from repro.graph import DistributedGraphEngine, GraphClient, TOY, generate
from repro.graph.service import EngineWorkerError
from repro.obs import (
    DEFAULT_NS_BUCKETS,
    Histogram,
    MetricsRegistry,
    Telemetry,
    Tracer,
    chrome_trace,
    span_scope,
    trace_events,
)

RELS = ("u2click2i", "i2click2u")

HARD_TIMEOUT_S = 120


@pytest.fixture
def watchdog():
    """Hard per-test timeout for the mp tests (mirrors test_graph_service)."""

    def _expired(signum, frame):
        raise TimeoutError(f"test exceeded hard {HARD_TIMEOUT_S}s watchdog")

    old = signal.signal(signal.SIGALRM, _expired)
    signal.alarm(HARD_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="module")
def ds():
    return generate(TOY, seed=0)


def make_trainer(ds, steps=6, engine_backend="inproc", **cfg_kw):
    from repro.core import Graph4RecConfig, HeteroGNNConfig
    from repro.embedding import EmbeddingConfig
    from repro.sampling import EgoConfig, PairConfig, PipelineConfig
    from repro.train import Graph4RecTrainer, TrainerConfig
    from repro.walk import WalkConfig

    mc = Graph4RecConfig(
        embedding=EmbeddingConfig(num_nodes=ds.graph.num_nodes, dim=16),
        gnn=HeteroGNNConfig(gnn_type="lightgcn", num_relations=2,
                            num_layers=1, dim=16),
        fanouts=(3,),
        relations=RELS,
        loss="inbatch_softmax",
    )
    pc = PipelineConfig(
        walk=WalkConfig(metapaths=["u2click2i - i2click2u"], walk_len=6),
        pair=PairConfig(win_size=2),
        ego=EgoConfig(relations=list(RELS), fanouts=[3]),
        batch_pairs=64, walks_per_round=16,
    )
    engine = (
        ds.graph if engine_backend == "mp"
        else DistributedGraphEngine(ds.graph, num_partitions=2)
    )
    cfg = TrainerConfig(num_steps=steps, log_every=0, eval_at_end=False,
                        seed=0, engine_backend=engine_backend, **cfg_kw)
    return Graph4RecTrainer(ds, engine, mc, pc, cfg)


# --------------------------------------------------------------- metrics
@pytest.mark.quick
class TestMetrics:
    def test_counter_and_gauge(self):
        reg = MetricsRegistry()
        c = reg.counter("x")
        c.inc()
        c.inc(3)
        assert c.value == 4
        assert reg.counter("x") is c  # get-or-create returns the same object
        g = reg.gauge("q")
        g.set(5)
        g.set(2)
        assert g.value == 2.0
        assert g.max == 5.0

    def test_histogram_pinned_percentiles(self):
        """Exact fixed-bucket interpolation on a hand-checkable ladder."""
        h = Histogram("lat", buckets=[10, 20, 40])
        for v in (5, 15, 30, 100):
            h.observe(v)
        assert h.count == 4
        assert h.sum == 150.0
        # rank(p50) = 2 lands at the top of bucket (10, 20]
        assert h.percentile(50.0) == pytest.approx(20.0)
        # rank(p99) = 3.96 lands in the overflow bucket -> its lower edge
        assert h.percentile(99.0) == pytest.approx(40.0)

    def test_histogram_interpolates_within_bucket(self):
        h = Histogram("lat", buckets=[10, 20, 40])
        h.observe(15)  # sole sample, bucket (10, 20]
        assert h.percentile(50.0) == pytest.approx(15.0)
        # below the first boundary interpolates from 0
        h2 = Histogram("lat2", buckets=[10, 20, 40])
        h2.observe(4)
        assert h2.percentile(50.0) == pytest.approx(5.0)

    def test_histogram_empty_and_bad_buckets(self):
        h = Histogram("lat")
        assert h.percentile(50.0) == 0.0
        assert h.summary() == {"count": 0, "sum": 0.0, "p50": 0.0, "p99": 0.0}
        with pytest.raises(ValueError):
            Histogram("bad", buckets=[20, 10])
        with pytest.raises(ValueError):
            Histogram("bad", buckets=[])

    def test_default_ladder_spans_us_to_50s(self):
        assert list(DEFAULT_NS_BUCKETS) == sorted(DEFAULT_NS_BUCKETS)
        assert DEFAULT_NS_BUCKETS[0] == 1_000  # 1 us in ns
        assert DEFAULT_NS_BUCKETS[-1] == 50_000_000_000  # 50 s in ns

    def test_registry_summary_shape(self):
        reg = MetricsRegistry()
        reg.counter("c").inc()
        reg.gauge("g").set(1.5)
        reg.histogram("h").observe(2_000)
        s = reg.summary()
        assert s["counters"] == {"c": 1}
        assert s["gauges"] == {"g": {"value": 1.5, "max": 1.5}}
        assert s["histograms"]["h"]["count"] == 1


# ---------------------------------------------------------------- tracer
@pytest.mark.quick
class TestTracer:
    def test_span_context_records(self):
        t = Tracer()
        with t.span("work", cat="test", rid=7):
            pass
        [(tid, tname, spans, dropped)] = t.threads()
        assert tid == 1 and dropped == 0
        [(name, cat, t0, dur, args)] = spans
        assert (name, cat) == ("work", "test")
        assert t0 > 0 and dur >= 0
        assert args == {"rid": 7}

    def test_nesting_inner_within_outer(self):
        t = Tracer()
        with t.span("outer"):
            with t.span("inner"):
                pass
        [(_, _, spans, _)] = t.threads()
        by_name = {s[0]: s for s in spans}
        # inner closes first, so it precedes outer in the ring
        assert [s[0] for s in spans] == ["inner", "outer"]
        _, _, it0, idur, _ = by_name["inner"]
        _, _, ot0, odur, _ = by_name["outer"]
        assert ot0 <= it0 and it0 + idur <= ot0 + odur

    def test_ring_overflow_keeps_newest_reports_drops(self):
        t = Tracer(capacity=4)
        for i in range(10):
            t.add_span(f"s{i}", "t", i, 1)
        [(_, _, spans, dropped)] = t.threads()
        assert [s[0] for s in spans] == ["s6", "s7", "s8", "s9"]  # oldest first
        assert dropped == 6
        assert t.dropped_count() == 6
        assert t.span_count() == 4

    def test_per_thread_rings(self):
        t = Tracer()
        t.add_span("main", "t", 0, 1)

        def record():
            t.add_span("other", "t", 0, 1)

        th = threading.Thread(target=record, name="obs-helper")
        th.start()
        th.join()
        got = t.threads()
        assert len(got) == 2
        names = {tname: [s[0] for s in spans] for _, tname, spans, _ in got}
        assert names[threading.current_thread().name] == ["main"]
        assert names["obs-helper"] == ["other"]
        tids = [tid for tid, _, _, _ in got]
        assert len(set(tids)) == 2

    def test_ingest_applies_clock_offset(self):
        t = Tracer()
        t.ingest("graph-worker-0", 4242,
                 [("worker.sample", "worker", 1000, 10, {"rid": 3})],
                 offset_ns=400, dropped=2)
        [(pname, pid, spans, dropped)] = t.foreign()
        assert (pname, pid, dropped) == ("graph-worker-0", 4242, 2)
        assert spans == [("worker.sample", "worker", 600, 10, {"rid": 3})]
        assert t.span_count() == 1
        assert t.dropped_count() == 2

    def test_ingest_negative_offset_shifts_forward(self):
        """A worker whose monotonic clock lags the trainer's has a
        negative offset; correction must shift its spans forward, never
        produce times before the foreign t0."""
        t = Tracer()
        t.ingest("graph-worker-1", 4243,
                 [("worker.sample", "worker", 1000, 10, None)],
                 offset_ns=-400)
        [(_, _, spans, _)] = t.foreign()
        assert spans == [("worker.sample", "worker", 1400, 10, None)]

    def test_ingest_accumulates_rounds_and_drops(self):
        """Repeated stats rounds from one worker each land as their own
        batch; spans and drop counts accumulate instead of clobbering."""
        t = Tracer()
        t.ingest("graph-worker-0", 99, [("a", "w", 10, 1, None)], dropped=2)
        t.ingest("graph-worker-0", 99, [("b", "w", 20, 1, None)], dropped=3)
        batches = t.foreign()
        assert [s[0] for _, _, spans, _ in batches for s in spans] == ["a", "b"]
        assert t.span_count() == 2
        assert t.dropped_count() == 5

    def test_mark_records_instant_events(self):
        t = Tracer()
        t.mark("trainer.fused_fallback", reason="budget")
        t.mark("plain")
        marks = t.marks()
        assert [m[0] for m in marks] == ["trainer.fused_fallback", "plain"]
        name, cat, t0, args = marks[0]
        assert cat == "mark" and t0 > 0 and args == {"reason": "budget"}
        assert marks[1][3] is None

    def test_mark_capacity_bounded(self):
        t = Tracer()
        for i in range(1100):
            t.mark(f"m{i}")
        assert len(t.marks()) == 1024  # oldest kept: marks are rare events

    def test_span_scope_disabled_is_shared_nullcontext(self):
        scope = span_scope(None, "anything", rid=1)
        assert isinstance(scope, contextlib.nullcontext)
        # one shared instance: disabled call sites allocate nothing
        assert span_scope(None, "a") is span_scope(None, "b")
        t = Tracer()
        with span_scope(t, "real", cat="test"):
            pass
        assert t.span_count() == 1


# ---------------------------------------------------------- chrome export
@pytest.mark.quick
class TestChromeExport:
    def _traced(self):
        tel = Telemetry(process_name="trainer")
        tel.tracer.add_span("step", "trainer", 2_500, 1_500, {"i": 0})
        tel.tracer.ingest(
            "graph-worker-0", 777,
            [("worker.sample", "worker", 5_000, 2_000, {"rid": 9})],
        )
        tel.metrics.counter("client.rounds_worker").inc()
        return tel

    def test_schema(self):
        trace = self._traced().chrome_trace()
        assert set(trace) == {"traceEvents", "displayTimeUnit", "otherData"}
        assert trace["displayTimeUnit"] == "ms"
        assert trace["otherData"]["dropped_spans"] == 0
        assert trace["otherData"]["metrics"]["counters"] == {
            "client.rounds_worker": 1
        }
        for ev in trace["traceEvents"]:
            assert ev["ph"] in ("X", "M")
            assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int)
            if ev["ph"] == "X":
                assert isinstance(ev["ts"], float) and isinstance(ev["dur"], float)
                assert isinstance(ev["name"], str) and isinstance(ev["cat"], str)
            else:
                assert ev["name"] in ("process_name", "thread_name")
                assert isinstance(ev["args"]["name"], str)

    def test_microsecond_conversion_and_args(self):
        evs = [e for e in trace_events(self._traced().tracer) if e["ph"] == "X"]
        local = next(e for e in evs if e["name"] == "step")
        assert local["ts"] == pytest.approx(2.5)  # 2500 ns -> 2.5 us
        assert local["dur"] == pytest.approx(1.5)
        assert local["args"] == {"i": 0}

    def test_foreign_spans_get_their_own_pid_track(self):
        tel = self._traced()
        evs = tel.chrome_trace()["traceEvents"]
        pids = {e["pid"] for e in evs if e["ph"] == "X"}
        assert 777 in pids and len(pids) == 2
        procs = {
            e["args"]["name"]
            for e in evs if e["ph"] == "M" and e["name"] == "process_name"
        }
        assert procs == {"trainer", "graph-worker-0"}
        # rid rides through to the exported args: the correlation handle
        worker = next(e for e in evs if e["pid"] == 777 and e["ph"] == "X")
        assert worker["args"]["rid"] == 9

    def test_overflow_drop_counts_survive_export(self):
        """Ring overflow on a local thread and reported worker drops both
        surface in otherData.dropped_spans — a truncated trace must say
        so, not pretend it is complete."""
        tel = Telemetry(span_capacity=4)
        for i in range(10):
            tel.tracer.add_span(f"s{i}", "t", i, 1)
        tel.tracer.ingest("graph-worker-0", 777, [], dropped=5)
        trace = tel.chrome_trace()
        assert trace["otherData"]["dropped_spans"] == 6 + 5
        xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
        assert len(xs) == 4  # newest survive

    def test_marks_export_as_instant_events(self):
        tel = Telemetry()
        tel.tracer.mark("health.degraded", reason="worker 0 silent")
        [ev] = [e for e in tel.chrome_trace()["traceEvents"]
                if e["ph"] == "i"]
        assert ev["name"] == "health.degraded"
        assert ev["s"] == "p"  # process-scoped instant line in Perfetto
        assert ev["pid"] == tel.tracer.pid
        assert ev["args"] == {"reason": "worker 0 silent"}
        assert isinstance(ev["ts"], float)

    def test_disabled_run_emits_nothing(self):
        tel = Telemetry()  # never handed to anything
        trace = tel.chrome_trace()
        assert [e["ph"] for e in trace["traceEvents"]] == ["M"]
        assert trace["otherData"]["dropped_spans"] == 0
        assert trace["otherData"]["metrics"]["counters"] == {}

    def test_write_trace_roundtrip(self, tmp_path):
        path = str(tmp_path / "out.trace.json")
        assert self._traced().write_trace(path) == path
        with open(path) as f:
            trace = json.load(f)
        assert any(e["ph"] == "X" for e in trace["traceEvents"])

    def test_text_summary(self):
        tel = self._traced()
        text = tel.text_summary()
        assert "worker.sample" in text
        assert "graph-worker-0" in text
        assert "client.rounds_worker" in text


# ------------------------------------------------------ trainer (inproc)
@pytest.mark.quick
class TestTrainerTelemetry:
    def test_traced_prefetch_run(self, ds):
        tel = Telemetry()
        tr = make_trainer(ds, steps=6, prefetch_batches=2, telemetry=tel)
        res = tr.train()
        # telemetry alone must not switch attribution output on
        assert res.attribution is None
        tracks = tel.tracer.threads()
        assert len(tracks) >= 2  # step loop + prefetch producer
        names = {s[0] for _, _, spans, _ in tracks for s in spans}
        assert {"dispatch", "batch_wait", "sample"} <= names
        snap = tel.metrics.summary()
        assert "prefetch.queue_depth" in snap["gauges"]

    def test_telemetry_plus_attribution_keeps_schema(self, ds):
        tel = Telemetry()
        res = make_trainer(ds, steps=6, prefetch_batches=2, telemetry=tel,
                           attribution=True).train()
        a = res.attribution
        assert a is not None and a["steps"] == 6
        assert {"wall_s", "host_visible_s", "device_residual_s",
                "phases"} <= set(a)
        # the rebased PhaseTimer mirrors each phase into the tracer
        cats = {s[1] for _, _, spans, _ in tel.tracer.threads() for s in spans}
        assert "phase" in cats

    def test_disabled_by_default(self, ds):
        tr = make_trainer(ds, steps=4, prefetch_batches=2)
        assert tr.cfg.telemetry is None
        res = tr.train()
        assert len(res.losses) == 4

    @pytest.mark.parametrize("backend", ["host", "fused"])
    def test_prologue_spans_when_traced(self, ds, backend):
        """``prologue`` runs from the top of train() to the first dispatch;
        its children lie inside it on the step loop's thread."""
        tel = Telemetry()
        tr = make_trainer(ds, steps=4, prefetch_batches=2,
                          sampling_backend=backend, sparse_min_rows=0,
                          telemetry=tel)
        tr.train()
        (tid, _, spans, _), = [t for t in tel.tracer.threads()
                               if any(s[0] == "prologue" for s in t[2])]
        by = {}
        for name, _, t0, dur, _ in spans:
            by.setdefault(name, []).append((t0, t0 + dur))
        (p0, p1), = by["prologue"]
        children = ("prologue.params", "prologue.opt_init",
                    "prologue.batches", "prologue.memory")
        for c in children:
            (c0, c1), = by[c]
            assert p0 <= c0 and c1 <= p1, c
        assert p1 <= min(t0 for t0, _ in by["dispatch"])

    def test_setup_stages_are_spans_when_traced_at_construction(self, ds):
        tel = Telemetry()
        tr = make_trainer(ds, steps=2, sampling_backend="fused",
                          telemetry=tel)
        got = {s[0]: s[3] for _, _, spans, _ in tel.tracer.threads()
               for s in spans if s[1] == "setup"}
        assert set(got) == {"setup.fused_tables", "setup.train_pairs"}
        for name, dur in got.items():
            stage = name.split(".", 1)[1]
            assert dur * 1e-9 == pytest.approx(tr.setup_stages[stage])

    def test_off_run_counts_and_traces_nothing(self, ds, monkeypatch):
        """attribution off and no telemetry: no phase timer, no counter,
        no span, no attribution (the set-up stages are still kept)."""
        from repro.obs.metrics import Counter
        from repro.train import trainer as trainer_mod

        def forbidden(*a, **kw):
            raise AssertionError("instrumentation ran with telemetry off")

        monkeypatch.setattr(trainer_mod, "PhaseTimer", forbidden)
        monkeypatch.setattr(Counter, "inc", forbidden)
        monkeypatch.setattr(Tracer, "add_span", forbidden)
        for backend in ("host", "fused"):
            tr = make_trainer(ds, steps=4, prefetch_batches=2,
                              sampling_backend=backend, sparse_min_rows=0)
            res = tr.train()
            assert res.attribution is None
            assert len(res.losses) == 4
            assert {"engine", "train_pairs"} <= set(tr.setup_stages)


# ----------------------------------------------------------- mp pipeline
@pytest.mark.mp
@pytest.mark.usefixtures("watchdog")
class TestMpTelemetry:
    def test_traced_mp_run_correlates_processes(self, ds):
        """The acceptance trace: >= 3 processes (trainer + 2 workers) and
        >= 2 trainer threads on one timeline, worker serve spans joined to
        client rounds by rid."""
        tel = Telemetry()
        tr = make_trainer(
            ds, steps=8, engine_backend="mp", prefetch_batches=2,
            num_engine_workers=2, engine_local_threshold=0, telemetry=tel,
        )
        with tr:
            res = tr.train()
        assert len(res.losses) == 8
        evs = tel.chrome_trace()["traceEvents"]
        xs = [e for e in evs if e["ph"] == "X"]
        trainer_pid = tel.tracer.pid
        pids = {e["pid"] for e in xs}
        assert trainer_pid in pids and len(pids) >= 3
        trainer_tids = {e["tid"] for e in xs if e["pid"] == trainer_pid}
        assert len(trainer_tids) >= 2
        waits = {
            e["args"]["rid"] for e in xs
            if e["pid"] == trainer_pid and e["name"] == "client.wait"
        }
        served = {
            e["args"]["rid"] for e in xs
            if e["pid"] != trainer_pid and e["name"].startswith("worker.")
        }
        assert waits and served
        assert waits & served  # same rounds, seen from both sides
        # client-side round metrics were recorded too
        snap = tel.metrics.summary()
        assert snap["counters"]["client.rounds_worker"] > 0
        assert snap["histograms"]["client.round_latency_ns"]["count"] > 0

    def test_stats_conservation_on_both_reply_paths(self, ds):
        """shm_replies + pickle_replies == batches per worker, with both
        counters exercised: a tiny slab forces the pickle fallback for big
        rounds while small rounds still ride the slab."""
        rng = np.random.default_rng(7)
        big = rng.integers(0, ds.graph.num_nodes, size=200)
        small = rng.integers(0, ds.graph.num_nodes, size=10)
        inproc = DistributedGraphEngine(ds.graph, num_partitions=4)
        with GraphClient(ds.graph, num_partitions=4, num_workers=2,
                         slot_bytes=4096) as c:
            for i in range(4):
                # 200x50 int32 replies (40 kB) overflow the 4 kB slot ->
                # pickle fallback; the request ids still fit -> balanced
                # dispatch, not owner fan-out
                got = c.sample_neighbors(
                    np.random.default_rng(i), big, RELS[0], 50
                )
                ref = inproc.sample_neighbors(
                    np.random.default_rng(i), big, RELS[0], 50
                )
                np.testing.assert_array_equal(got, ref)
                c.sample_neighbors(np.random.default_rng(i), small, RELS[1], 2)
            per = c.worker_stats()
            assert len(per) == 2
            for s in per:
                assert s["shm_replies"] + s["pickle_replies"] == s["batches"]
            assert sum(s["pickle_replies"] for s in per) >= 4
            assert sum(s["shm_replies"] for s in per) >= 1

    def test_worker_error_carries_context(self, ds):
        with GraphClient(ds.graph, num_partitions=2, num_workers=1) as c:
            c.sample_neighbors(np.random.default_rng(0), np.arange(8), RELS[0], 2)
            with pytest.raises(EngineWorkerError, match="KeyError") as ei:
                c.sample_neighbors(
                    np.random.default_rng(0), np.arange(8), "no2such2rel", 2
                )
        err = ei.value
        assert err.worker_id == 0
        assert isinstance(err.rid, int)
        assert err.stats is not None and err.stats["batches"] >= 1
        assert "stats at failure" in str(err)
