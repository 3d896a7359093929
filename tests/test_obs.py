"""Observability layer (PR 10): the FREE-and-INVARIANT contract.

The load-bearing assertions: threading a ``TraceRecorder`` through
``DecodeEngine(trace=...)`` leaves token streams BITWISE identical,
``EngineStats`` identical, and ``compile_counts()`` identical to the
untraced run — over clean, faulty, speculative and preemptive
schedules — and every recorded event is built from host scalars only
(JSON-serializable without any numpy/jax coercion), which is the
observable face of the zero-device-fetch guarantee.

Plus the plumbing underneath: ring bounding/overflow accounting,
histogram bucket edges, exporter round-trips (JSONL, Chrome
trace_event, Prometheus text, JSON), derived lifecycle latencies, and
the adapter-cache spill/reload event hook.

The engine's profiler spans (``repro.obs.span``) are held to the same
contract under ``jax.profiler``, and read back from the profile's host
plane: one ``engine.tick`` per tick, one dispatch/fetch/sample triple per
decode executable run, and ``request_id`` args that join the recorder's.
"""
from __future__ import annotations

import glob
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import AdapterStateCache, DoRAConfig
from repro.launch.engine import FINISH_REASONS, DecodeEngine
from repro.launch.steps import StepConfig
from repro.launch.train import build_state
from repro.obs import (AUX_EVENTS, EVENT_NAMES, LIFECYCLE_EVENTS,
                       SECONDS_BUCKETS, TICK_BUCKETS, Counter, Gauge,
                       Histogram, MetricsRegistry, TraceRecorder,
                       engine_metrics, latency_metrics,
                       lifecycle_latencies, monotonic, parse_prometheus,
                       percentile)

DCFG = DoRAConfig(rank=4, alpha=8.0, mode="eager")
ARCH = "qwen2-7b"
ML = 14


class _FakeClock:
    """Deterministic monotone clock for exporter/latency tests."""

    def __init__(self, dt: float = 0.5):
        self.t = 0.0
        self.dt = dt

    def __call__(self) -> float:
        self.t += self.dt
        return self.t


# ---------------------------------------------------------------------------
# Ring buffer
# ---------------------------------------------------------------------------

class TestRing:
    def test_bounding_and_overflow_accounting(self):
        rec = TraceRecorder(capacity=4, clock=_FakeClock())
        for i in range(10):
            rec.emit("token", tick=i, request_id=0, token=i)
        assert len(rec) == 4
        assert rec.emitted == 10
        assert rec.dropped == 6
        # oldest dropped first: the survivors are the LAST four
        assert [e.tick for e in rec] == [6, 7, 8, 9]

    def test_capacity_must_be_positive(self):
        with pytest.raises(ValueError):
            TraceRecorder(capacity=0)

    def test_filters_and_request_ids(self):
        rec = TraceRecorder(clock=_FakeClock())
        rec.emit("submitted", tick=0, request_id=1)
        rec.emit("submitted", tick=0, request_id=2)
        rec.emit("terminal", tick=3, request_id=1, reason="length")
        rec.emit("fault", tick=2, kind="nan")
        assert rec.request_ids() == [1, 2]
        assert len(rec.events("submitted")) == 2
        assert len(rec.events(request_id=1)) == 2
        assert rec.events("terminal", request_id=1)[0].data["reason"] \
            == "length"
        assert rec.events("terminal", request_id=2) == []

    def test_t_wall_is_monotone(self):
        rec = TraceRecorder(clock=_FakeClock())
        for i in range(5):
            rec.emit("token", tick=i)
        ws = [e.t_wall for e in rec]
        assert ws == sorted(ws) and ws[0] >= 0.0

    def test_taxonomy_is_closed(self):
        # terminal's reason field mirrors the engine's finish reasons —
        # the docs table is generated from these tuples.
        assert set(LIFECYCLE_EVENTS) & set(AUX_EVENTS) == set()
        assert EVENT_NAMES == LIFECYCLE_EVENTS + AUX_EVENTS
        assert "terminal" in LIFECYCLE_EVENTS
        assert len(FINISH_REASONS) == 6


# ---------------------------------------------------------------------------
# Metrics primitives
# ---------------------------------------------------------------------------

class TestPrimitives:
    def test_histogram_bucket_edges_are_inclusive_upper(self):
        h = Histogram(buckets=(1, 2, 4))
        for v in (1, 1.5, 4, 5):
            h.observe(v)
        assert h.cumulative() == [(1.0, 1), (2.0, 2), (4.0, 3),
                                  (math.inf, 4)]
        assert h.count == 4 and h.sum == pytest.approx(11.5)

    def test_histogram_rejects_unsorted_or_empty_edges(self):
        with pytest.raises(ValueError):
            Histogram(buckets=(2, 1))
        with pytest.raises(ValueError):
            Histogram(buckets=())

    def test_counter_rejects_negative(self):
        c = Counter()
        c.inc(2)
        with pytest.raises(ValueError):
            c.inc(-1)
        assert c.value == 2

    def test_percentile_nearest_rank(self):
        xs = [1, 2, 3, 4]
        assert percentile(xs, 50) == 2
        assert percentile(xs, 100) == 4
        assert percentile(xs, 0) == 1
        assert percentile([], 50) == 0.0
        with pytest.raises(ValueError):
            percentile(xs, 101)

    def test_registry_kind_collision(self):
        reg = MetricsRegistry()
        reg.counter("x")
        with pytest.raises(ValueError):
            reg.gauge("x")

    def test_registry_labels_are_distinct_samples(self):
        reg = MetricsRegistry()
        reg.counter("n", labels={"k": "a"}).inc(1)
        reg.counter("n", labels={"k": "b"}).inc(2)
        assert reg.counter("n", labels={"k": "a"}).value == 1
        assert reg.counter("n", labels={"k": "b"}).value == 2


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

class TestExporters:
    def _registry(self):
        reg = MetricsRegistry()
        reg.counter("reqs_total", "requests").inc(7)
        reg.gauge("occupancy", "busy slots").set(1.5)
        h = reg.histogram("wait_ticks", "queue wait",
                          buckets=(1, 2, 4))
        for v in (1, 3, 9):
            h.observe(v)
        reg.counter("finished_total", labels={"reason": "eos"}).inc(2)
        return reg

    def test_prometheus_round_trip(self, tmp_path):
        reg = self._registry()
        path = str(tmp_path / "m.prom")
        text = reg.to_prometheus(path)
        assert open(path).read() == text
        parsed = parse_prometheus(text)
        assert parsed["repro_reqs_total"] == 7
        assert parsed["repro_occupancy"] == 1.5
        assert parsed['repro_finished_total{reason="eos"}'] == 2
        assert parsed['repro_wait_ticks_bucket{le="1"}'] == 1
        assert parsed['repro_wait_ticks_bucket{le="4"}'] == 2
        assert parsed['repro_wait_ticks_bucket{le="+Inf"}'] == 3
        assert parsed["repro_wait_ticks_sum"] == 13
        assert parsed["repro_wait_ticks_count"] == 3
        # HELP/TYPE lines present (text exposition v0.0.4)
        assert "# TYPE repro_wait_ticks histogram" in text
        assert "# HELP repro_reqs_total requests" in text

    def test_json_snapshot(self, tmp_path):
        reg = self._registry()
        path = str(tmp_path / "m.json")
        snap = reg.to_json(path)
        assert json.load(open(path)) == json.loads(json.dumps(snap))
        assert snap["reqs_total"]["kind"] == "counter"
        assert snap["wait_ticks"]["samples"][0]["count"] == 3
        assert snap["wait_ticks"]["samples"][0]["buckets"][-1] == \
            ["inf", 3]

    def test_jsonl_round_trip(self, tmp_path):
        rec = TraceRecorder(clock=_FakeClock())
        rec.emit("submitted", tick=0, request_id=0, prompt_len=5)
        rec.emit("terminal", tick=4, request_id=0, slot=1,
                 reason="length", n_tokens=4)
        path = str(tmp_path / "t.jsonl")
        text = rec.to_jsonl(path)
        assert open(path).read() == text
        parsed = [json.loads(line) for line in text.splitlines()]
        assert parsed == [e.as_dict() for e in rec]
        assert parsed[1]["data"]["reason"] == "length"

    def test_chrome_trace_spans(self, tmp_path):
        # one full lifecycle with a preemption, as the engine emits it:
        # every seating emits "admitted"; re-admissions add "resumed".
        rec = TraceRecorder(clock=_FakeClock())
        rec.emit("submitted", tick=0, request_id=0, prompt_len=5)
        rec.emit("queued", tick=0, request_id=0, depth=1)
        rec.emit("admitted", tick=0, request_id=0, slot=0, prompt_len=5)
        rec.emit("first_token", tick=1, request_id=0, slot=0, token=7)
        rec.emit("token", tick=2, request_id=0, slot=0, token=9)
        rec.emit("preempted", tick=3, request_id=0, slot=0,
                 n_generated=2)
        rec.emit("admitted", tick=5, request_id=0, slot=1, prompt_len=7)
        rec.emit("resumed", tick=5, request_id=0, slot=1, attempt=1)
        rec.emit("terminal", tick=7, request_id=0, slot=1,
                 reason="length", n_tokens=4)
        rec.emit("fault", tick=6, kind="nan")
        path = str(tmp_path / "t.json")
        doc = rec.to_chrome_trace(path)
        assert json.load(open(path)) == json.loads(json.dumps(doc))
        evs = doc["traceEvents"]
        assert all(e["ph"] in ("M", "X", "i") for e in evs)
        spans = [e for e in evs if e["ph"] == "X"]
        assert all(e["dur"] >= 0.0 for e in spans)
        # two queue-wait spans (initial + post-preemption re-queue) and
        # two residency spans (slot 0 then slot 1)
        queue = [e for e in spans if e["name"].startswith("queued")]
        resid = [e for e in spans if e["name"] == "r0"]
        assert len(queue) == 2 and len(resid) == 2
        assert sorted(e["tid"] for e in resid) == [0, 1]
        assert queue[0]["args"]["ticks"] == 0
        assert queue[1]["args"]["ticks"] == 2     # preempted@3 -> admitted@5
        # the fault instant lands on the engine track (above all slots)
        inst = [e for e in evs if e["ph"] == "i" and e["name"] == "fault"]
        assert inst and inst[0]["tid"] > max(e["tid"] for e in resid)
        assert doc["otherData"]["emitted"] == 10

    def test_chrome_trace_closes_open_spans(self):
        rec = TraceRecorder(clock=_FakeClock())
        rec.emit("submitted", tick=0, request_id=0)
        rec.emit("admitted", tick=0, request_id=0, slot=0)
        rec.emit("token", tick=1, request_id=0, slot=0, token=3)
        doc = rec.to_chrome_trace()
        open_spans = [e for e in doc["traceEvents"]
                      if e["ph"] == "X" and e["name"].endswith("(open)")]
        assert len(open_spans) == 1 and open_spans[0]["dur"] >= 0.0


# ---------------------------------------------------------------------------
# Derived latencies
# ---------------------------------------------------------------------------

class TestLatencies:
    def _rec(self):
        rec = TraceRecorder(clock=_FakeClock(dt=1.0))
        rec.emit("submitted", tick=0, request_id=0)
        rec.emit("admitted", tick=2, request_id=0, slot=0)
        rec.emit("first_token", tick=3, request_id=0, slot=0, token=1)
        rec.emit("token", tick=4, request_id=0, slot=0, token=2)
        rec.emit("token", tick=6, request_id=0, slot=0, token=3)
        rec.emit("terminal", tick=6, request_id=0, slot=0,
                 reason="length", n_tokens=3)
        # a queued-timeout request: submitted but never admitted
        rec.emit("submitted", tick=1, request_id=1)
        rec.emit("terminal", tick=5, request_id=1, reason="timeout",
                 queued=True)
        return rec

    def test_tick_domain_deltas(self):
        lat = lifecycle_latencies(self._rec())
        r0 = lat[0]
        assert r0["queue_wait_ticks"] == 2
        assert r0["ttft_ticks"] == 3
        assert r0["admit_to_retire_ticks"] == 4
        assert r0["itl_ticks"] == [1, 2]
        assert r0["reason"] == "length"
        # wall deltas exist and are positive (fake clock: 1s/event)
        assert r0["ttft_s"] == pytest.approx(2.0)
        r1 = lat[1]
        assert r1["admitted_tick"] is None
        assert r1["queue_wait_ticks"] is None
        assert r1["ttft_ticks"] is None and r1["itl_ticks"] == []
        assert r1["reason"] == "timeout"

    def test_latency_metrics_fill(self):
        reg = latency_metrics(self._rec())
        text = reg.to_prometheus()
        parsed = parse_prometheus(text)
        assert parsed["repro_ttft_ticks_count"] == 1
        assert parsed["repro_itl_ticks_count"] == 2
        assert parsed['repro_requests_finished_total{reason="length"}'] \
            == 1
        assert parsed['repro_requests_finished_total{reason="timeout"}'] \
            == 1
        assert parsed["repro_trace_events_emitted_total"] == 8
        assert parsed["repro_trace_events_dropped_total"] == 0


# ---------------------------------------------------------------------------
# Engine integration: the invariance contract
# ---------------------------------------------------------------------------

_REQS = [(5, 4), (6, 5), (4, 3), (5, 4)]     # (prompt_len, budget)


def _setup():
    mcfg = get_config(ARCH, smoke=True)
    scfg = StepConfig(dora=DCFG)
    params, _, _ = build_state(mcfg, DCFG, 0)
    cache = AdapterStateCache.for_serving(mcfg, scfg)
    _, ad, _ = build_state(mcfg, DCFG, 10)
    cache.register("t0", ad)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, mcfg.vocab_size, P, dtype=np.int32)
               for P, _ in _REQS]
    return mcfg, scfg, params, cache, prompts


def _drive(trace=None, *, plan=None, deadline=None, speculative_k=0,
           paged=False, dynamic_grouping=False):
    mcfg, scfg, params, cache, prompts = _setup()
    eng = DecodeEngine(mcfg, scfg, params, slots=2, max_len=ML,
                       adapter_cache=cache, fault_plan=plan,
                       speculative_k=speculative_k, paged=paged,
                       dynamic_grouping=dynamic_grouping, trace=trace)
    for i, (p, (_, g)) in enumerate(zip(prompts, _REQS)):
        eng.submit(p, adapter="t0", max_new_tokens=g, key_id=i,
                   deadline_ticks=deadline if i == 3 else None)
    return eng.run(), eng


def _streams(results):
    return {r.request_id: (tuple(int(t) for t in r.tokens),
                           r.finish_reason) for r in results}


class TestInvariance:
    """ACCEPTANCE: tracing on == tracing off, bitwise."""

    @pytest.mark.parametrize("variant", ["clean", "faulty", "spec"])
    def test_tracing_changes_nothing(self, variant):
        from repro.launch.faults import FaultPlan
        kw = {}
        if variant == "faulty":
            kw = dict(plan=FaultPlan.parse("nan@3"), deadline=3)
        elif variant == "spec":
            kw = dict(speculative_k=2)
        off_res, off_eng = _drive(None, **kw)
        rec = TraceRecorder()
        on_res, on_eng = _drive(rec, **kw)
        assert _streams(on_res) == _streams(off_res)
        assert on_eng.stats().as_dict() == off_eng.stats().as_dict()
        assert on_eng.compile_counts() == off_eng.compile_counts()
        assert len(rec) > 0 and rec.dropped == 0

    def test_events_are_host_scalars_only(self):
        """The zero-device-fetch face: every recorded field must already
        be a host scalar — json.dumps with no default= coercion proves
        no numpy/jax value ever reached the emit path."""
        rec = TraceRecorder()
        _drive(rec, speculative_k=2)
        for e in rec:
            json.dumps(e.as_dict())        # raises on np.*/jax.Array
            assert e.name in EVENT_NAMES, e


class TestLifecycleEvents:
    def test_conservation_and_order(self):
        rec = TraceRecorder()
        results, _ = _drive(rec)
        assert rec.request_ids() == [0, 1, 2, 3]
        for rid in rec.request_ids():
            evs = rec.events(request_id=rid)
            # exactly one submitted and one terminal per request
            assert sum(e.name == "submitted" for e in evs) == 1
            assert sum(e.name == "terminal" for e in evs) == 1
            assert evs[0].name == "submitted"
            assert evs[-1].name == "terminal"
            assert evs[-1].data["reason"] in FINISH_REASONS
            # ticks monotone along each request's own event sequence
            ticks = [e.tick for e in evs]
            assert ticks == sorted(ticks), (rid, ticks)
            # exactly one first_token, before any plain token
            names = [e.name for e in evs]
            assert names.count("first_token") == 1
            assert "token" not in names[:names.index("first_token")]
        # token events tally with the engine's own accounting
        for r in results:
            n_tok = len(rec.events("first_token", r.request_id)) \
                + len(rec.events("token", r.request_id))
            assert n_tok == len(r.tokens)

    def test_preemption_emits_preempt_resume_pair(self):
        mcfg, scfg, params, cache, prompts = _setup()
        rec = TraceRecorder()
        eng = DecodeEngine(mcfg, scfg, params, slots=1, max_len=ML,
                           adapter_cache=cache, trace=rec)
        eng.submit(prompts[0], adapter="t0", max_new_tokens=8)
        for _ in range(2):
            eng.step()
        eng.submit(prompts[1][:4], adapter="t0", max_new_tokens=2,
                   priority=5)
        results = {r.request_id: r for r in eng.run()}
        assert results[0].preempted == 1
        pre = rec.events("preempted", 0)
        res = rec.events("resumed", 0)
        assert len(pre) == 1 and len(res) == 1
        assert res[0].data["attempt"] == 1
        assert pre[0].tick <= res[0].tick
        # the victim re-seats: two admitted events, one per residency
        assert len(rec.events("admitted", 0)) == 2
        # the timeline stays well-formed through the preemption
        doc = rec.to_chrome_trace()
        r0_spans = [e for e in doc["traceEvents"]
                    if e["ph"] == "X" and e["name"] == "r0"]
        assert len(r0_spans) == 2

    def test_quarantine_trace_sequence(self):
        from repro.launch.faults import FaultPlan
        rec = TraceRecorder()
        results, eng = _drive(rec, plan=FaultPlan.parse("nan@3"))
        poisoned = [r.request_id for r in results
                    if r.finish_reason == "error_numeric"]
        assert poisoned, "nan@3 quarantined nothing"
        assert len(rec.events("fault")) == 1
        assert rec.events("fault")[0].data["kind"] == "nan"
        for rid in poisoned:
            q = rec.events("quarantined", rid)
            t = rec.events("terminal", rid)
            assert len(q) == 1 and len(t) == 1
            assert t[0].data["reason"] == "error_numeric"
            assert q[0].tick == t[0].tick

    def test_chunk_prefill_events_cover_the_prompt(self):
        rec = TraceRecorder()
        results, eng = _drive(rec, paged=True)
        assert _streams(results) == _streams(_drive(None, paged=True)[0])
        for rid, (P, _) in enumerate(_REQS):
            chunks = rec.events("chunk_prefill", rid)
            assert chunks, f"r{rid}: no chunk events"
            assert sum(c.data["chunk_len"] for c in chunks) == P
            assert chunks[-1].data["final"] is True
            assert all(not c.data["final"] for c in chunks[:-1])


# ---------------------------------------------------------------------------
# Profiler spans: the same contract with jax.profiler on
# ---------------------------------------------------------------------------

# Every path the engine serves by, each through the fleet stack so that
# ``engine.stack_insert`` fires on all three.
_SPAN_PATHS = {
    "paged": dict(paged=True, dynamic_grouping=True),
    "rect": dict(dynamic_grouping=True),
    "spec": dict(speculative_k=2, dynamic_grouping=True),
}


def _engine_spans(path: str) -> list[tuple[str, int, int, dict]]:
    """(name, start ns, end ns, args) of every ``engine.*`` event on the
    host plane of the profile under ``path``, parents before children."""
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(files) == 1, files
    pd = jax.profiler.ProfileData.from_file(files[0])
    out = [(ev.name, int(ev.start_ns), int(ev.end_ns), dict(ev.stats))
           for plane in pd.planes if plane.name == "/host:CPU"
           for line in plane.lines for ev in line.events
           if ev.name.startswith("engine.")]
    return sorted(out, key=lambda e: (e[1], -e[2]))


@pytest.fixture(scope="module", params=sorted(_SPAN_PATHS))
def profiled(request, tmp_path_factory):
    """One path served untraced, then with a TraceRecorder under
    ``jax.profiler.trace``: (path name, untraced results, untraced engine,
    traced results, traced engine, recorder, engine spans)."""
    kw = _SPAN_PATHS[request.param]
    off_res, off_eng = _drive(None, **kw)
    rec = TraceRecorder()
    d = tmp_path_factory.mktemp(f"spans_{request.param}")
    with jax.profiler.trace(str(d)):
        on_res, on_eng = _drive(rec, **kw)
    return (request.param, off_res, off_eng, on_res, on_eng, rec,
            _engine_spans(str(d)))


def _ticks(spans):
    """Each ``engine.tick`` span with the spans it holds, in start order."""
    return [(t, [s for s in spans if s is not t and t[1] <= s[1]
                 and s[2] <= t[2]])
            for t in spans if t[0] == "engine.tick"]


class TestProfilerSpans:
    def test_profiling_changes_nothing(self, profiled):
        _, off_res, off_eng, on_res, on_eng, _, spans = profiled
        assert spans, "the profile holds no engine span"
        assert _streams(on_res) == _streams(off_res)
        assert on_eng.stats().as_dict() == off_eng.stats().as_dict()
        assert on_eng.compile_counts() == off_eng.compile_counts()

    def test_one_tick_span_per_tick_and_one_triple_per_decode(
            self, profiled):
        path_name, _, _, _, eng, _, spans = profiled
        st = eng.stats()
        ticks = _ticks(spans)
        assert [t[3]["tick"] for t, _ in ticks] == list(range(st.steps))
        # every other span lies inside exactly one tick
        held = sorted(s for _, inner in ticks for s in inner)
        assert held == sorted(s for s in spans if s[0] != "engine.tick")
        names = [s[0] for s in spans]
        runs = st.decode_steps + st.draft_steps + st.verify_steps
        assert names.count("engine.decode") == runs
        # one read-back and one sample per decode run and per completed
        # admission (its first token)
        assert names.count("engine.fetch") == runs + st.prefills
        assert names.count("engine.sample") == runs + st.prefills
        decode_ticks = [(t, inner) for t, inner in ticks
                        if any(s[0] == "engine.decode" for s in inner)]
        assert len(decode_ticks) == st.decode_steps + st.verify_steps
        k = _SPAN_PATHS[path_name].get("speculative_k", 0)
        for t, inner in decode_ticks:
            seq = [s[0] for s in inner]
            tail = seq[seq.index("engine.decode"):]
            n = tail.count("engine.decode")
            assert n in ((1, k + 1) if k else (1,)), (t, seq)
            assert tail == ["engine.decode", "engine.fetch",
                            "engine.sample"] * n + ["engine.deliver"], seq
            rows = {s[3]["rows"] for s in inner if s[0] == "engine.decode"}
            assert rows and min(rows) >= 1

    def test_request_ids_join_the_recorder(self, profiled):
        path_name, _, _, _, eng, rec, spans = profiled
        chunks = [(s[3]["request_id"], s[3]["slot"], s[3]["start"],
                   s[3]["tokens"]) for s in spans if s[0] == "engine.chunk"]
        assert chunks == [(e.request_id, e.slot, e.data["start"],
                           e.data["chunk_len"])
                          for e in rec.events("chunk_prefill")]
        assert bool(chunks) == _SPAN_PATHS[path_name].get("paged", False)
        inserts = [(s[3]["request_id"], s[3]["slot"]) for s in spans
                   if s[0] == "engine.stack_insert"]
        assert len(inserts) == eng.stats().stack_inserts >= 1
        admitted = {(e.request_id, e.slot) for e in rec.events("admitted")}
        assert set(inserts) <= admitted


class TestCacheEvents:
    def test_traced_engine_claims_the_hook(self):
        mcfg, scfg, params, cache, _ = _setup()
        assert cache.on_event is None
        rec = TraceRecorder()
        eng = DecodeEngine(mcfg, scfg, params, slots=2, max_len=ML,
                           adapter_cache=cache, trace=rec)
        hook = cache.on_event
        assert hook is not None
        DecodeEngine(mcfg, scfg, params, slots=2, max_len=ML,
                     adapter_cache=cache)
        assert cache.on_event is hook, \
            "an untraced engine must not strip another engine's hook"
        del eng

    def test_spill_reload_emit_events(self):
        """Unit-level: drive the tiered cache through a spill and a
        reload with the hook wired straight to a recorder."""
        from repro.core import init_dora_params, precompute_adapter_state
        d_out, d_in = 16, 12

        def pre(params, adapters):
            return precompute_adapter_state(params, adapters, DCFG,
                                            act_dtype=jnp.float32,
                                            fold_gsb=True)

        def tenant(seed):
            key = jax.random.PRNGKey(seed)
            W = jax.random.normal(key, (d_out, d_in), jnp.float32)
            return init_dora_params(jax.random.fold_in(key, 1), W, DCFG)

        W = jax.random.normal(jax.random.PRNGKey(9), (d_out, d_in),
                              jnp.float32)
        state_bytes = 4 * (DCFG.rank * d_in + d_out * DCFG.rank + d_out
                           + d_out + d_out * DCFG.rank)
        cache = AdapterStateCache(pre, act_dtype=jnp.float32,
                                  fold_gsb=True, max_bytes=state_bytes,
                                  host_max_bytes=10 * state_bytes)
        rec = TraceRecorder(clock=_FakeClock())
        cache.on_event = lambda kind, key: rec.emit(
            kind, tick=0, adapter=key.adapter_id, version=key.version)
        hs = [cache.register(f"t{i}", tenant(i)) for i in range(2)]
        cache.get_state(W, hs[0])
        cache.get_state(W, hs[1])          # evicts + spills t0
        cache.get_state(W, hs[0])          # reloads t0 (spills t1)
        spills = rec.events("spill")
        reloads = rec.events("reload")
        assert [e.data["adapter"] for e in spills] == ["t0", "t1"]
        assert [e.data["adapter"] for e in reloads] == ["t0"]
        st = cache.stats()
        assert st.spills == len(spills) and st.reloads == len(reloads)


class TestEngineMetrics:
    def test_snapshot_wraps_all_stat_surfaces(self):
        rec = TraceRecorder()
        results, eng = _drive(rec, paged=True)
        reg = engine_metrics(eng, rec)
        parsed = parse_prometheus(reg.to_prometheus())
        st = eng.stats()
        assert parsed["repro_engine_retired_total"] == st.retired
        assert parsed["repro_engine_slots"] == 2
        assert parsed["repro_engine_generated_tokens_total"] == \
            st.generated_tokens
        assert parsed["repro_engine_mean_occupancy"] == \
            pytest.approx(st.mean_occupancy)
        assert parsed["repro_adapter_cache_entries"] == 1
        # compile counts carried as labelled counters
        assert parsed['repro_compiles_total{fn="prefill_chunk",sig=""}'] \
            == eng.compile_counts()["prefill_chunk"]
        # paged pool gauges present (pool drained after run)
        assert parsed["repro_pool_used_blocks"] == 0
        assert parsed['repro_pool_slot_blocks{slot="0"}'] == 0
        # derived latency histograms folded in from the trace
        assert parsed["repro_ttft_ticks_count"] == len(results)
        assert parsed['repro_requests_finished_total{reason="length"}'] \
            == len(results)

    def test_snapshot_is_json_exportable(self, tmp_path):
        rec = TraceRecorder()
        _, eng = _drive(rec)
        path = str(tmp_path / "m.json")
        snap = engine_metrics(eng, rec).to_json(path)
        assert json.load(open(path)) == json.loads(json.dumps(snap))
        assert snap["engine_retired_total"]["samples"][0]["value"] == 4

    def test_monotonic_clock_is_perf_counter(self):
        import time
        assert monotonic is time.perf_counter
        a, b = monotonic(), monotonic()
        assert b >= a
