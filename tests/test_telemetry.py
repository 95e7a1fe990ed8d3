"""Serving telemetry subsystem (ISSUE 5): metrics registry accuracy,
span-tracer invariants, Chrome-trace export schema, and the zero-
behavior-change guarantee.

The load-bearing test is the differential: the tier-1 serving anchor
workload must emit **bit-identical** tokens with telemetry fully on
(registry + tracer) vs off — telemetry is host-side observation only.
The export round-trip runs the oversubscribed swap/preemption workload
and checks the trace carries at least one preempt/resume pair plus the
evict/fault engine spans (the ISSUE acceptance trace).
"""
import importlib.util
import json
import math
import os
import re

import numpy as np
import jax
import pytest

from repro.configs import get, smoke_variant
from repro.models import model as M
from repro.runtime.monitor import KVCacheMonitor, StragglerMonitor
from repro.runtime.tracing import (ENGINE_TRACK, RequestStateTracker,
                                   SpanTracer, request_track, span)
from repro.runtime.trace_export import (build_trace, export_chrome_trace,
                                        validate_chrome_trace)
from repro.serving import EngineConfig, GenerationEngine, Request
from repro.serving.telemetry import (Counter, Gauge, Histogram,
                                     MetricsRegistry, Telemetry,
                                     geometric_edges, linear_edges,
                                     serving_report_line)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------------
# metrics registry
# --------------------------------------------------------------------------

def test_histogram_percentiles_match_numpy_linear_buckets():
    """With buckets much finer than the sample spacing, interpolated
    percentiles track numpy's to within a couple of bucket widths."""
    rng = np.random.default_rng(7)
    xs = rng.uniform(0.0, 1.0, size=2_000)
    h = Histogram("t", edges=linear_edges(0.0, 1.0, 500))
    for x in xs:
        h.observe(float(x))
    for q in (0.5, 0.95, 0.99):
        assert h.percentile(q) == pytest.approx(
            float(np.quantile(xs, q)), abs=3 * (1.0 / 500)), q
    assert h.mean == pytest.approx(float(xs.mean()), rel=1e-9)
    assert h.min == xs.min() and h.max == xs.max()
    assert h.count == len(xs)


def test_histogram_percentiles_geometric_default_relative_error():
    """The default serving buckets (geometric, factor 1.2) keep the
    quantile estimate within the documented ~20% relative error."""
    rng = np.random.default_rng(3)
    xs = np.exp(rng.normal(-4.0, 1.0, size=5_000))     # lognormal seconds
    h = Histogram("t")                                  # default edges
    for x in xs:
        h.observe(float(x))
    for q in (0.5, 0.95, 0.99):
        ref = float(np.quantile(xs, q))
        assert abs(h.percentile(q) - ref) / ref < 0.25, q


def test_histogram_edge_cases():
    h = Histogram("t", edges=[1.0, 2.0])
    assert math.isnan(h.percentile(0.5)) and math.isnan(h.mean)
    h.observe(5.0)                       # overflow bucket, single sample
    assert h.percentile(0.5) == 5.0 == h.percentile(0.99)
    h.observe(0.25)                      # underflow bucket
    assert h.percentile(0.0) >= h.min
    assert h.min <= h.percentile(0.5) <= h.max
    with pytest.raises(ValueError):
        Histogram("bad", edges=[2.0, 1.0])
    assert geometric_edges(1e-5, 60.0)[0] == 1e-5


def test_registry_get_or_create_and_kind_conflicts():
    reg = MetricsRegistry()
    c = reg.counter("a_total", unit="tok")
    c.inc(3)
    assert reg.counter("a_total") is c and reg.value("a_total") == 3
    g = reg.gauge("depth")
    g.set(5.0)
    g.set(2.0)
    assert g.value == 2.0 and g.peak == 5.0
    with pytest.raises(TypeError):
        reg.gauge("a_total")             # name bound to a counter
    assert "a_total" in reg and reg.get("missing") is None
    snap = reg.snapshot()
    assert snap["a_total"]["value"] == 3
    assert snap["depth"]["peak"] == 5.0
    json.dumps(snap)                     # JSON-safe by contract
    assert serving_report_line(reg).startswith("tok=")


# --------------------------------------------------------------------------
# span tracer
# --------------------------------------------------------------------------

def test_tracer_bounded_buffer_drops_instead_of_growing():
    tr = SpanTracer(capacity=4)
    for i in range(10):
        tr.instant("engine", f"e{i}")
    assert len(tr) == 4 and tr.n_dropped == 6
    trace = build_trace(tr)
    assert trace["otherData"]["n_dropped_events"] == 6


def test_request_state_tracker_invariants():
    """State spans on one request track are back-to-back (never
    overlapping) and every open state closes on finish."""
    t = [0.0]
    tr = SpanTracer(clock=lambda: t[0])
    rs = RequestStateTracker(tr)
    for rid in (1, 2):
        rs.transition(rid, "queued")
    t[0] = 1.0
    rs.transition(1, "prefilling")
    t[0] = 2.0
    rs.transition(1, "decoding")
    assert rs.open_states == {1: "decoding", 2: "queued"}
    t[0] = 3.0
    rs.finish(1)
    rs.finish(2)
    assert rs.open_states == {}
    spans = [(name, track, ts, dur) for ph, cat, name, track, ts, dur, _
             in tr.events if ph == "X"]
    per_track: dict = {}
    for name, track, ts, dur in spans:
        per_track.setdefault(track, []).append((ts, ts + dur, name))
    for track, ivs in per_track.items():
        ivs.sort()
        for (s0, e0, _), (s1, _, _) in zip(ivs, ivs[1:]):
            assert s1 >= e0, (track, ivs)       # no overlap
    assert [n for _, _, n in sorted(per_track[request_track(1)])] == \
        ["queued", "prefilling", "decoding"]


def test_tracer_span_context_manager_and_counters():
    tr = SpanTracer()
    with tr.span("engine", "decode_step", args={"step": 1}):
        pass
    tr.counter("serving_queue_depth", 3)
    (ph, cat, name, track, ts, dur, args) = tr.events[0]
    assert (ph, cat, name, track) == ("X", "engine", "decode_step",
                                      ENGINE_TRACK)
    assert dur >= 0 and args == {"step": 1}
    assert tr.events[1][0] == "C" and tr.events[1][6] == 3.0


# --------------------------------------------------------------------------
# monitors (satellite fixes)
# --------------------------------------------------------------------------

def test_straggler_monitor_zero_first_sample_seeds_ewma():
    """A legitimate 0.0-second first sample must seed the EWMA (the old
    ``_ewma = 0.0`` sentinel treated it as uninitialized and let the
    next sample overwrite it wholesale)."""
    m = StragglerMonitor(ewma_alpha=0.05)
    assert m.ewma_seconds == 0.0         # no samples yet
    m.observe(0.0, step=0)
    m.observe(1.0, step=1)
    assert m.ewma_seconds == pytest.approx(0.05)    # not 1.0
    # outlier detection still works through observe()
    for i in range(20):
        m.observe(0.01, step=i + 2)
    stats = m.observe(10.0, step=99)
    assert stats.is_straggler and m.alarms[-1].step == 99


def test_kvcache_monitor_mixed_engines_no_keyerror():
    """One monitor shared across engines with different capabilities
    (with/without swap tier, with/without chunked prefill) summarizes
    what it saw instead of raising KeyError."""
    mon = KVCacheMonitor()
    mon.record({"pages_in_use": 4, "cold_pages_in_use": 1,
                "page_bytes": 100, "cache_bytes_paged": 500,
                "cache_bytes_raw_equiv": 600, "monolithic_bytes": 1000,
                "cold_bytes_ragged": 60,
                "pages_in_use_per_shard": [3, 1]})
    s = mon.summary()                    # no swap keys ever recorded
    assert s["steps"] == 1 and "peak_swap_bytes" not in s
    assert s["peak_pages_in_use"] == 5
    mon.record({"pages_in_use": 2, "swap_bytes_used": 7,
                "swap_out_bytes_total": 7, "swap_in_bytes_total": 0,
                "n_preempted": 1, "n_resumed": 0,
                "pages_in_use_per_shard": [1, 4]})
    s = mon.summary()                    # swap section appears, defaulted
    assert s["peak_swap_bytes"] == 7 and s["n_preempted"] == 1
    assert mon.peak_per_shard() == [3, 4]
    assert mon.n_samples == 2
    assert KVCacheMonitor().summary() == {}     # empty monitor


# --------------------------------------------------------------------------
# engine integration: bit-identity, compile counters, export round-trip
# --------------------------------------------------------------------------

def _anchor_requests():
    return [Request(prompt=[1, 2, 3, 4], max_new_tokens=5, id=9_100),
            Request(prompt=[5, 6, 7], max_new_tokens=6, id=9_101),
            Request(prompt=[9, 10], max_new_tokens=4, id=9_102),
            Request(prompt=[11, 12, 13], max_new_tokens=4, id=9_103)]


def _serve(params, cfg, reqs, **kw):
    eng = GenerationEngine(params, cfg, config=EngineConfig(max_batch=2, max_len=48, **kw))
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done for r in reqs)
    return [r.out_tokens for r in reqs], eng


def test_telemetry_on_off_bit_identical():
    """The tier-1 serving anchor emits the same tokens with telemetry
    fully on (registry + tracer) as with it off."""
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    kw = dict(cache_mode="paged", prefill_chunk=4)
    bare, _ = _serve(params, cfg, _anchor_requests(), **kw)
    tel = Telemetry()
    instr, eng = _serve(params, cfg, _anchor_requests(), telemetry=tel,
                        **kw)
    assert instr == bare

    reg = tel.registry
    assert reg.value("serving_requests_submitted_total") == 4
    assert reg.value("serving_requests_finished_total") == 4
    assert reg.value("serving_tokens_generated_total") == \
        sum(len(t) for t in bare)
    ttft = reg.get("serving_ttft_seconds")
    assert ttft.count == 4 and ttft.min > 0
    assert reg.get("serving_request_latency_seconds").count == 4
    assert reg.get("serving_decode_step_seconds").count == eng.steps
    # compile counters exist and are deltas vs engine construction
    # (the jit caches are process-shared, so the absolute value depends
    # on what compiled before — it must only never go negative)
    assert reg.value("serving_decode_compile_total") >= 0
    assert reg.value("serving_prefill_compile_total") >= 0
    assert eng.decode_compile_count() >= 1       # process-wide cache
    # every request's state spans closed on drain
    assert tel.requests.open_states == {}
    assert serving_report_line(reg)              # heartbeat renders


def test_oversubscribed_trace_export_round_trip(tmp_path):
    """ISSUE acceptance: an oversubscribed run exports a valid
    Chrome-trace with lifecycle spans incl. >= 1 preempt/resume pair."""
    from test_serving import _OVERSUB, _oversub_requests
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    tel = Telemetry()
    _, eng = _serve(params, cfg, _oversub_requests(id_base=9_200),
                    telemetry=tel, **_OVERSUB)
    assert eng.scheduler.n_preempted > 0

    path = tmp_path / "trace.json"
    trace = export_chrome_trace(tel.tracer, str(path), tel.registry)
    assert validate_chrome_trace(trace) == []
    loaded = json.loads(path.read_text())
    assert validate_chrome_trace(loaded) == []
    assert loaded == trace

    evs = loaded["traceEvents"]
    names = {e["name"] for e in evs}
    # the acceptance spans: request preempt/resume pair + swap movement
    assert {"preempted", "resume", "preempt", "evict", "fault",
            "decode_step", "finished"} <= names
    cats = {e.get("cat") for e in evs if e["ph"] == "X"}
    assert {"engine", "swap", "request"} <= cats
    # request rows: pid 2, thread-named, one per submitted request
    req_tids = {e["tid"] for e in evs if e["pid"] == 2 and e["ph"] != "M"}
    assert req_tids == {9_200 + i for i in range(
        len(_oversub_requests()))}
    thread_names = {e["args"]["name"] for e in evs
                    if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "request 9200" in thread_names
    # counter tracks render as ph C with numeric args.value
    ctr = [e for e in evs if e["ph"] == "C"]
    assert {"serving_queue_depth", "kvcache_pages_in_use"} <= \
        {e["name"] for e in ctr}
    assert all(isinstance(e["args"]["value"], (int, float)) for e in ctr)
    # embedded registry snapshot travels with the trace
    metrics = loaded["otherData"]["metrics"]
    assert metrics["serving_preempted_total"]["value"] > 0
    assert metrics["serving_resumed_total"]["value"] > 0
    assert loaded["otherData"]["n_dropped_events"] == 0


def test_metrics_only_mode_keeps_no_event_buffer():
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    tel = Telemetry(trace=False)
    assert tel.tracer is None and tel.requests is None
    toks, _ = _serve(params, cfg, _anchor_requests(), telemetry=tel,
                     cache_mode="paged")
    assert tel.registry.get("serving_ttft_seconds").count == 4


# --------------------------------------------------------------------------
# spans on the profiler's clock, named device scopes
# --------------------------------------------------------------------------

def _coding_requests():
    """Prompts that fill pages of 8, so full pages go cold while they
    decode."""
    return [Request(prompt=list(range(1, 20)), max_new_tokens=6, id=9_300),
            Request(prompt=list(range(30, 42)), max_new_tokens=7, id=9_301),
            Request(prompt=[5, 6, 7], max_new_tokens=4, id=9_302)]


_CODING = dict(cache_mode="paged", page_size=8, compress_cold=True)


def _host_spans(logdir) -> list:
    """(name, start_ns, end_ns, stats) of the engine's spans, from the
    host thread that holds them."""
    import glob
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(logdir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                   for ev in line.events
                   if ev.name.startswith(("engine.", "kv.", "swap."))]
            if any(n == "engine.step" for n, *_ in evs):
                out += evs
    return out


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_engine_spans_on_the_profiler_clock(tmp_path):
    """A paged engine that codes cold pages, stepped under the profiler:
    the trace holds each ``engine.step`` with its decode step (dispatch
    and sync), commit and page coding nested in it, and the coded-page
    count; the tracer buffer gets the same spans; tokens are the same
    with the profiler on and off."""
    cfg = smoke_variant(get("qwen3-8b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    bare, _ = _serve(params, cfg, _coding_requests(), **_CODING)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced, _ = _serve(params, cfg, _coding_requests(), **_CODING)
    finally:
        jax.profiler.stop_trace()
    assert traced == bare

    evs = _host_spans(tmp_path)
    steps = [e for e in evs if e[0] == "engine.step"]
    assert steps

    def kids(parent, name):
        return [e for e in evs if e[0] == name and _inside(e, parent)]

    full = [st for st in steps if kids(st, "engine.decode_step")]
    assert full
    for st in full:
        dec, = kids(st, "engine.decode_step")
        assert dec[3]["rows"] >= 1
        assert len(kids(dec, "engine.dispatch")) == 1
        assert len(kids(dec, "engine.sync")) == 1
        assert len(kids(st, "engine.commit")) == 1
        assert len(kids(st, "kv.code_pages")) == 1
    coded = sum(e[3]["pages"] for e in evs if e[0] == "kv.code_pages.done")
    pages = [e for e in evs if e[0] == "kv.code_page"]
    assert coded > 0 and len(pages) >= coded
    for page in pages:
        assert {"slot", "page"} <= set(page[3])
        for part in ("kv.page_fetch", "kv.page_encode"):
            assert len(kids(page, part)) == 1

    tel = Telemetry()
    instr, _ = _serve(params, cfg, _coding_requests(), telemetry=tel,
                      **_CODING)
    assert instr == bare
    buffered = {f"{cat}.{name}" for ph, cat, name, track, *_ in
                tel.tracer.events if ph == "X" and track == ENGINE_TRACK}
    assert buffered == {e[0] for e in evs if not e[0].endswith(".done")}
    code = [a for ph, cat, name, *_, a in tel.tracer.events
            if (cat, name) == ("kv", "code_pages")]
    assert sum(a["pages"] for a in code) == coded


def test_span_closes_when_its_body_raises():
    tr = SpanTracer()
    with pytest.raises(RuntimeError):
        with span("swap.fault", tr, slot=3):
            raise RuntimeError("out of pages")
    (ph, cat, name, track, _, dur, args), = tr.events
    assert (ph, cat, name, args) == ("X", "swap", "fault", {"slot": 3})
    assert dur >= 0


def test_decode_step_carries_the_device_scopes(monkeypatch):
    """The lowered decode step of compressed weights over a cache with a
    cold pool names the cold-page decode, its permutation and the weight
    decode in its ops' metadata (the scopes a TPU trace reports as each
    op's ``tf_op``)."""
    import jax.numpy as jnp
    import repro.kvcache.paged as PG
    from repro.core.store import compress_tree
    from repro.kvcache import PagedKVCache
    # the TPU's branch of the in-graph cold decode (kernels.decode_pages)
    monkeypatch.setattr(PG, "interpret_mode", lambda: False)
    cfg = smoke_variant(get("qwen3-8b"))
    params, _ = compress_tree(M.init_params(jax.random.PRNGKey(0), cfg),
                              fmt="tpu", min_elems=2048)
    pkv = PagedKVCache(cfg, 2, 32, dtype=jnp.float32, page_size=8,
                       compress_cold=True)
    tok = jnp.zeros((2, 1), jnp.int32)
    text = jax.jit(lambda p, t, c: M.decode_step(p, cfg, t, c)).lower(
        params, tok, pkv.init_cache()).as_text(debug_info=True)
    for scope in ("kv.cold_decode", "kv.cold_permute", "weights.decode"):
        # a location's name path: "<scope>/op" or ".../<scope>/op"
        assert re.search(rf'["/]{re.escape(scope)}/', text), scope


# --------------------------------------------------------------------------
# docs lint (tools/check_metrics.py, same contract as the CI docs job)
# --------------------------------------------------------------------------

def _load_metrics_checker():
    spec = importlib.util.spec_from_file_location(
        "check_metrics", os.path.join(REPO, "tools", "check_metrics.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_every_emitted_metric_name_is_documented():
    chk = _load_metrics_checker()
    assert chk.check_metrics() == []
    names = chk.emitted_names()
    assert len(names) >= 20              # the subsystem is wired in
    assert "serving_ttft_seconds" in names
    assert "kvcache_evict_pages_total" in names
