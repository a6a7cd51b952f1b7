"""Span tracing (repro.obs.trace), analyzer, histogram, and export tests.

DESIGN.md §10 acceptance: seeded chaos runs produce deterministic span
trees, spans survive CompositeTracker fan-out and a JSONL round-trip,
the analyzer validates/attributes/exports them, and the streaming
histogram replaces the biased first-N reservoir.
"""
import json
import math
import os

import numpy as np
import pytest

from repro import obs
from repro.core import marina_p, problems, stepsizes
from repro.obs import analyze
from repro.obs.hist import StreamingHistogram, percentile
from repro.transport import FaultSpec

CHAOS = FaultSpec(drop=0.3, straggler=0.3, straggler_ticks=3, seed=7)


@pytest.fixture(scope="module")
def prob():
    return problems.generate_problem(n=4, d=32, noise_scale=1.0, seed=0)


def _chaos_run(prob, tracker, *, seed=1, T=10):
    k = prob.d // prob.n
    p = k / prob.d
    return marina_p.run(prob, mode="perm", k=k, p=p,
                        stepsize=stepsizes.Constant(gamma=0.01), T=T,
                        seed=seed, transport=CHAOS, tracker=tracker)


# -- span API -----------------------------------------------------------------


def test_span_nesting_and_ids():
    tr = obs.MemoryTracker()
    with tr.span("round", round=0) as rsp:
        with tr.span("broadcast"):
            with tr.span("encode"):
                pass
        rsp.attrs["gamma"] = 0.5
    with tr.span("round", round=1):
        pass
    spans = analyze.span_events(tr.events)
    # emitted at exit: children before parents
    assert [s["name"] for s in spans] == ["encode", "broadcast", "round", "round"]
    by_name = {s["name"]: s for s in spans[:3]}
    assert by_name["broadcast"]["parent"] == by_name["round"]["span_id"]
    assert by_name["encode"]["parent"] == by_name["broadcast"]["span_id"]
    assert by_name["round"]["parent"] is None
    # deterministic counter ids, attrs mutable until exit
    assert [s["span_id"] for s in spans] == [2, 1, 0, 3]
    assert by_name["round"]["attrs"] == {"round": 0, "gamma": 0.5}
    assert all(s["t1"] >= s["t0"] for s in spans)


def test_maybe_span_none_tracker_is_noop():
    from repro.obs.trace import maybe_attr, maybe_span

    with maybe_span(None, "round") as sp:
        assert sp is None
        maybe_attr(sp, x=1)  # must not raise


def test_span_composite_fanout_and_jsonl_roundtrip(tmp_path):
    log = tmp_path / "run.jsonl"
    mem = obs.MemoryTracker()
    jl = obs.JsonlTracker(str(log))
    comp = obs.CompositeTracker(mem, jl)
    with comp.span("round", round=0):
        with comp.span("broadcast", full_sync=False):
            pass
    comp.finish()
    assert obs.events_equal(mem.events, obs.read_jsonl(str(log)))
    spans = analyze.span_events(obs.read_jsonl(str(log)))
    assert [s["name"] for s in spans] == ["broadcast", "round"]
    assert spans[0]["attrs"] == {"full_sync": False}


# -- determinism under fault injection ---------------------------------------


def test_chaos_span_tree_deterministic(prob):
    """Same transport/algorithm seed => identical span tree (names,
    nesting, retry/resync/delivery attrs); only timestamps differ."""
    t1, t2 = obs.MemoryTracker(), obs.MemoryTracker()
    _chaos_run(prob, t1)
    _chaos_run(prob, t2)
    assert obs.events_equal(t1.events, t2.events)
    f1 = analyze.build_tree(t1.events)
    f2 = analyze.build_tree(t2.events)
    assert [r.signature() for r in f1] == [r.signature() for r in f2]
    # a different seed must actually change the tree (retries differ)
    t3 = obs.MemoryTracker()
    _chaos_run(prob, t3, seed=2)
    assert [r.signature() for r in f1] != [
        r.signature() for r in analyze.build_tree(t3.events)
    ]


def test_chaos_spans_carry_link_attribution(prob):
    tr = obs.MemoryTracker()
    _chaos_run(prob, tr)
    roots = analyze.build_tree(tr.events)
    assert all(r.name == "round" for r in roots)
    names = {s.name for r in roots for s in r.walk()}
    assert {"round", "subgrad", "stepsize", "broadcast", "encode"} <= names
    assert any(n.startswith("link/worker") for n in names)
    links = [s for r in roots for s in r.walk()
             if s.name.startswith("link/worker") and "/" not in s.name[5:]]
    assert links and all("delivered" in s.attrs and "retries" in s.attrs
                         for s in links)
    # the chaos spec must actually exercise the repair paths
    assert sum(int(s.attrs["retries"]) for s in links) > 0


def test_round_reports_attribute_degraded_rounds(prob):
    tr = obs.MemoryTracker()
    _chaos_run(prob, tr)
    reports = analyze.round_reports(analyze.build_tree(tr.events))
    assert len(reports) == 10
    degraded = [r for r in reports if r.degraded]
    assert degraded, "chaos spec produced no degraded round"
    assert all(r.culprit.startswith("link/worker") for r in degraded)
    text, n_degraded = analyze.report(tr.events)
    assert n_degraded == len(degraded)
    assert "DEGRADED <- link/worker" in text


# -- validation + Perfetto export ---------------------------------------------


def test_validate_spans_catches_malformed_streams():
    ok = {"kind": "span", "name": "a", "span_id": 0, "parent": None,
          "t0": 1.0, "t1": 2.0, "attrs": {}}
    assert analyze.validate_spans([ok]) == []
    orphan = dict(ok, span_id=1, parent=99)
    assert any("orphan parent" in e for e in analyze.validate_spans([ok, orphan]))
    backwards = dict(ok, span_id=2, t0=3.0, t1=1.0)
    assert any("t1 < t0" in e for e in analyze.validate_spans([backwards]))
    dup = dict(ok)
    assert any("duplicate span_id" in e for e in analyze.validate_spans([ok, dup]))
    missing = {"kind": "span", "name": "a", "span_id": 3}
    assert any("missing t0" in e for e in analyze.validate_spans([missing]))


def test_perfetto_export_well_formed(prob, tmp_path):
    tr = obs.MemoryTracker()
    _chaos_run(prob, tr, T=4)
    doc = analyze.to_perfetto(tr.events)
    assert analyze.validate_perfetto(doc) == []
    assert doc["displayTimeUnit"] == "ms"
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(xs) == len(analyze.span_events(tr.events))
    assert all(e["dur"] >= 0 and e["ts"] >= 0 for e in xs)
    assert all(e["pid"] == 1 and e["tid"] == 1 for e in xs)
    # span ids + parentage travel in args for trace-query reconstruction
    with_parent = [e for e in xs if "parent" in e["args"]]
    assert with_parent and all("span_id" in e["args"] for e in xs)
    # document is valid JSON end to end
    out = tmp_path / "trace.json"
    out.write_text(json.dumps(doc))
    assert analyze.validate_perfetto(json.loads(out.read_text())) == []
    bad = {"traceEvents": [{"ph": "X", "name": "x", "ts": 0.0, "dur": -1.0,
                            "pid": 1, "tid": 1}]}
    assert any("negative" in e for e in analyze.validate_perfetto(bad))


def test_analyze_cli_end_to_end(prob, tmp_path, capsys):
    log = tmp_path / "run.jsonl"
    tr = obs.JsonlTracker(str(log))
    _chaos_run(prob, tr)
    tr.finish()
    trace = tmp_path / "trace.json"
    rc = analyze.main([str(log), "--perfetto", str(trace), "--require-degraded"])
    assert rc == 0
    assert os.path.exists(trace)
    assert analyze.main(["--validate-trace", str(trace)]) == 0
    out = capsys.readouterr().out
    assert "round" in out and "DEGRADED" in out
    # an empty log has no degraded rounds to attribute
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert analyze.main([str(empty), "--require-degraded"]) == 1


# -- percentiles / histogram (bench_json reservoir-bias fix) ------------------


def test_percentile_linear_interpolation():
    vals = sorted(float(v) for v in range(100))  # 0..99
    assert percentile(vals, 0.0) == 0.0
    assert percentile(vals, 1.0) == 99.0
    assert percentile(vals, 0.50) == pytest.approx(49.5)  # not nearest-rank 50
    assert percentile(vals, 0.99) == pytest.approx(98.01)
    assert percentile([5.0], 0.75) == 5.0


def test_streaming_histogram_exact_below_cap():
    h = StreamingHistogram(exact_cap=1000)
    vals = list(np.random.default_rng(0).normal(10.0, 2.0, 500))
    for v in vals:
        h.add(v)
    s = sorted(vals)
    assert h.quantile(0.5) == pytest.approx(percentile(s, 0.5))
    assert h.quantile(0.99) == pytest.approx(percentile(s, 0.99))
    assert h.n == 500


def test_streaming_histogram_sees_past_cap():
    """The old reservoir kept only the first 4096 samples — a later shift
    in the distribution never moved p99. The histogram tracks it."""
    h = StreamingHistogram(exact_cap=256)
    for _ in range(256):
        h.add(1e-3)  # warm-up plateau fills the exact window
    for _ in range(4096):
        h.add(1.0)   # steady state is 1000x slower
    q = h.quantile(0.99)
    assert q == pytest.approx(1.0, rel=0.05)
    assert h.n == 4352 and h.max >= 1.0
    # relative accuracy of the log-binned estimate
    h2 = StreamingHistogram(exact_cap=64)
    data = np.random.default_rng(1).lognormal(0.0, 1.0, 20000)
    for v in data:
        h2.add(float(v))
    for q_ in (0.5, 0.99):
        ref = float(np.quantile(data, q_))
        assert h2.quantile(q_) == pytest.approx(ref, rel=0.05)


def test_streaming_histogram_ignores_nan_and_summary():
    h = StreamingHistogram()
    h.add(float("nan"))
    for v in (0.5, -2.0, 3.0):
        h.add(v)
    assert h.n == 3
    s = h.summary("_s")
    assert s["n"] == 3
    assert s["total_s"] == pytest.approx(1.5)
    assert s["p50_s"] == pytest.approx(0.5)


def test_bench_sink_aggregates_spans_as_namespaced_timers(tmp_path):
    sink = obs.BenchJsonSink("t", str(tmp_path))
    with sink.span("round"):
        pass
    with sink.time_block("round"):
        pass
    sink.finish()
    doc = obs.load(sink.path)
    assert "span/round" in doc["timers"] and "round" in doc["timers"]
    from repro.obs import bench_json

    assert bench_json.validate(doc) == []


# -- profiler annotations -----------------------------------------------------


def test_spans_are_annotations_on_the_profiler_clock(tmp_path):
    """Under a jax.profiler trace every span has its prefixed annotation in
    the host plane, nested as the spans are and as long to within 0.1 ms."""
    import glob
    import time

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from repro.obs.trace import ANNOTATION_PREFIX

    tr = obs.MemoryTracker()
    with jax.profiler.trace(str(tmp_path)):
        for t in range(3):
            with tr.span("round", round=t):
                with tr.span("feed"):
                    time.sleep(0.002)
                with tr.span("dispatch"):
                    x = jnp.ones(64) * t
                with tr.span("wait"):
                    x.block_until_ready()
                with tr.span("log"):
                    with tr.span("link/w0/retry"):
                        pass
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = sorted(((e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for plane in ProfileData.from_file(path).planes
                   if plane.name == "/host:CPU"
                   for line in plane.lines for e in line.events
                   if e.name.startswith(ANNOTATION_PREFIX)),
                  key=lambda h: (h[1], -h[2]))
    spans = sorted((e for e in tr.events if e["kind"] == "span"), key=lambda e: e["t0"])
    assert len(spans) == 3 * 6
    assert [n for n, _, _ in host] == [ANNOTATION_PREFIX + s["name"] for s in spans]
    by_id = {s["span_id"]: iv for s, iv in zip(spans, host)}
    for s, (_, a, b) in zip(spans, host):
        assert abs((b - a) / 1e9 - (s["t1"] - s["t0"])) < 1e-4, s["name"]
        if s["parent"] is not None:
            _, pa, pb = by_id[s["parent"]]
            assert pa <= a and b <= pb, s["name"]


# -- fleet cohort spans -------------------------------------------------------


def test_fleet_run_spans_attribute_dropped_slots():
    from repro.core import stepsizes as ss
    from repro.fleet import make_fleet, make_sampler
    from repro.fleet.cohort import fleet_run
    from repro.fleet.population import FleetL1Problem

    spec = make_fleet("flaky_mobile", 512, seed=0)
    prob = FleetL1Problem(spec, d=32)
    sampler = make_sampler("uniform", spec, 8, seed=1)
    tr = obs.MemoryTracker()
    fleet_run(prob, sampler, ss.Constant(gamma=0.05), algorithm="marina_p",
              mode="perm", T=8, seed=0, tracker=tr)
    assert analyze.validate_spans(tr.events) == []
    roots = analyze.build_tree(tr.events)
    reports = analyze.round_reports(roots)
    assert len(reports) == 8
    # flaky_mobile's per-client drop model must surface as degraded rounds
    # attributed to specific client links with fresh/delivered attrs
    degraded = [r for r in reports if r.degraded]
    assert degraded and all(r.culprit.startswith("link/client") for r in degraded)
    links = [s for r in roots for s in r.walk() if s.name.startswith("link/client")]
    assert links and all(
        "delivered" in s.attrs and "fresh" in s.attrs for s in links
    )


# -- serve spans --------------------------------------------------------------


def test_serve_request_spans(tmp_path):
    import jax

    from repro import configs
    from repro.models import lm
    from repro.serve import DecodeEngine

    cfg = configs.get_smoke("gemma-2b")
    params = lm.lm_init(cfg, jax.random.PRNGKey(0))
    tr = obs.MemoryTracker()
    eng = DecodeEngine(cfg, params, cache_len=16, batch_size=2, tracker=tr)
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, cfg.vocab_size)
    eng.run(prompts, n_new_tokens=4)
    roots = analyze.build_tree(tr.events)
    reqs = [r for r in roots if r.name == "serve/request"]
    assert len(reqs) == 1
    assert [c.name for c in reqs[0].children] == ["prefill", "decode"]
    assert reqs[0].attrs["tokens_per_s"] > 0
    assert reqs[0].attrs["batch"] == 2
    # serve/request rounds get latency reports too
    reports = analyze.round_reports(roots)
    assert len(reports) == 1 and not reports[0].degraded
    # existing timer telemetry is untouched by the spans
    timers = [e["name"] for e in tr.events if e["kind"] == "timer"]
    assert timers == ["serve/prefill", "serve/decode"]
