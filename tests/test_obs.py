"""repro.obs: metrics registry, spans, JSONL hardening, bit-parity.

The load-bearing guarantee is **trace bit-parity**: attaching an
`EngineObs` must not change the compiled round program, so an
instrumented run's trace equals an uninstrumented run's record for
record — on both the event-loop and the scanned path.  Everything else
here pins the registry semantics (cardinality guard, Prometheus text
golden, snapshot round-trip), the span tree machinery, and the JSONL
crash hardening (torn final lines, sink reopen after rotation).
"""
import contextlib
import dataclasses
import glob
import json
import os

import jax
import jax.numpy as jnp
import pytest

import repro.api as api
from repro.api import (AggregatorSpec, ControllerSpec, Federation,
                       FederationSpec, FleetSpec, TaskSpec)
from repro.api.records import (JsonlSink, RoundRecord, read_jsonl_trace,
                               tail_jsonl)
from repro.data import dirichlet_partition, make_classification
from repro.obs import (METRICS_SCHEMA, SPAN_SCHEMA, EngineObs,
                       MetricsRegistry, SpanRecorder, compiles,
                       merge_snapshot_records, snapshot_record)


def _data(n=1536, dim=48, devices=8, seed=0):
    key = jax.random.PRNGKey(seed)
    data = make_classification(key, n=n, dim=dim)
    return data, dirichlet_partition(key, data.y, devices)


def _spec(seed=0, execution="scanned"):
    return FederationSpec(
        fleet=FleetSpec(n_devices=8),
        clustering=api.ClusteringSpec(n_clusters=2),
        controller=ControllerSpec("fixed", {"a": 3}),
        execution=execution, rounds=4, sim_seconds=1e9,
        local_batch=32, seed=seed)


class ListSink:
    def __init__(self):
        self.records = []

    def append(self, rec):
        self.records.append(rec)


# --------------------------------------------------------------------- #
# registry semantics
# --------------------------------------------------------------------- #
def test_counter_gauge_histogram_semantics():
    reg = MetricsRegistry()
    c = reg.counter("rounds_total", "rounds")
    c.inc()
    c.inc(2, cluster="0")
    c.inc(3, cluster="1")
    assert c.value() == 1
    assert c.value(cluster="0") == 2
    assert c.total() == 6
    with pytest.raises(ValueError):
        c.inc(-1)

    g = reg.gauge("queue", "deficit")
    g.set(4.5)
    g.set(2.0)
    assert g.value() == 2.0

    h = reg.histogram("dur", "round duration", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    s = h._series[()]
    assert s.counts == [1, 1, 1]        # <=0.1, <=1.0, +Inf
    assert s.count == 3
    assert s.sum == pytest.approx(5.55)

    # re-declaration is idempotent per kind, an error across kinds
    assert reg.counter("rounds_total") is c
    with pytest.raises(ValueError):
        reg.gauge("rounds_total")
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(1.0, 0.5))


def test_cardinality_guard_collapses_to_overflow():
    reg = MetricsRegistry(max_series=2)
    c = reg.counter("per_device", "per-device tally")
    for i in range(5):
        c.inc(1, device=str(i))
    # 2 real series + the reserved overflow series holding the rest
    assert c.value(device="0") == 1 and c.value(device="1") == 1
    assert c.value(overflow="true") == 3
    assert c.total() == 5
    dropped = reg.get("metrics_dropped_series_total")
    assert dropped.value(metric="per_device") == 3


def test_prometheus_exposition_golden():
    reg = MetricsRegistry()
    reg.counter("fl_rounds_total", "rounds executed").inc(7)
    g = reg.gauge("fl_loss", "last loss")
    g.set(0.25)
    c2 = reg.counter("fl_cluster_rounds_total", "per cluster")
    c2.inc(4, cluster="0")
    c2.inc(3, cluster="1")
    h = reg.histogram("fl_dur", "duration", buckets=(0.5, 1.0))
    h.observe(0.3)
    h.observe(2.0)
    assert reg.to_prometheus() == (
        "# HELP fl_cluster_rounds_total per cluster\n"
        "# TYPE fl_cluster_rounds_total counter\n"
        'fl_cluster_rounds_total{cluster="0"} 4\n'
        'fl_cluster_rounds_total{cluster="1"} 3\n'
        "# HELP fl_dur duration\n"
        "# TYPE fl_dur histogram\n"
        'fl_dur_bucket{le="0.5"} 1\n'
        'fl_dur_bucket{le="1"} 1\n'
        'fl_dur_bucket{le="+Inf"} 2\n'
        "fl_dur_sum 2.3\n"
        "fl_dur_count 2\n"
        "# HELP fl_loss last loss\n"
        "# TYPE fl_loss gauge\n"
        "fl_loss 0.25\n"
        "# HELP fl_rounds_total rounds executed\n"
        "# TYPE fl_rounds_total counter\n"
        "fl_rounds_total 7\n")


def test_snapshot_roundtrip_is_lossless():
    reg = MetricsRegistry()
    reg.counter("a_total", "a").inc(3, k="v")
    reg.gauge("b", "b").set(-1.5)
    reg.histogram("c", "c", buckets=(1.0,)).observe(0.5)
    snap = json.loads(json.dumps(reg.snapshot()))   # through JSON
    assert snap["schema"] == METRICS_SCHEMA
    back = MetricsRegistry.from_snapshot(snap)
    assert back.totals() == reg.totals()
    assert back.to_prometheus() == reg.to_prometheus()
    with pytest.raises(ValueError):
        MetricsRegistry.from_snapshot({"schema": "metrics/999"})


def test_merge_snapshot_records_latest_per_source():
    service, chaos = MetricsRegistry(), MetricsRegistry()
    c = service.counter("fl_rounds_total", "rounds")
    k = chaos.counter("chaos_sigkills_total", "kills")
    c.inc(5)
    old = snapshot_record(service, source="service", ts=1.0)
    c.inc(5)
    new = snapshot_record(service, source="service", ts=2.0)
    k.inc(1)
    ch = snapshot_record(chaos, source="chaos", ts=1.5)
    merged = merge_snapshot_records([old, ch, new])
    got = MetricsRegistry.from_snapshot(merged).totals()
    assert got["fl_rounds_total"] == 10          # latest service snapshot
    assert got["chaos_sigkills_total"] == 1      # merged across sources
    assert merge_snapshot_records([{"schema": "span/1"}]) is None


# --------------------------------------------------------------------- #
# spans
# --------------------------------------------------------------------- #
def test_span_nesting_and_sink_emission():
    sink = ListSink()
    rec = SpanRecorder(sink=sink)
    with rec.span("segment", segment=1) as seg:
        with rec.span("round", rounds=4) as rd:
            rd.mark("dispatch")
        with rec.span("checkpoint"):
            pass
    assert [c.name for c in seg.children] == ["round", "checkpoint"]
    assert "dispatch_s" in seg.children[0].attrs
    assert seg.dur_s >= sum(c.dur_s for c in seg.children) > 0
    # only the completed root is emitted; children nest inside it
    assert len(sink.records) == 1
    root = sink.records[0]
    assert root["schema"] == SPAN_SCHEMA
    assert root["name"] == "segment"
    assert [c["name"] for c in root["children"]] == ["round", "checkpoint"]
    assert rec.last("segment") is seg
    assert rec.last("nope") is None


def test_span_fence_blocks_on_device_values():
    rec = SpanRecorder()
    x = None
    with rec.span("round", fence_on=None) as sp:
        x = jax.numpy.ones((8, 8)) @ jax.numpy.ones((8, 8))
        sp.mark("dispatch")
    # fencing on the result must be tolerated for arbitrary pytrees too
    with rec.span("fenced", fence_on={"x": x, "n": 3}):
        pass
    assert rec.last("fenced").dur_s >= 0


# --------------------------------------------------------------------- #
# JSONL hardening (satellites: torn lines, sink reopen)
# --------------------------------------------------------------------- #
def _write_trace(path, n):
    recs = [RoundRecord(t=float(i), round=i + 1, cluster=0, a=2,
                        loss=1.0 / (i + 1), acc=None, energy=float(i),
                        agg_count=i) for i in range(n)]
    with open(path, "w") as f:
        for r in recs:
            f.write(json.dumps(dataclasses.asdict(r)) + "\n")
    return recs


def test_read_jsonl_trace_skips_torn_final_line(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    recs = _write_trace(path, 3)
    with open(path, "a") as f:            # writer SIGKILLed mid-append
        f.write('{"t": 3.0, "round": 4, "clu')
    trace = read_jsonl_trace(path)
    assert trace.records == recs
    assert tail_jsonl(path, n=10) == [dataclasses.asdict(r) for r in recs]


def test_read_jsonl_trace_rejects_mid_file_corruption(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    recs = _write_trace(path, 3)
    lines = open(path).read().splitlines()
    lines[1] = lines[1][:20]              # torn line with records after it
    open(path, "w").write("\n".join(lines) + "\n")
    with pytest.raises(json.JSONDecodeError):
        read_jsonl_trace(path)
    del recs


def test_jsonl_sink_reopens_after_rotation(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    sink = JsonlSink(path)
    sink.append({"i": 0})
    os.replace(path, path + ".1")         # logrotate-style move-away
    sink.append({"i": 1})                 # must land in a fresh file
    os.unlink(path)                       # hostile: unlink underneath
    sink.append({"i": 2})
    sink.close()
    assert [r["i"] for r in tail_jsonl(path, n=10)] == [2]
    assert [r["i"] for r in tail_jsonl(path + ".1", n=10)] == [0]
    # dataclass records still serialize (the trace.jsonl path)
    sink2 = JsonlSink(path)
    sink2.append(RoundRecord(t=0.0, round=1, cluster=0, a=1, loss=1.0,
                             acc=None, energy=0.0, agg_count=0))
    sink2.close()
    assert tail_jsonl(path, n=1)[0]["round"] == 1


# --------------------------------------------------------------------- #
# bit-parity: telemetry must not perturb the trace
# --------------------------------------------------------------------- #
def test_scanned_trace_bit_parity_with_obs(tmp_path):
    data, parts = _data(seed=5)
    plain = Federation.from_spec(_spec(seed=5), data=data, parts=parts)
    want = plain.engine.run_scanned(6, eval_final=False).records

    sink = JsonlSink(str(tmp_path / "metrics.jsonl"))
    obs = EngineObs(sink=sink, source="service")
    inst = Federation.from_spec(_spec(seed=5), data=data, parts=parts)
    inst.engine.set_obs(obs)
    got = inst.engine.run_scanned(6, eval_final=False).records

    assert len(got) == len(want) == 6
    for a, b in zip(want, got):
        assert a == b                     # dataclass eq: floats exact
    totals = obs.registry.totals()
    assert totals["fl_rounds_total"] == 6
    # the compile counter sees every compile of the process; the scan's
    # program among them, once
    assert obs.registry.get("fl_compiles_total").value(
        fn="jit(run_k)") == 1
    assert totals["fl_sim_seconds_total"] > 0
    assert obs.spans.last("round") is not None
    assert obs.spans.last("compile") is not None
    sink.close()
    schemas = [r.get("schema")
               for r in tail_jsonl(str(tmp_path / "metrics.jsonl"), n=64)]
    assert SPAN_SCHEMA in schemas and "event/1" in schemas


def test_event_loop_trace_bit_parity_with_obs():
    data, parts = _data(seed=6)
    plain = Federation.from_spec(_spec(seed=6, execution="event"),
                                 data=data, parts=parts)
    want = plain.run(eval_every=1.0, max_rounds=10).records

    obs = EngineObs()
    inst = Federation.from_spec(_spec(seed=6, execution="event"),
                                data=data, parts=parts)
    inst.engine.set_obs(obs)
    got = inst.run(eval_every=1.0, max_rounds=10).records

    assert len(got) == len(want) > 0
    for a, b in zip(want, got):
        assert a == b
    totals = obs.registry.totals()
    assert totals["fl_rounds_total"] == 10
    assert totals["fl_evals_total"] > 0
    assert totals["fl_energy_joules_total"] > 0


def test_state_summary_is_read_only():
    data, parts = _data(seed=7)
    fed = Federation.from_spec(_spec(seed=7), data=data, parts=parts)
    fed.engine.run_scanned(3, eval_final=False)
    before = jax.tree.map(lambda x: x, fed.engine.state)
    summary = fed.engine.obs_state_summary()
    for k in ("queue_deficit", "reputation_min", "reputation_mean",
              "reputation_max", "twin_beta_sum"):
        assert isinstance(summary[k], float)
    assert summary["reputation_min"] <= summary["reputation_mean"] \
        <= summary["reputation_max"]
    after = fed.engine.run_scanned(3, eval_final=False)
    del before, after                     # summary ran between segments
    # and calling it again mid-stream gives the same numbers (pure read)
    assert fed.engine.obs_state_summary() == fed.engine.obs_state_summary()


# --------------------------------------------------------------------- #
# the engine on the profiler's clock: host spans, compiles, device stages
# --------------------------------------------------------------------- #
SEGMENT_SPANS = ["prepare", "round", "flush", "host_sync", "records", "eval"]
ROUND_STAGES = ("fl.control", "fl.batch", "fl.local_sgd", "fl.flatten",
                "fl.trust", "fl.aggregate", "fl.twins")


def _host_events(trace_dir, prefixes):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                         "*.xplane.pb"), recursive=True))[-1]
    pd = ProfileData.from_file(path)
    return sorted(((e.name, e.start_ns, e.end_ns)
                   for p in pd.planes if p.name.startswith("/host:")
                   for line in p.lines for e in line.events
                   if e.name.startswith(prefixes)), key=lambda e: e[1])


def test_segment_spans_reach_the_profiler_without_obs(tmp_path):
    data, parts = _data(seed=8)
    fed = Federation.from_spec(_spec(seed=8), data=data, parts=parts)
    fed.engine.run_scanned(4)               # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.segment"):
            fed.engine.run_scanned(4)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path, ("fl.", "test."))
    (_, t0, t1), = [e for e in events if e[0] == "test.segment"]
    inside = [n for n, a, b in events
              if n.startswith("fl.") and t0 <= a and b <= t1]
    assert inside == ["fl." + n for n in SEGMENT_SPANS]

    # with a recorder attached, its spans carry the same names
    obs = EngineObs()
    fed.engine.set_obs(obs)
    fed.engine.run_scanned(4)
    assert [sp.name for sp in obs.spans.finished] == SEGMENT_SPANS


def test_recorder_spans_are_profiler_annotations(tmp_path):
    rec = SpanRecorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("segment"):
            with rec.span("checkpoint"):
                pass
    finally:
        jax.profiler.stop_trace()
    names = [n for n, _, _ in _host_events(tmp_path, ("fl.",))]
    assert names == ["fl.segment", "fl.checkpoint"]


def test_compile_counter_counts_a_new_shape_once():
    f = jax.jit(lambda x: x * 2.0 + 1.0)
    small, big = jnp.ones(3), jnp.ones(5)
    obs = EngineObs()                       # fed while it lives
    before = compiles.counts()
    f(small)
    first = compiles.counts()
    f(small)
    repeat = compiles.counts()
    f(big)
    reshaped = compiles.counts()
    assert first["lowerings"] - before["lowerings"] == 1
    assert first["compiles"] - before["compiles"] == 1
    assert repeat == first
    assert reshaped["lowerings"] - repeat["lowerings"] == 1
    counter = obs.registry.get("fl_compiles_total")
    assert counter.value(fn="jit(<lambda>)") == 2
    assert obs.registry.get("fl_compile_seconds_total").total() > 0


def test_compiled_scan_carries_each_stage_scope():
    data, parts = _data(seed=9)
    # a controller that scores the features (a fixed `a` leaves no
    # control ops in the optimized program)
    spec = dataclasses.replace(_spec(seed=9), controller=ControllerSpec(
        "lyapunov", {"budget": 200.0, "horizon": 40}))
    fed = Federation.from_spec(spec, data=data, parts=parts)
    obs = EngineObs()                       # compiles ahead of time
    fed.engine.set_obs(obs)
    fed.engine.run_scanned(3, eval_final=False)
    hlo = fed.engine._scan_cache[3].as_text()
    for name in ROUND_STAGES:
        assert f"/{name}/" in hlo, name


def test_stage_scopes_leave_the_trace_bit_identical(monkeypatch):
    data, parts = _data(seed=10)

    def run():
        fed = Federation.from_spec(_spec(seed=10), data=data, parts=parts)
        fed.engine.set_obs(EngineObs())
        records = fed.engine.run_scanned(6).records
        return records, fed.engine._scan_cache[6].as_text()

    want, scoped_hlo = run()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    got, plain_hlo = run()
    assert "/fl.trust/" in scoped_hlo and "/fl.trust/" not in plain_hlo
    assert len(got) == len(want) == 7
    for a, b in zip(want, got):
        assert a == b                     # dataclass eq: floats exact


# --------------------------------------------------------------------- #
# serve integration: metrics.jsonl + status metrics block
# --------------------------------------------------------------------- #
def _tiny_spec_file(tmp_path):
    spec = FederationSpec(
        fleet=FleetSpec(n_devices=8),
        clustering=api.ClusteringSpec(n_clusters=2),
        controller=ControllerSpec("fixed", {"a": 2}),
        aggregator=AggregatorSpec("trust"),
        task=TaskSpec("autoencoder-anomaly",
                      {"n_samples": 512, "dim": 16, "n_types": 4,
                       "latent": 2, "hidden": 16, "code": 4,
                       "dirichlet_alpha": 5.0}),
        execution="scanned", rounds=3, sim_seconds=1e9,
        local_batch=16, lr=0.1, seed=11)
    path = tmp_path / "spec.json"
    path.write_text(json.dumps(spec.to_dict()))
    return str(path)


def test_serve_metrics_file_and_status_block(tmp_path):
    from repro.serve.__main__ import main
    from repro.serve.service import load_run_metrics, service_status

    run_dir = str(tmp_path / "run")
    assert main(["start", "--run-dir", run_dir,
                 "--spec-file", _tiny_spec_file(tmp_path),
                 "--segment-rounds", "3", "--max-segments", "2",
                 "--foreground"]) == 0

    recs = tail_jsonl(os.path.join(run_dir, "metrics.jsonl"), n=64)
    schemas = {r.get("schema") for r in recs}
    assert {METRICS_SCHEMA, SPAN_SCHEMA, "event/1"} <= schemas
    seg = [r for r in recs if r.get("schema") == SPAN_SCHEMA
           and r.get("name") == "segment"]
    assert len(seg) == 2
    assert {c["name"] for c in seg[-1]["children"]} \
        >= {"round", "checkpoint"}

    st = service_status(run_dir)
    m = st["metrics"]
    assert m["fl_rounds_total"] == 6
    assert m["fl_checkpoints_total"] == 2
    assert m["service_segments_total"] == 2
    assert st["last_span"]["name"] == "segment"

    # the Prometheus dump path works off the same merged snapshot
    text = MetricsRegistry.from_snapshot(
        load_run_metrics(run_dir)).to_prometheus()
    assert "fl_rounds_total 6" in text
    assert 'fl_compiles_total{fn="' in text

    assert main(["metrics", "--run-dir", run_dir]) == 0
    assert main(["status", "--run-dir", run_dir, "--watch", "--once"]) == 0
