"""The readers of the program's own spans, scopes and compile markers:
on hand-made events, on a trimmed chip trace recorded with them
(``paper-v.scan.fl.xplane.pb``), and the per-layer readers that were
there before, unchanged on the trace recorded without them."""
import dataclasses
import glob
import os
import shutil

import jax
import jax.numpy as jnp
import pytest

from bench import program_trace
from bench import trace as bench_trace
from bench.metrics import (device_idle, dispatch_ms_per_segment,
                           eval_ms_per_segment, flatten_device_ms,
                           fleet_mfu, host_ms_per_segment,
                           host_sync_ms_per_segment, records_ms_per_segment,
                           round_device_ms, sgd_device_ms, trust_device_ms,
                           trust_kernel_roofline, window_compiles)

DATA = os.path.join(os.path.dirname(__file__), "data")
SPAN_READERS = (dispatch_ms_per_segment, host_sync_ms_per_segment,
                records_ms_per_segment, eval_ms_per_segment)
SCOPE_READERS = (sgd_device_ms, flatten_device_ms, trust_device_ms)
NEW_READERS = SPAN_READERS + SCOPE_READERS + (window_compiles,)
BODY = "jit(run_k)/jit(main)/while/body/"


def _events():
    """One device; a window 0..1000 with two segments.  Each segment:
    prepare, round (the scan runs on the device meanwhile), flush,
    host_sync, records, eval; one lowering marker in the second."""
    devices = [[
        ("%fusion.1", 110, 130, BODY + "fl.local_sgd/vmap(sgd)/dot_general"),
        ("%fusion.2", 130, 140, BODY + "transpose(jvp(fl.local_sgd))/mul"),
        ("%copy.3", 140, 150, BODY + "fl.flatten/concatenate"),
        ("%fusion.4", 150, 155, BODY + "fl.trust/reduce_sum"),
        ("%trust_aggregate_global.5", 155, 160, BODY + "fl.aggregate/pc"),
        ("%fusion.6", 180, 190, "jit(_argmax)/argmax"),
        ("%fusion.1", 510, 530, BODY + "fl.local_sgd/vmap(sgd)/dot_general"),
        ("%copy.3", 540, 550, BODY + "fl.flatten/concatenate"),
        ("%fusion.4", 550, 555, BODY + "fl.trust/reduce_sum"),
        ("%fusion.7", 555, 556, "")]]
    spans = [("bench.window", 0, 1000),
             ("bench.segment", 100, 300), ("bench.segment", 500, 700)]
    for s in (100, 500):
        spans += [("fl.prepare", s + 1, s + 5), ("fl.round", s + 5, s + 8),
                  ("fl.flush", s + 8, s + 9), ("fl.host_sync", s + 9, s + 70),
                  ("fl.records", s + 70, s + 80), ("fl.eval", s + 80, s + 199)]
    spans.append(("fl.lowering", 600, 601))
    modules = [("jit_run_k(3)", 105, 165), ("jit_run_k(3)", 505, 560),
               ("jit__argmax(4)", 180, 190)]
    return devices, spans, modules


def _ctx(view, rounds=4):
    return {"trace": view.red, "program": view,
            "window": {"rounds": [(0, 1)] * rounds}}


def test_host_spans_per_segment():
    ctx = _ctx(program_trace.reduce(*_events()))
    # ns per segment -> ms: (4 + 3) ns, 61 ns, (1 + 10) ns, 119 ns
    assert dispatch_ms_per_segment.read(ctx) == pytest.approx(7e-6)
    assert host_sync_ms_per_segment.read(ctx) == pytest.approx(61e-6)
    assert records_ms_per_segment.read(ctx) == pytest.approx(11e-6)
    assert eval_ms_per_segment.read(ctx) == pytest.approx(119e-6)


def test_program_spans_outside_the_segments_are_left_out():
    devices, spans, modules = _events()
    spans = spans + [("fl.eval", 800, 900)]       # the check's, say
    view = program_trace.reduce(devices, spans, modules)
    assert eval_ms_per_segment.read(_ctx(view)) == pytest.approx(119e-6)


def test_device_time_by_scope_inside_the_scan():
    ctx = _ctx(program_trace.reduce(*_events()))
    # local SGD 30 + 20 ns, flatten 10 + 10, trust 5 + 5, over 4 rounds;
    # the transposed SGD op keeps its scope, the evaluation's op has none
    assert sgd_device_ms.read(ctx) == pytest.approx(50e-9 * 1e3 / 4)
    assert flatten_device_ms.read(ctx) == pytest.approx(20e-9 * 1e3 / 4)
    assert trust_device_ms.read(ctx) == pytest.approx(10e-9 * 1e3 / 4)
    total = sum(r.read(ctx) for r in SCOPE_READERS)
    assert total <= round_device_ms.read(ctx)


def test_scoped_time_outside_the_scan_is_left_out():
    devices, spans, modules = _events()
    devices = [devices[0] + [("%fusion.9", 200, 230,
                              "jit(other)/fl.local_sgd/dot_general")]]
    view = program_trace.reduce(devices, spans, modules)
    assert sgd_device_ms.read(_ctx(view)) == pytest.approx(50e-9 * 1e3 / 4)


def test_idle_gaps_go_to_the_innermost_program_span():
    red = program_trace.reduce(*_events()).red
    idle = red.idle_by_span
    # host_sync: 109-110, 160-170; 509-510, 530-540, 556-570
    assert idle["fl.host_sync"] == 11 + 25
    # eval: 190-299; 580-600, 601-699 (600-601 is the lowering's)
    assert idle["fl.eval"] == 109 + 118
    assert idle["fl.lowering"] == 1
    assert idle["fl.prepare"] == 4 + 4 and idle["fl.records"] == 10 + 10
    assert idle["bench.segment"] == 2 + 2
    assert idle["outside bench spans"] == 100 + 200 + 300
    names = [n for n, _ in bench_trace.breakdown(red)["idle_gaps"]]
    assert names[:3] == ["outside bench spans", "fl.eval", "fl.host_sync"]


def test_window_compiles_counts_the_markers():
    view = program_trace.reduce(*_events())
    assert window_compiles.read(_ctx(view)) == 1


def test_a_trace_without_program_spans_reads_nothing():
    devices, spans, modules = _events()
    devices = [[op[:3] for op in devices[0]]]
    spans = [s for s in spans if not s[0].startswith("fl.")]
    view = program_trace.reduce(devices, spans, modules)
    for reader in NEW_READERS:
        assert reader.read(_ctx(view)) is None, reader.__name__


def test_a_forced_recompile_in_the_window_is_counted(tmp_path):
    """The program's compile counter marks each lowering on the profiler's
    host plane; a new shape inside the window is one."""
    from jax.profiler import ProfileData
    from repro.obs import compiles
    compiles.install()
    f = jax.jit(lambda x: x + 1.0)
    warm, new = jnp.ones(4), jnp.ones(6)
    f(warm)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            f(warm)                             # cached: no lowering
            with jax.profiler.TraceAnnotation("fl.round"):
                f(new).block_until_ready()      # a new shape: one
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    _, spans, _ = program_trace.events(ProfileData.from_file(path))
    (_, t0, _), = [s for s in spans if s[0] == "bench.window"]
    view = program_trace.reduce([[("op", t0, t0 + 1)]], spans)
    assert window_compiles.read(_ctx(view)) == 1


# the readers that were there before, on the trace recorded before the
# program had spans of its own: the values they gave then
BEFORE = {"host_ms_per_segment": 16.766462, "round_device_ms": None,
          "fleet_mfu": 0.1957522369027247,
          "trust_kernel_roofline": 92.58408792140395,
          "device_idle": 88.4811281044164}


def test_existing_readers_read_the_recorded_trace_as_before():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(os.path.join(DATA, "paper-v.scan.xplane.pb"))
    red = bench_trace.reduce(*bench_trace.events(pd))
    ctx = {"trace": red,
           "window": {"rounds": [(c % 4, 1 + c % 3) for c in range(30)],
                      "evals": 3},
           "members": [4, 4, 4, 4],
           "spec": {"local_batch": 64, "clustering": {"n_clusters": 4}},
           "dims": {"dim": 784, "hidden": 200, "n_classes": 10},
           "cfg": {"data": {"n_samples": 4096}},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    for reader in (host_ms_per_segment, round_device_ms, fleet_mfu,
                   trust_kernel_roofline, device_idle):
        name = reader.__name__.rsplit(".", 1)[-1]
        assert reader.read(ctx) == BEFORE[name], name
    assert bench_trace.breakdown(red)["idle_gaps"] == [
        ["bench.segment", 0.050299386],
        ["outside bench spans", 6.945000000000001e-05]]
    for reader in NEW_READERS:
        assert reader.read(ctx) is None
    # read with the program's spans and scopes, it holds none of them
    ctx["program"] = program_trace.load(
        os.path.join(DATA, "paper-v.scan.xplane.pb"))
    assert not ctx["program"].has_program_spans()
    assert not ctx["program"].scoped
    for reader in NEW_READERS:
        assert reader.read(ctx) is None


# a trimmed chip trace recorded with the program's spans and scopes:
# `bench/run.py --trace 1` on paper-v.scan (TPU v5 lite), three 10-round
# segments of its traced window
SEGMENT_SPANS = ["fl.prepare", "fl.round", "fl.flush", "fl.host_sync",
                 "fl.records", "fl.eval"]


RECORDED = os.path.join(DATA, "paper-v.scan.fl.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    """What a run's readers get: `bench.trace`'s reduction of the trace
    and, read from the same file, the program's view of it."""
    from jax.profiler import ProfileData
    red = bench_trace.reduce(*bench_trace.events(
        ProfileData.from_file(RECORDED)))
    return {"trace": red, "program": program_trace.load(RECORDED),
            "window": {"rounds": [(0, 1)] * 30}}


def test_recorded_segments_hold_the_program_spans_in_order(recorded):
    red = recorded["program"].red
    segs = red.spans["bench.segment"]
    assert len(segs) == 3
    for a, b in segs:
        inside = sorted((s, n) for n in SEGMENT_SPANS
                        for s, e in red.spans[n] if a <= s and e <= b)
        assert [n for _, n in inside] == SEGMENT_SPANS
        # the program's spans cover nine tenths of the segment and more
        covered = sum(e - s for n in SEGMENT_SPANS
                      for s, e in red.spans[n] if a <= s and e <= b)
        assert covered >= 0.9 * (b - a)


def test_recorded_readers(recorded):
    values = {r.__name__.rsplit(".", 1)[-1]: r.read(recorded)
              for r in NEW_READERS}
    assert values["window_compiles"] == 0
    assert all(v is not None and v > 0 for k, v in values.items()
               if k != "window_compiles"), values
    # the spans hold the segment's host time and the device time it waits
    # for, inside the segment's wall time
    segs = recorded["program"].red.spans["bench.segment"]
    wall = 1e-6 * sum(b - a for a, b in segs) / len(segs)
    host = host_ms_per_segment.read(recorded)
    spans = sum(r.read(recorded) for r in SPAN_READERS)
    assert host < spans <= wall
    scoped = sum(r.read(recorded) for r in SCOPE_READERS)
    assert scoped <= round_device_ms.read(recorded)


def test_recorded_idle_gaps_name_the_program_spans(recorded):
    red = recorded["program"].red
    gaps = dict(bench_trace.breakdown(red)["idle_gaps"])
    assert {"fl.eval", "fl.host_sync"} <= set(gaps)
    assert gaps["bench.segment"] < 0.1 * sum(gaps.values())


def test_a_traced_run_breaks_its_idle_down_by_program_span(recorded):
    """`bench/run.py`'s breakdown names the innermost ``fl.*`` span of
    each idle gap, and splits the same idle time as the ``bench.*``
    reduction; without the program's view it falls back to that."""
    from bench import run as bench_run
    before = bench_trace.breakdown(recorded["trace"])["idle_gaps"]
    after = bench_run.breakdown(recorded)
    gaps = dict(after["idle_gaps"])
    assert {"fl.eval", "fl.host_sync", "fl.prepare"} <= set(gaps)
    assert sum(gaps.values()) == pytest.approx(
        sum(v for _, v in before), rel=1e-12)
    assert after["device_ops"] == bench_trace.breakdown(
        recorded["trace"])["device_ops"]
    alone = dict(recorded, program=None)
    assert bench_run.breakdown(alone)["idle_gaps"] == before


def test_recorded_trust_kernel_is_one_op(recorded):
    red = recorded["trace"]
    assert len(bench_trace.op_names(red, trust_kernel_roofline.PATTERN)) == 1
    seconds, calls = bench_trace.op_seconds(red, trust_kernel_roofline.PATTERN)
    assert calls == 30 and seconds > 0
    scope = program_trace.op_scopes(RECORDED)
    name, = bench_trace.op_names(red, trust_kernel_roofline.PATTERN)
    assert "/fl.aggregate/" in scope[name]


def test_a_run_finds_its_own_trace_in_the_cell_work_dir(recorded, tmp_path,
                                                      monkeypatch):
    """The readers get only the run's context: the program's view is read
    from the trace `bench/run.py` left in the cell's work directory, once,
    and only where its window is the one the run reduced."""
    from bench import harness as h
    monkeypatch.setattr(h, "WORK", str(tmp_path))
    where = program_trace.trace_dir("paper-v.scan")
    os.makedirs(os.path.join(where, "plugins", "profile", "run"))
    shutil.copy(RECORDED, os.path.join(where, "plugins", "profile", "run",
                                       "host.xplane.pb"))
    ctx = {"workload": "paper-v.scan", "trace": recorded["trace"],
           "window": recorded["window"]}
    assert eval_ms_per_segment.read(ctx) == eval_ms_per_segment.read(
        recorded)
    view = ctx["program"]
    assert view is not None and sgd_device_ms.read(ctx) > 0
    assert program_trace.of(ctx) is view
    # another run's reduction: the file there is not its trace
    t0, t1 = recorded["trace"].window_ns
    other = dataclasses.replace(recorded["trace"], window_ns=(t0 + 1, t1))
    ctx = {"workload": "paper-v.scan", "trace": other,
           "window": recorded["window"]}
    assert eval_ms_per_segment.read(ctx) is None
    # no trace directory at all
    ctx = {"workload": "none", "trace": recorded["trace"],
           "window": recorded["window"]}
    assert eval_ms_per_segment.read(ctx) is None
