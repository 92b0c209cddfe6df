"""The trace reduction: on hand-made events, and on a trimmed chip trace
recorded from `bench/run.py --trace 1` (paper-v.scan, TPU v5 lite: three
10-round segments of the traced window)."""
import os

import pytest

from bench import trace as bench_trace
from bench.metrics import round_device_ms, trust_kernel_roofline

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "paper-v.scan.xplane.pb")


def _events():
    # one device; window 0..100; ops at 10-20, 15-30 (overlap), 60-70
    devices = [[("fusion.1", 10, 20), ("fusion.2", 15, 30),
                ("_global_kernel", 60, 70), ("fusion.1", 120, 130)]]
    spans = [("bench.window", 0, 100), ("bench.segment", 5, 35),
             ("bench.segment", 50, 80), ("bench.restore", 40, 50)]
    return devices, spans


def test_busy_is_the_union_of_operations_in_the_window():
    red = bench_trace.reduce(*_events())
    assert red.busy_ns == 30          # 10-30 and 60-70; 120-130 is outside
    assert red.window_ns == (0, 100)
    assert red.op_ns["fusion.1"] == 10 and red.op_count["fusion.1"] == 1


def test_idle_gaps_go_to_the_open_host_span():
    red = bench_trace.reduce(*_events())
    # idle 0-10, 30-60 and 70-100: 0-5 before the first segment, 5-10
    # and 30-35 in it, 35-40 between, 40-50 in the restore, 50-60 and
    # 70-80 in the second segment, 80-100 after it
    assert red.idle_by_span == {"outside bench spans": 5 + 5 + 20,
                                "bench.segment": 5 + 5 + 10 + 10,
                                "bench.restore": 10}
    assert red.busy_within(red.spans["bench.segment"]) == pytest.approx(
        30e-9)


def test_kernel_time_by_name():
    red = bench_trace.reduce(*_events())
    assert bench_trace.op_seconds(red, r"_global_kernel") == (10e-9, 1)


def test_device_time_of_one_program_leaves_the_others_out():
    devices, spans = _events()
    modules = [("jit_run_k(7)", 8, 32), ("jit_evaluate(9)", 58, 72)]
    red = bench_trace.reduce(devices, spans, modules)
    assert red.module_runs(round_device_ms.SCAN_MODULE) == [(8, 32)]
    ctx = {"trace": red, "window": {"rounds": [(0, 1)] * 4}}
    # 20 ns of the 30 busy lie in the scan's run: 5 ns a round
    assert round_device_ms.read(ctx) == pytest.approx(20e-9 * 1e3 / 4)
    red = bench_trace.reduce(devices, spans)
    assert round_device_ms.read(dict(ctx, trace=red)) is None


def test_the_trust_kernel_is_read_by_its_name_alone():
    op = '%trust_aggregate_global.{} = f32[1,128] custom-call(), ' \
         'custom_call_target="tpu_custom_call"'
    other = '%other_kernel.3 = f32[8] custom-call(), ' \
            'custom_call_target="tpu_custom_call"'
    spans = [("bench.window", 0, 100)]
    ctx = {"window": {"rounds": [(0, 1)]}, "members": [4],
           "spec": {"clustering": {"n_clusters": 4}},
           "dims": {"dim": 784, "hidden": 200, "n_classes": 10},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    red = bench_trace.reduce([[(op.format(1), 0, 10), (other, 10, 90)]],
                             spans)
    assert bench_trace.op_seconds(red, trust_kernel_roofline.PATTERN) == (
        10e-9, 1)
    red = bench_trace.reduce([[(op.format(1), 0, 10),
                               (op.format(2), 20, 30)]], spans)
    with pytest.raises(ValueError):
        trust_kernel_roofline.read(dict(ctx, trace=red))


def test_breakdown_lists_at_most_ten():
    devices = [[(f"op{i}", 2 * i, 2 * i + 1) for i in range(40)]]
    red = bench_trace.reduce(devices, [("bench.window", 0, 100)])
    bd = bench_trace.breakdown(red)
    assert len(bd["device_ops"]) == 10 and len(bd["idle_gaps"]) <= 10


def test_missing_window_is_an_error():
    with pytest.raises(ValueError):
        bench_trace.reduce([[("a", 0, 1)]], [])


def test_recorded_chip_trace():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(RECORDED)
    red = bench_trace.reduce(*bench_trace.events(pd))
    assert red.n_devices == 1
    assert 0 < red.busy_s < red.window_s
    assert len(red.spans["bench.segment"]) == 3
    # the trust kernel runs once a round
    seconds, calls = bench_trace.op_seconds(red,
                                            trust_kernel_roofline.PATTERN)
    assert calls == 30 and 0 < seconds < red.busy_s
    bd = bench_trace.breakdown(red)
    assert any("trust_aggregate_global" in n for n, _ in bd["device_ops"])
    assert dict(bd["idle_gaps"])["bench.segment"] > 0
