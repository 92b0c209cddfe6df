"""Count functions and the table of peaks, against hand arithmetic."""
import json

import numpy as np
import pytest

from bench import harness as h
from bench import run as bench_run
from bench.metrics import fleet_mfu, trust_kernel_roofline as tkr

PAPER_V = {"dim": 784, "hidden": 200, "n_classes": 10}
FEMNIST = {"dim": 784, "hidden": 200, "n_classes": 62}
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def test_parameter_counts_match_the_configurations():
    assert tkr.n_params(PAPER_V) == 159_010
    assert tkr.n_params(FEMNIST) == 169_462
    assert h.dims(h.config("paper-v")) == PAPER_V


@pytest.mark.parametrize("dims, n_mm", [(PAPER_V, 158_800),
                                        (FEMNIST, 169_200)])
def test_model_flops_per_sample(dims, n_mm):
    assert fleet_mfu.n_mm(dims) == n_mm
    # a = 5 steps of 64 samples on 4 members, then one loss each
    assert fleet_mfu.round_flops(5, 4, 64, dims) == (6 * 5 + 2) * 4 * 64 * n_mm
    assert fleet_mfu.eval_flops(4096 + 1024, dims) == 2 * 5120 * n_mm


def test_model_flops_count_real_members_only():
    members = [4, 2]               # real members of clusters 0 and 1
    rounds = [(0, 5), (1, 3), (0, 1)]
    got = fleet_mfu.model_flops(rounds, members, 64, 2, 5120, PAPER_V)
    n = 158_800
    want = ((32 * 4 + 20 * 2 + 8 * 4) * 64 + 2 * 2 * 5120) * n
    assert got == want


def test_trust_kernel_work_at_paper_v_shapes():
    m, b, n = 4, 4, 159_010
    assert tkr.kernel_flops(m, b, n) == 2 * 8 * 159_010
    assert tkr.kernel_bytes(m, b, n) == 4 * (8 * 159_010 + 159_010)
    # the stream bounds it: 5.7 MB at 819 GB/s
    want = 4 * 9 * 159_010 / 819e9
    assert tkr.least_seconds(m, b, n, V5E) == pytest.approx(want)


def test_padded_rows_do_not_count():
    # a cluster padded to 7 rows with 4 real members streams 3 rows that
    # are not the work: the least time is the 4-member one
    assert tkr.least_seconds(4, 4, 1000, V5E) < tkr.least_seconds(
        7, 4, 1000, V5E)
    ctx_rounds = [(0, 5)]
    members = [4]
    got = tkr.least_seconds(members[ctx_rounds[0][0]], 4, 1000, V5E)
    assert got == tkr.least_seconds(4, 4, 1000, V5E)


def test_peaks_are_keyed_by_device_kind():
    table = h.load_json("peaks.json")
    assert "TPU v5" in table["source"] or "v5e" in table["source"]
    v5e = bench_run.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        bench_run.peaks("TPU v9 imaginary")
    json.dumps(table)


def test_writer_shards_sum_to_the_data_and_none_is_empty():
    import jax

    from bench import data as bench_data
    parts = bench_data.writer_partition(jax.random.key(3), 5000, 300,
                                        mean=18.46, std=7.24)
    rows = np.concatenate(parts)
    assert len(parts) == 300 and min(len(p) for p in parts) >= 1
    assert sorted(rows.tolist()) == list(range(5000))
