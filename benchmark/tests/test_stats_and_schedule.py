"""Percentile and open-loop schedule arithmetic; the generator's framing
against the program's own decoder."""
import numpy as np
import pytest

from benchmark.harness import loadgen, stats


def test_percentile_is_nearest_rank():
    v = list(range(1, 101))  # 1..100
    assert stats.percentile(v, 50) == 50
    assert stats.percentile(v, 95) == 95
    assert stats.percentile(v, 100) == 100
    assert stats.percentile([7.0], 95) == 7.0
    # a tail is a reading that happened, never an interpolation
    assert stats.percentile([1.0, 2.0, 10.0], 95) == 10.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_failed_requests_count_as_slowest():
    lat = np.array([5.0] * 90 + [12000.0] * 10)  # 10% failed -> sentinel
    assert stats.percentile(lat, 95) == 12000.0
    assert stats.percentile(lat, 50) == 5.0


def test_poisson_schedule_is_seeded_sorted_and_at_rate():
    a = loadgen.poisson_schedule(500.0, 10.0, np.random.default_rng(3))
    b = loadgen.poisson_schedule(500.0, 10.0, np.random.default_rng(3))
    c = loadgen.poisson_schedule(500.0, 10.0, np.random.default_rng(4))
    assert np.array_equal(a, b) and not np.array_equal(a[:50], c[:50])
    assert (np.diff(a) > 0).all() and a[0] >= 0 and a[-1] < 10.0
    # Poisson count: mean 5000, sd 71
    assert abs(len(a) - 5000) < 5 * 71
    gaps = np.diff(a)
    assert abs(gaps.mean() - 1 / 500.0) < 2e-4
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1  # exponential: cv 1


def test_short_draw_is_extended():
    rng = np.random.default_rng(0)
    t = loadgen.poisson_schedule(0.5, 400.0, rng)
    assert t[-1] < 400.0 and len(t) > 100


def test_rows_mix_shares():
    mix = {"1": 0.5, "2": 0.2, "4": 0.15, "8": 0.1, "16": 0.05}
    rows = loadgen.draw_rows(mix, 40000, np.random.default_rng(1))
    for k, share in mix.items():
        assert abs((rows == int(k)).mean() - share) < 0.01
    assert abs(rows.mean() - 3.1) < 0.1


def test_plan_is_a_function_of_the_seed():
    spec = {"seed": 5, "rate": 100.0, "seconds": 2.0, "seq": 16,
            "rows_mix": {"1": 0.5, "4": 0.5}, "token_low": 10,
            "token_high": 100}
    due1, rows1, frames1 = loadgen.plan(spec)
    due2, rows2, frames2 = loadgen.plan(spec)
    assert np.array_equal(due1, due2) and np.array_equal(rows1, rows2)
    assert frames1 == frames2


def test_framing_matches_the_programs_decoder():
    from paddle_tpu.inference import wire_spec as ws

    rows = np.arange(6, dtype=np.int32).reshape(2, 3)
    frame = loadgen.encode_request(rows)
    assert frame == ws.build_request(ws.CMD_INFER, ws.encode_arrays([rows]))
    assert loadgen.STATUS_OK == ws.STATUS_OK
