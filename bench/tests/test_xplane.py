"""The trace reduction on a small recorded trace with known intervals."""

from pathlib import Path

import pytest
from jax.profiler import ProfileData

import xplane

TRACE = Path(__file__).with_name("small_trace.textproto")


@pytest.fixture(scope="module")
def red():
    return xplane.reduce(ProfileData.from_text_proto(TRACE.read_text()))


def test_window_is_the_traced_annotation(red):
    assert red["chips"] == 1
    assert red["window_ns"] == 100_000


def test_busy_is_the_union_of_op_intervals(red):
    # ops 10-40 and 30-50 overlap (union 10-50), 60-70, 80-95, and one op
    # at 120-130 outside the window: busy 40 + 10 + 15
    assert red["busy_ns"] == 65_000


def test_ops_and_modules_are_summed_by_name_inside_the_window(red):
    assert red["ops_ns"] == {"fusion.1": 30_000, "convolution.2": 20_000,
                             "all-gather.3": 10_000, "fusion.4": 15_000}
    assert red["modules_ns"] == {"jit_step_fn": 85_000}
    assert red["module_runs"] == {"jit_step_fn": 1}


def test_collective_time_and_the_part_with_no_compute_beside_it(red):
    # the all-gather at 60-70 has no other op beside it
    assert red["collective_ns"] == 10_000
    assert red["collective_exposed_ns"] == 10_000


def test_idle_gaps_are_labelled_by_the_host(red):
    # gaps 0-10 (host.tick open), 50-60 (bench.step only), 70-80 (none
    # but the window), 95-100 (bench.data)
    assert red["gaps"] == [("host.tick", 10_000), ("bench.step", 10_000),
                           ("none", 10_000), ("bench.data", 5_000)]


def test_breakdown_in_seconds(red):
    b = xplane.breakdown(red, top=2)
    assert b["device_ops"] == [["fusion.1", 30e-6], ["convolution.2", 20e-6]]
    assert b["idle_gaps"] == [["host.tick", 10e-6], ["bench.step", 10e-6]]


def test_union_merges_touching_and_nested_intervals():
    assert xplane.union([(5, 6), (0, 2), (2, 3), (1, 1.5)]) == [(0, 3), (5, 6)]
