"""The reductions that read the program's own names: device self time by
scope and idle time by program span, on a recorded trace with two host
threads and while ops spanning their bodies; and the metric readers that
report them."""

import json
from pathlib import Path

import pytest
from jax.profiler import ProfileData

import harness
import scopes
import xplane

TRACE = Path(__file__).with_name("spans_trace.textproto")
PROGRAM_SPANS = {"step", "step.resolve", "step.observe", "phase.upload",
                 "phase.dispatch", "phase.readback", "prefetch.wait", "prefetch.upload"}


@pytest.fixture(scope="module")
def pd():
    return ProfileData.from_text_proto(TRACE.read_text())


@pytest.fixture(scope="module")
def stacks():
    return scopes.op_scopes(ProfileData.text_proto_to_serialized_xspace(TRACE.read_text()))


def test_op_scopes_read_the_name_stack_from_the_op_metadata(stacks):
    # fusion.9 holds its stack by reference to a stat metadata's name; the
    # while ops and copy.10 have none
    by_op = {xplane.op_name(name): stack for name, stack in stacks.items()}
    assert sorted(by_op) == ["fusion.1", "fusion.3", "fusion.4", "fusion.5",
                             "fusion.7", "fusion.8", "fusion.9"]
    assert by_op["fusion.9"] == "jit(step_fn)/adamw/sub:"
    assert by_op["fusion.5"] == "jit(step_fn)/jvp(head_loss)/dot_general:"
    assert "%fusion.5 = f32[8]{0} fusion()" in stacks  # keyed by the whole event name


@pytest.mark.parametrize("stack, part", [
    ("jit(step_fn)/jvp()/while/body/closed_call/layers/dot_general:", "forward"),
    ("jit(step_fn)/jvp(head_loss)/dot_general:", "forward"),
    ("jit(step_fn)/jvp(embed)/gather:", "forward"),
    ("jit(step_fn)/transpose(jvp(head_loss))/dot_general:", "backward"),
    ("jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/layers/dot_general:", "backward"),
    ("jit(step_fn)/transpose(jvp())/broadcast_in_dim:", "backward"),
    ("jit(step_fn)/adamw/sub:", "adamw"),
    ("jit(step_fn)/coded_pack/gather:", "coded_pack"),
    ("jit(step_fn)/jvp()/while:", "unscoped"),
    ("params['embed']:", "unscoped"),
    ("opt.mu['layers']", "unscoped"),
    ("", "unscoped"),
])
def test_phase_of_a_name_stack(stack, part):
    assert scopes.phase(stack) == part


def test_self_time_counts_a_while_body_once():
    # a loop 0-20 around body ops 1-10 and 10-19, then an op 20-25
    evs = [("while.1", 0, 20), ("fusion.2", 1, 10), ("fusion.3", 10, 19), ("fusion.4", 20, 25)]
    assert scopes.self_ns(evs) == [2, 9, 9, 5]


def test_scope_self_times_add_up_to_busy(pd, stacks):
    # window 0-200 us; fusion.3 at 205 us lies outside it.  forward:
    # fusion.3 9 + fusion.4 8 + fusion.5 5 + 5; backward fusion.7 9 +
    # fusion.8 14 (remat recompute included); adamw 11 + 5; coded_pack
    # 5 + 5; unscoped: while.2 20 - 17, while.6 25 - 23, copy.10 2
    by = scopes.scope_ns(pd, stacks)
    assert by == {"forward": 27_000, "backward": 23_000, "adamw": 16_000,
                  "coded_pack": 10_000, "unscoped": 7_000}
    assert sum(by.values()) == xplane.reduce(pd)["busy_ns"]


def test_without_scopes_every_op_is_unscoped(pd):
    by = scopes.scope_ns(pd, {})
    assert by["unscoped"] == xplane.reduce(pd)["busy_ns"]


def test_idle_is_labelled_by_the_dispatching_thread_alone(pd):
    # gaps 0-40 (prefetch.wait), 106-108 (phase.readback), 110-140
    # (prefetch.wait), 150-185 (only step open here; the prefetch thread's
    # prefetch.upload and the benchmark's bench.step do not count), 190-200
    # (no program span)
    assert scopes.idle_by_span(pd, PROGRAM_SPANS) == {
        "prefetch.wait": 70_000, "phase.readback": 2_000, "step": 35_000, "none": 10_000}
    assert sum(ns for _, ns in xplane.reduce(pd)["gaps"]) == 117_000


def test_idle_by_span_is_none_without_a_step_span(pd):
    assert scopes.idle_by_span(pd, PROGRAM_SPANS - {"step"}) is None


def _span(name, t0, t1):
    return {"kind": "span", "name": name, "t0": t0, "t1": t1, "clock": "wall"}


CTX = {
    "steps_s": [0.5, 0.5], "profiled_steps": 2,
    "device_by_scope": {"forward": 20e6, "backward": 60e6, "adamw": 4e6,
                        "coded_pack": 1e6, "unscoped": 0.0},
    "spans": [_span("phase.upload", 0.0, 0.001), _span("phase.dispatch", 0.001, 0.004),
              _span("phase.readback", 0.004, 0.5), _span("prefetch.wait", 0.5, 0.502),
              _span("phase.upload", 1.0, 1.002), _span("phase.dispatch", 1.002, 1.004),
              _span("prefetch.wait", 1.5, 1.504)],
}


@pytest.mark.parametrize("metric, value", [
    ("fwd_device_ms", 10.0), ("bwd_device_ms", 30.0), ("adamw_device_ms", 2.0),
    ("dispatch_ms", 4.0), ("prefetch_wait_ms", 3.0),
])
def test_metric_readers_on_a_hand_made_ctx(metric, value):
    reader = harness.load_module(harness.BENCH / "metrics" / f"{metric}.py")
    assert reader.read(CTX) == pytest.approx(value)


@pytest.mark.parametrize("metric", ["fwd_device_ms", "bwd_device_ms", "adamw_device_ms",
                                    "dispatch_ms", "prefetch_wait_ms"])
def test_metric_readers_find_nothing_where_the_program_has_no_names(metric):
    # a program without scopes or these spans: every op unscoped, no
    # phase.dispatch or prefetch.wait span; or no trace at all
    bare = {"steps_s": [0.5], "profiled_steps": 1,
            "device_by_scope": {"forward": 0.0, "backward": 0.0, "adamw": 0.0,
                                "coded_pack": 0.0, "unscoped": 9e6},
            "spans": [_span("phase.upload", 0.0, 0.001), _span("phase.fused", 0.001, 0.5)]}
    reader = harness.load_module(harness.BENCH / "metrics" / f"{metric}.py")
    assert reader.read(bare) is None
    assert reader.read({"steps_s": [0.5]}) is None


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    (tmp_path / "t.xplane.pb").write_bytes(ProfileData.text_proto_to_serialized_xspace(TRACE.read_text()))
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    return tmp_path


def test_device_readers_read_the_traced_runs_trace_once(trace_dir, pd):
    ctx = {"trace": xplane.reduce(pd), "profiled_steps": 1}
    fwd = harness.load_module(harness.BENCH / "metrics" / "fwd_device_ms.py")
    assert fwd.read(ctx) == pytest.approx(0.027)
    (trace_dir / "t.xplane.pb").unlink()  # the second reader uses what the first kept
    bwd = harness.load_module(harness.BENCH / "metrics" / "bwd_device_ms.py")
    assert bwd.read(ctx) == pytest.approx(0.023)


def test_scopes_cli_prints_both_per_traced_step(trace_dir, capsys):
    assert scopes.main([str(trace_dir), "--steps", "2"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["device_by_scope"] == pytest.approx(
        {"forward": 13.5e-6, "backward": 11.5e-6, "adamw": 8e-6, "coded_pack": 5e-6, "unscoped": 3.5e-6})
    assert out["idle_by_span"] == pytest.approx(
        {"prefetch.wait": 35e-6, "phase.readback": 1e-6, "step": 17.5e-6, "none": 5e-6})
