"""The comparison that decides ``correct``, driven through a whole run at
test size on the CPU (the harness's look for a chip skipped): a sound run
passes the cell's limits; the control and each fault the cell can have
fail them."""

import time

import jax
import numpy as np
import pytest

import check
import control
import harness
import tiny
from repro.optim import adam
from repro.train import engine

WORKLOADS = ["smollm-360m.fused.group4", "mamba2-370m.fused.group4"]
SEED = 2**33 + 17


def _run(workload):
    cell = tiny.tiny_cell(workload)
    return harness.run(cell, SEED, 0.5, False, t_start=time.perf_counter(),
                       require_chip=False), cell


@pytest.mark.parametrize("workload", WORKLOADS)
def test_sound_run_is_correct(workload):
    result, _ = _run(workload)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("variant", sorted(control.VARIANTS))
@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_is_not_correct(workload, variant):
    """The FP8 control and the faults planted in the reference.  The FP8
    error grows with depth (the gradient's difference reads 0.1 at 2 SSD
    layers, 0.6 at 16 and 0.7 at the published 48), so the control runs 16
    layers deep."""
    cell = tiny.tiny_cell(workload, layers=16)
    vals = control.readings(cell, SEED, [variant])[variant]
    assert not check.verdict(vals, cell.limits)[0], vals
    if variant == "frozen":
        assert vals["grad_gap"] == vals["grad_diff"] == vals["change_gap"] == 1.0


def _frozen(self, params, grads, opt, step):
    return params, opt, adam.global_norm(grads), self._lr(step)


class _HalfBatch:
    """The second half of every step's partitions replaced by the first:
    half of the batch left out, the mean taken over the rest."""

    def __init__(self, inner):
        self.inner = inner

    def batch(self, step):
        out = self.inner.batch(step)
        k = out["tokens"].shape[0]
        return {key: np.concatenate([v[: k // 2], v[: k // 2]]) for key, v in out.items()}


def _doubled_largest_leaf(update):
    """The decoded gradient of the largest leaf (the embedding) doubled on
    its way into the optimizer."""

    def wrapped(params, grads, state, **kw):
        leaves, tdef = jax.tree.flatten(grads)
        big = max(range(len(leaves)), key=lambda i: leaves[i].size)
        leaves = [2 * x if i == big else x for i, x in enumerate(leaves)]
        return update(params, jax.tree.unflatten(tdef, leaves), state, **kw)

    return wrapped


FAULTS = {
    "state_unchanged": lambda mp: mp.setattr(engine.StepEngine, "_adamw", _frozen),
    "half_batch": lambda mp: mp.setattr(
        harness, "feed", lambda *a, _f=harness.feed: _HalfBatch(_f(*a))),
    "gradient_altered": lambda mp: mp.setattr(
        engine, "adamw_update", _doubled_largest_leaf(engine.adamw_update)),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", WORKLOADS)
def test_planted_fault_is_not_correct(workload, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    result, _ = _run(workload)
    assert not result["correct"], result["checks"]
