"""SPMD integration tests (subprocess with 8 fake devices, so this pytest
process keeps the single real CPU device — required by the dry-run rules)."""

import os
import subprocess
import sys

import pytest

_DRIVER = os.path.join(os.path.dirname(__file__), "spmd_driver.py")
_ENV = {**os.environ, "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src")}


def _run(check: str):
    proc = subprocess.run(
        [sys.executable, _DRIVER, check], env=_ENV, capture_output=True, text=True, timeout=560
    )
    assert proc.returncode == 0, f"{check} failed:\n{proc.stdout}\n{proc.stderr}"


def test_faithful_protocol_on_mesh():
    _run("faithful_spmd")


def test_fused_step_sharding_invariance():
    _run("fused_sharded")


def test_engine_spmd_backend_matches_reference():
    _run("engine_spmd")


def test_engine_spmd_backend_matches_reference_inexact():
    _run("engine_spmd_inexact")


def test_engine_spmd_wire_kernels_match_unfused():
    _run("engine_spmd_wire")


def test_engine_spmd_backend_matches_reference_after_membership_change():
    _run("engine_spmd_churn")


def test_dryrun_lowering_small_mesh():
    _run("dryrun_small")


def test_chip_smoke_four_chip_phase_on_fake_devices():
    _run("chip_smoke_spmd")
