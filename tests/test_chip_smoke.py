"""chip_smoke.py off the chip: its device check refuses a CPU run, and its
main-path phase trains the reduced model through ``launch/train.main``."""

import json

import numpy as np
import pytest

import chip_smoke


def test_device_check_refuses_cpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_train_phase_reduced_loss_falls(capsys):
    summary = chip_smoke.train_phase(reduced=True, seq_len=32, steps=6)
    losses = np.asarray(summary["losses"])
    assert losses.shape == (6,) and np.all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    assert summary["n_params"] > 0 and len(summary["step_wall_s"]) == 6
    first = capsys.readouterr().out.splitlines()[0]
    assert json.loads(first)["device"]["platform"] == "cpu"
