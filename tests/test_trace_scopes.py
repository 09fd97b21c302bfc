"""Named device work (DESIGN.md §10): the fused step's parts carry name
scopes in their HLO ``op_name`` metadata, the backward under
``transpose(``, and the scopes change no compiled op."""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import CodingConfig, TrainConfig
from repro.core.straggler import NoStragglers
from repro.train.trainer import CodedTrainer


def _fused_step_hlo():
    """Optimized CPU HLO of a tiny LM's fused coded step."""
    from repro.configs import get_config
    from repro.data.pipeline import SyntheticData
    from repro.models.lm import build_model

    cfg = get_config("smollm-360m").reduced()
    tr = CodedTrainer(
        build_model(cfg), CodingConfig(scheme="heter_aware", s=1),
        TrainConfig(lr=1e-3, warmup_steps=2, total_steps=64), m=4, part_mb=1,
        straggler_model=NoStragglers(), true_speeds=np.linspace(1.0, 3.0, 4), rng=0,
    )
    eng = tr.engine
    state = tr.init_state(jax.random.PRNGKey(0))
    pbatch = jax.tree.map(
        jnp.asarray, SyntheticData(cfg, k=tr.k, part_mb=1, seq_len=16).batch(0))
    pids, coeff, mask = eng._device_plan()
    lowered = eng._fused_step.lower(
        state.params, state.opt, pbatch, jnp.ones((tr.m,), jnp.float32),
        eng._support_dev(None), pids, coeff, mask, jnp.asarray(0),
    )
    return lowered.compile().as_text()


@pytest.fixture(scope="module")
def fused_hlo():
    return _fused_step_hlo()


def test_fused_step_hlo_names_its_parts(fused_hlo):
    op_names = re.findall(r'op_name="([^"]*)"', fused_hlo)
    words = {w for name in op_names for w in re.findall(r"[\w.-]+", name)}
    for scope in ("coded_pack", "adamw", "embed", "layers", "head_loss"):
        assert scope in words, scope
    backward = [n for n in op_names if "transpose(" in n]
    assert backward and any("layers" in n for n in backward)
    assert not any("adamw" in n or "coded_pack" in n for n in backward)


def test_named_scopes_leave_compiled_ops_unchanged(fused_hlo, monkeypatch):
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _fused_step_hlo()

    def ops(text):  # the computations, without the metadata and source tables
        body = text[re.search(r"^(%|ENTRY)", text, re.M).start():]
        return re.sub(r",? metadata=\{[^}]*\}", "", body)

    assert "head_loss" not in bare
    assert ops(bare) == ops(fused_hlo)
