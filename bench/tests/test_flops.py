"""The references' FLOP counts against hand counts, and the count as a lower
bound on what XLA computes."""

import jax
import jax.numpy as jnp
import pytest

import check
import harness
from tiny import TINY_MODEL


def _ref(family):
    return harness.load_module(harness.BENCH / "references" / f"{family}.py")


def test_dense_forward_by_hand():
    # d=64, 4 heads of 16, 2 kv heads, ff 128, vocab 256, 2 layers, seq 8:
    # per position: q 64·64, k and v 64·32 each, o 64·64, mlp 3·64·128
    # multiply-adds; attention 2·(t+1)·64 for QK^T and PV; head 64·256 over
    # the 7 positions that have a next token
    per_pos = 2 * (64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128)
    attn = sum(2 * 2 * 64 * (t + 1) for t in range(8))
    want = 2 * (8 * per_pos + attn) + 2 * 64 * 256 * 7
    assert _ref("dense").forward_flops(TINY_MODEL["dense"], 8) == want


def test_ssm_forward_by_hand():
    # d=64, d_inner 128, 4 heads, state 16, 1 group, conv 4, 2 layers:
    # in_proj 64 -> 2·128 + 2·16 + 4, out_proj 128 -> 64, conv 4 taps over
    # 128 + 32 channels, scan x⊗B and h·C: 128·16 each
    per_pos = 2 * (64 * 292 + 128 * 64 + 4 * 160 + 2 * 128 * 16)
    want = 2 * 8 * per_pos + 2 * 64 * 256 * 7
    assert _ref("ssm").forward_flops(TINY_MODEL["ssm"], 8) == want


@pytest.mark.parametrize("family", ["dense", "ssm"])
def test_count_is_a_lower_bound_of_the_compiled_forward(family):
    """mfu cannot pass 100 %: the count is at most what XLA computes for the
    plain forward of one layer stack without replication or remat (the
    program runs every unique sequence at least once forward and twice
    backward)."""
    cfg = {**TINY_MODEL[family], "n_layers": 1}
    ref = _ref(family)
    params = ref.init(cfg, jax.random.PRNGKey(0), jnp.float32)
    toks = jnp.zeros((3, 32), jnp.int32)
    f = jax.jit(lambda p, t: ref.seq_losses(cfg, p, t, check.mm_f32))
    cost = f.lower(params, toks).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert ref.forward_flops(cfg, 32) * 3 <= cost["flops"]
