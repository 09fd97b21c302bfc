"""Coded gradient aggregation: the fused/pjit path, the protocol oracle, and
ground truth must agree exactly under any <= s straggler pattern."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # image without hypothesis: seeded-random fallback
    from _hypothesis_compat import given, settings, st

from repro.core import Decoder, build_cyclic, build_group_based, build_heter_aware
from repro.core.aggregator import (
    fused_coded_value_and_grad,
    make_plan,
    pack_coded_batch,
    protocol_reference,
    slot_weights,
    uniform_weights,
)


def _toy_loss(params, batch):
    pred = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
    return jnp.mean((pred[:, 0] - batch["y"]) ** 2)


def _setup(k, mb=3, d=4, h=8, seed=0):
    r = np.random.default_rng(seed)
    params = {
        "w1": jnp.asarray(r.normal(size=(d, h)), jnp.float32),
        "w2": jnp.asarray(r.normal(size=(h, 1)), jnp.float32),
    }
    pb = {
        "x": jnp.asarray(r.normal(size=(k, mb, d)), jnp.float32),
        "y": jnp.asarray(r.normal(size=(k, mb)), jnp.float32),
    }
    gt = jax.tree.map(jnp.zeros_like, params)
    for j in range(k):
        g = jax.grad(_toy_loss)(params, jax.tree.map(lambda x: x[j], pb))
        gt = jax.tree.map(lambda a, b: a + b / k, gt, g)
    return params, pb, gt


def _trees_close(a, b, atol=2e-5, rtol=2e-4):
    # Alg.1 coefficients from near-singular C_i can reach |B| ~ 1e2-1e3,
    # amplifying f32 rounding; correctness is relative, not absolute
    return all(
        np.allclose(x, y, atol=atol, rtol=rtol)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


@pytest.mark.parametrize("builder", ["heter", "cyclic", "group"])
def test_fused_equals_truth_all_patterns(builder):
    m, s, k = 4, 1, 8
    c = [1.0, 2.0, 3.0, 2.0]
    sch = {
        "heter": lambda: build_heter_aware(k, s, c, rng=0),
        "cyclic": lambda: build_cyclic(m, s, rng=0),
        "group": lambda: build_group_based(k, s, c, rng=0),
    }[builder]()
    params, pb, gt = _setup(sch.k)
    plan = make_plan(sch)
    dec = Decoder(sch)
    vg = jax.jit(fused_coded_value_and_grad(_toy_loss))
    sb = pack_coded_batch(pb, plan)
    for dead in itertools.combinations(range(sch.m), s):
        avail = [i for i in range(sch.m) if i not in dead]
        w = slot_weights(plan, dec.decode_vector(avail))
        _, grads = vg(params, sb, jnp.asarray(w))
        assert _trees_close(grads, gt), f"pattern {dead} decodes wrong"


def test_protocol_reference_equals_truth():
    sch = build_heter_aware(8, 1, [1, 2, 3, 2], rng=0)
    params, pb, gt = _setup(8)
    dec, coded = protocol_reference(_toy_loss, params, pb, sch, available=[0, 2, 3])
    assert _trees_close(dec, gt)
    # the wire tensors themselves satisfy the encode definition
    grad_fn = jax.grad(_toy_loss)
    pgs = [grad_fn(params, jax.tree.map(lambda x, j=j: x[j], pb)) for j in range(8)]
    for w_idx in range(sch.m):
        expect = jax.tree.map(jnp.zeros_like, params)
        for j in sch.allocation.partitions[w_idx]:
            expect = jax.tree.map(lambda a, g, b=float(sch.B[w_idx, j]): a + b * g, expect, pgs[j])
        assert _trees_close(coded[w_idx], expect)


@given(st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_fused_equals_protocol_random_schemes(seed):
    r = np.random.default_rng(seed)
    m = int(r.integers(3, 6))
    s = int(r.integers(1, min(m - 1, 2) + 1))
    k = m * int(r.integers(1, 3))
    c = r.uniform(0.5, 3.0, m)
    sch = build_heter_aware(k, s, c, rng=seed)
    params, pb, gt = _setup(k, seed=seed)
    dead = sorted(r.choice(m, size=s, replace=False).tolist())
    avail = [i for i in range(m) if i not in dead]
    ref, _ = protocol_reference(_toy_loss, params, pb, sch, available=avail)
    plan = make_plan(sch)
    w = slot_weights(plan, Decoder(sch).decode_vector(avail))
    _, grads = jax.jit(fused_coded_value_and_grad(_toy_loss))(
        params, pack_coded_batch(pb, plan), jnp.asarray(w)
    )
    # wider tolerance: random seeds can draw near-singular C_i whose large
    # B coefficients amplify f32 rounding (see _trees_close note)
    assert _trees_close(grads, ref, atol=1e-4, rtol=1e-3)
    assert _trees_close(grads, gt, atol=1e-4, rtol=1e-3)


def test_uniform_weights_is_plain_dp():
    """naive scheme + all workers == classic data parallelism."""
    from repro.core import build_naive

    sch = build_naive(6)
    params, pb, gt = _setup(6)
    plan = make_plan(sch)
    w = uniform_weights(plan)
    _, grads = jax.jit(fused_coded_value_and_grad(_toy_loss))(
        params, pack_coded_batch(pb, plan), jnp.asarray(w)
    )
    assert _trees_close(grads, gt)


def test_plan_padding_stable_shapes():
    """Fixed slot capacity: rebuilding with different c keeps shapes."""
    c1, c2 = [1, 1, 1, 1], [1, 4, 2, 3]
    s1 = build_heter_aware(8, 1, c1, rng=0)
    s2 = build_heter_aware(8, 1, c2, rng=0)
    n_slots = 8
    p1, p2 = make_plan(s1, n_slots), make_plan(s2, n_slots)
    assert p1.slot_pids.shape == p2.slot_pids.shape == (4, n_slots)
    params, pb, gt = _setup(8)
    vg = jax.jit(fused_coded_value_and_grad(_toy_loss))
    for sch, plan in [(s1, p1), (s2, p2)]:
        w = slot_weights(plan, Decoder(sch).decode_vector(range(4)))
        _, grads = vg(params, pack_coded_batch(pb, plan), jnp.asarray(w))
        assert _trees_close(grads, gt)


def test_wire_ravel_roundtrip_lane_padded():
    """The spmd wire flattens leaves in tree order, zero-pads each ≥2-D
    leaf's minor dim to 128 lanes, and its unraveler restores the tree."""
    from repro.core.aggregator import wire_ravel, wire_unraveler

    r = np.random.default_rng(3)
    tree = {
        "a": jnp.asarray(r.normal(size=(3, 130)), jnp.bfloat16),  # pads to 256
        "b": jnp.asarray(r.normal(size=(2, 5, 128)), jnp.float32),  # aligned
        "c": jnp.asarray(r.normal(size=(7,)), jnp.float32),  # 1-D: no pad
        "d": jnp.asarray(1.5, jnp.float32),  # scalar
    }
    flat = wire_ravel(tree)
    unravel, D = wire_unraveler(tree)
    assert flat.dtype == jnp.float32 and flat.shape == (D,)
    assert D == 3 * 256 + 2 * 5 * 128 + 7 + 1
    a_rows = np.asarray(flat[: 3 * 256]).reshape(3, 256)
    assert np.all(a_rows[:, 130:] == 0)  # pad lanes carry zeros
    np.testing.assert_array_equal(a_rows[:, :130], np.asarray(tree["a"], np.float32))
    back = unravel(flat)
    for k in tree:
        assert back[k].dtype == tree[k].dtype and back[k].shape == tree[k].shape
        np.testing.assert_array_equal(np.asarray(back[k]), np.asarray(tree[k]))
