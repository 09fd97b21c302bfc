"""Device-resident data path (DESIGN.md §6) equivalence suite.

The contract under test: the in-jit encode over the unique batch (device
path) and the flat-gradient Pallas decode produce what the pre-§6 host
numpy pack over the replicated slots / per-leaf tree decode produced —
across every registered scheme, exact and inexact decodes (DecodeOutcome
with support masks), on the backends runnable in-process (fused
device/host + reference; the spmd leg runs on a real mesh in
tests/spmd_driver.py).  Also: the engine's
device-resident plan cache invalidates on rebalance, and the trainer's
double-buffered prefetch loop is step-for-step identical to the manual
loop.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import CodingConfig, TrainConfig
from repro.core import Codec, get_scheme, scheme_names
from repro.core.aggregator import slot_weights_device, unique_batch_device
from repro.train.engine import StepEngine

_C4 = [1.0, 2.0, 3.0, 2.0]


class _ToyModel:
    d, h = 4, 8

    def init(self, rng):
        k1, k2 = jax.random.split(rng)
        return {
            "w1": jax.random.normal(k1, (self.d, self.h), jnp.float32),
            "w2": jax.random.normal(k2, (self.h, 1), jnp.float32),
        }

    def weighted_loss(self, params, batch):
        pred = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
        return jnp.sum((pred[:, 0] - batch["y"]) ** 2 * batch["weight"])


def _partition_batch(k, mb=3, d=4, seed=0):
    r = np.random.default_rng(seed)
    return {
        "x": r.normal(size=(k, mb, d)).astype(np.float32),
        "y": r.normal(size=(k, mb)).astype(np.float32),
    }


def _codec(name, seed=0):
    return Codec(get_scheme(name, m=4, k=8, s=1, c=_C4, rng=seed))


def _tree_close(ta, tb, atol=3e-5, rtol=3e-4):
    for x, y in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=atol, rtol=rtol)


# ---------------------------------------------------------------------------
# pack + weights: device twins == host originals, every scheme
# ---------------------------------------------------------------------------


class _RowsModel(_ToyModel):
    """_ToyModel that records the rows of every batch it is traced on."""

    def __init__(self):
        self.rows = []

    def weighted_loss(self, params, batch):
        self.rows.append(batch["x"].shape[0])
        return super().weighted_loss(params, batch)


@pytest.mark.parametrize("name", sorted(scheme_names()))
def test_device_pack_matches_host_flat_batch(name):
    """The in-jit encode hands the model the k·mb unique sequences (the
    partition-major batch reshaped), each partition weighted by the sum of
    the host replicated pack's weights over that partition's slots — for
    exact AND partial-work decodes."""
    codec = _codec(name)
    model = _RowsModel()
    eng = StepEngine(model, TrainConfig(), codec, backend="fused")
    host_eng = StepEngine(model, TrainConfig(), codec, backend="fused", host_pack=True)
    pb = _partition_batch(codec.k)
    mb = pb["x"].shape[1]
    rng = np.random.default_rng(3)
    outcome = codec.decode_outcome(range(codec.m))
    a = outcome.a
    support = (rng.uniform(size=(codec.m, codec.k)) < 0.7).astype(np.float64)
    slot_pids = np.repeat(codec.plan.slot_pids.reshape(-1), mb)  # pid of each host row
    for sup in [None, support]:
        host = host_eng._flat_batch(pb, a, sup)
        pids = jnp.asarray(codec.plan.slot_pids)
        sup_dev = (
            jnp.ones((codec.m, codec.k), jnp.float32) if sup is None
            else jnp.asarray(sup, jnp.float32)
        )
        w = slot_weights_device(
            jnp.asarray(a, jnp.float32), sup_dev,
            jnp.asarray(codec.plan.slot_coeff), jnp.asarray(codec.plan.slot_mask),
            pids, codec.k,
        )
        dev = unique_batch_device({k: jnp.asarray(v) for k, v in pb.items()}, pids, w, codec.k)
        assert set(dev) == set(host)
        for key in pb:
            np.testing.assert_array_equal(
                np.asarray(dev[key]), pb[key].reshape((codec.k * mb,) + pb[key].shape[2:]),
                err_msg=f"{name}/{key}",
            )
        host_c = np.array([host["weight"][slot_pids == j].sum() for j in range(codec.k)])
        np.testing.assert_allclose(
            np.asarray(dev["weight"]).reshape(codec.k, mb).sum(axis=1), host_c,
            atol=1e-7, rtol=1e-6, err_msg=f"{name}/weight",
        )
        eng.gradients(model.init(jax.random.PRNGKey(0)), pb, dataclasses.replace(outcome, support=sup))
    # traced once (the shapes do not depend on the decode): k·mb rows, not m·n_slots·mb
    assert model.rows == [codec.k * mb], name


@pytest.mark.parametrize("name", sorted(scheme_names()))
def test_device_gradients_match_host_and_reference(name):
    """Acceptance: fused device-pack grads == fused host-pack grads ==
    paper-protocol oracle, for every registered scheme (exact decode)."""
    codec_d, codec_h, codec_r = _codec(name), _codec(name), _codec(name)
    model = _ToyModel()
    params = model.init(jax.random.PRNGKey(2))
    pb = _partition_batch(codec_d.k, seed=5)
    outcome = codec_d.decode_outcome(range(codec_d.m))
    tc = TrainConfig()
    g_dev = StepEngine(model, tc, codec_d, backend="fused").gradients(params, pb, outcome)
    g_host = StepEngine(model, tc, codec_h, backend="fused", host_pack=True).gradients(
        params, pb, codec_h.decode_outcome(range(codec_h.m))
    )
    g_ref = StepEngine(model, tc, codec_r, backend="reference").gradients(
        params, pb, codec_r.decode_outcome(range(codec_r.m))
    )
    _tree_close(g_dev, g_host, atol=1e-6, rtol=1e-5)  # identical math, same device
    _tree_close(g_dev, g_ref)


@pytest.mark.parametrize("name", ["partial_work", "bernoulli", "heter_aware"])
@pytest.mark.parametrize("seed", [0, 7, 23])
def test_device_gradients_match_on_inexact_outcomes(name, seed):
    """Inexact leg: random partial-completion support masks flow through the
    device slot weights exactly as through the host path and the masked-B
    oracle."""
    rng = np.random.default_rng(seed)
    model = _ToyModel()
    codec = _codec(name, seed=seed % 3)
    support = (rng.uniform(size=(codec.m, codec.k)) < 0.6).astype(np.float64)
    outcome = codec.decode_partial(support)
    params = model.init(jax.random.PRNGKey(seed))
    pb = _partition_batch(codec.k, seed=seed)
    tc = TrainConfig()
    g_dev = StepEngine(model, tc, codec, backend="fused").gradients(params, pb, outcome)
    g_host = StepEngine(model, tc, codec, backend="fused", host_pack=True).gradients(
        params, pb, outcome
    )
    g_ref = StepEngine(model, tc, codec, backend="reference").gradients(params, pb, outcome)
    _tree_close(g_dev, g_host, atol=1e-6, rtol=1e-5)
    _tree_close(g_dev, g_ref)


def test_dispatch_span_counts_unique_and_slot_rows():
    """With tracing on, the fused step's ``phase.dispatch`` span says how many
    sequences ran forward/backward (k·mb) and how many the code assigns
    (m·n_slots·mb)."""
    from repro.obs.trace import Tracer

    codec = _codec("heter_aware")
    eng = StepEngine(_ToyModel(), TrainConfig(), codec, backend="fused")
    eng.tracer = Tracer()
    pb = _partition_batch(codec.k)
    eng.step(eng.init_state(jax.random.PRNGKey(0)), pb, codec.decode_vector(range(codec.m)))
    (rec,) = eng.tracer.records(kind="span", name="phase.dispatch")
    mb = pb["x"].shape[1]
    assert rec["args"] == {"rows": codec.k * mb, "slot_rows": codec.m * codec.n_slots * mb}


# ---------------------------------------------------------------------------
# language models: MoE aux per sequence, bf16 under an ill-conditioned decode
# ---------------------------------------------------------------------------


def _lm(arch, **overrides):
    from repro.configs import get_config
    from repro.models.lm import build_model

    cfg = dataclasses.replace(get_config(arch).reduced(), **overrides)
    return cfg, build_model(cfg)


def _lm_batch(cfg, k, mb=2, seq=16, step=0):
    from repro.data.pipeline import SyntheticData

    return SyntheticData(cfg, k=k, part_mb=mb, seq_len=seq).batch(step)


@pytest.mark.parametrize("inexact", [False, True])
def test_moe_unique_pass_matches_protocol(inexact):
    """A MoE stack's load-balance loss is per sequence, so the fused pass over
    the unique batch is the paper protocol's decoded gradient (capacity
    high enough that no token drops), exact and partial-work decodes."""
    cfg, model = _lm("mixtral-8x7b", n_layers=2)
    assert cfg.capacity_factor >= 8.0 and cfg.aux_coef > 0
    codec = _codec("heter_aware", seed=1)
    params = model.init(jax.random.PRNGKey(3))
    pb = _lm_batch(cfg, codec.k, mb=1)
    if inexact:
        support = (np.random.default_rng(5).uniform(size=(codec.m, codec.k)) < 0.6).astype(np.float64)
        outcome = codec.decode_partial(support)
    else:
        outcome = codec.decode_outcome([0, 2, 3])
    tc = TrainConfig()
    g_fused = StepEngine(model, tc, codec, backend="fused").gradients(params, pb, outcome)
    g_ref = StepEngine(model, tc, codec, backend="reference").gradients(params, pb, outcome)
    _tree_close(g_fused, g_ref)


def test_moe_aux_per_sequence_keeps_uniform_loss():
    """Under uniform weights the per-sequence aux gives the batch-mean form's
    loss, mean(ce) + aux_coef·mean(aux); a stack with no MoE layer keeps the
    scalar zero aux."""
    cfg, model = _lm("mixtral-8x7b", n_layers=2)
    _, ce_only = _lm("mixtral-8x7b", n_layers=2, aux_coef=0.0)
    params = model.init(jax.random.PRNGKey(4))
    pb = _lm_batch(cfg, k=4)
    batch = {key: jnp.asarray(v.reshape((-1,) + v.shape[2:])) for key, v in pb.items()}
    n = batch["tokens"].shape[0]
    batch["weight"] = jnp.full((n,), 1.0 / n, jnp.float32)
    _, aux = jax.jit(model.forward)(params, batch)
    assert aux.shape == (n,)
    assert float(jnp.std(aux)) > 0  # the sequences route differently
    ce = jax.jit(ce_only.seq_losses)(params, batch)
    want = jnp.mean(ce) + cfg.aux_coef * jnp.mean(aux)
    got = jax.jit(model.weighted_loss)(params, batch)
    assert float(got) == pytest.approx(float(want), rel=1e-6)

    _, dense = _lm("smollm-360m", n_layers=2)
    _, dense_aux = jax.eval_shape(dense.forward, dense.init(jax.random.PRNGKey(4)), batch)
    assert dense_aux.shape == ()


def _amplification(codec, a):
    """max_j Σ_w|a_w·B_wj| / |Σ_w a_w·B_wj|: how far the decode cancels."""
    aB = np.asarray(a, np.float64)[:, None] * codec.scheme.B
    return float(np.max(np.abs(aB).sum(0) / np.abs(aB.sum(0))))


def _rel_err(tree, ref):
    diff = sum(float(jnp.sum((x.astype(jnp.float32) - y) ** 2))
               for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(ref)))
    norm = sum(float(jnp.sum(y ** 2)) for y in jax.tree.leaves(ref))
    return (diff / norm) ** 0.5


def test_bf16_fused_grad_does_not_grow_with_decode_amplification():
    """bf16 model, heter_aware codes drawn from two seeds, one worker faulted:
    a decode that cancels by over 1000× loses no more precision in the fused
    step than one that cancels by under 20, because the slot weights are
    summed per partition in f32 before any bf16 op; the replicated host pack
    weights each slot's bf16 gradient separately and does lose it."""
    cfg, model = _lm("smollm-360m", n_layers=2, dtype="bfloat16")
    _, model32 = _lm("smollm-360m", n_layers=2, dtype="float32")
    params = model.init(jax.random.PRNGKey(0))
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    k = 8
    pb = _lm_batch(cfg, k, mb=1)
    flat = {key: jnp.asarray(v.reshape((-1,) + v.shape[2:])) for key, v in pb.items()}
    flat["weight"] = jnp.full((k,), 1.0 / k, jnp.float32)
    g_ref = jax.grad(model32.weighted_loss)(p32, flat)  # the exact decode's mean gradient
    errs, amps = {}, {}
    tc = TrainConfig()
    for label, seed, fault in (("low", 60, 1), ("high", 170, 1)):
        codec = Codec(get_scheme("heter_aware", m=4, k=k, s=1, c=[2.0, 4.0, 8.0, 16.0], rng=seed))
        outcome = codec.decode_outcome([w for w in range(codec.m) if w != fault])
        assert outcome.exact
        amps[label] = _amplification(codec, outcome.a)
        eng = StepEngine(model, tc, codec, backend="fused")
        errs[label] = _rel_err(eng.gradients(params, pb, outcome), g_ref)
    host = StepEngine(model, tc, codec, backend="fused", host_pack=True)
    errs["high, replicated"] = _rel_err(host.gradients(params, pb, outcome), g_ref)
    assert amps["low"] < 20 and amps["high"] > 1000, amps
    assert errs["high"] <= 2 * errs["low"], errs
    assert errs["high, replicated"] > 2 * errs["low"], errs


# ---------------------------------------------------------------------------
# full optimizer steps + plan-cache invalidation
# ---------------------------------------------------------------------------


def test_full_step_device_equals_host_pack():
    model = _ToyModel()
    tc = TrainConfig(lr=1e-2, warmup_steps=1, total_steps=6)
    states, metrics = [], []
    for hp in (False, True):
        codec = _codec("heter_aware")
        eng = StepEngine(model, tc, codec, backend="fused", host_pack=hp)
        st = eng.init_state(jax.random.PRNGKey(4))
        for i in range(3):
            st, met = eng.step(st, _partition_batch(codec.k, seed=i), codec.decode_vector([0, 2, 3]))
        states.append(st)
        metrics.append(met)
    assert metrics[0]["loss"] == pytest.approx(metrics[1]["loss"], rel=1e-6)
    _tree_close(states[0].params, states[1].params, atol=1e-6, rtol=1e-6)


def test_plan_cache_invalidated_on_rebalance():
    """An elastic rebalance bumps codec.version; the engine must re-upload
    its device plan tensors (and the rebalanced grads must match a host-pack
    engine built fresh on the new plan)."""
    model = _ToyModel()
    codec = _codec("heter_aware")
    eng = StepEngine(model, TrainConfig(), codec, backend="fused")
    params = model.init(jax.random.PRNGKey(0))
    pb = _partition_batch(codec.k)
    eng.gradients(params, pb, codec.decode_vector(range(codec.m)))
    plan0, v0 = eng._plan_ref, codec.version
    assert plan0 is codec.plan
    codec.rebalance([4.0, 1.0, 1.0, 4.0])
    assert codec.version == v0 + 1
    assert codec.plan is not plan0  # value change => new plan identity
    a = codec.decode_vector(range(codec.m))
    g_new = eng.gradients(params, pb, a)
    assert eng._plan_ref is codec.plan
    g_host = StepEngine(model, TrainConfig(), codec, backend="fused", host_pack=True).gradients(
        params, pb, a
    )
    _tree_close(g_new, g_host, atol=1e-6, rtol=1e-5)


def test_membership_change_invalidates_every_device_cache():
    """An in-place membership change (DESIGN.md §8) must bump Codec.version
    EXACTLY once and rotate the plan object, so the engine's device-resident
    plan tensors, the (m, k) all-ones support mask, and the scheme's
    decode/outcome LRUs all refresh — post-churn grads must match a fresh
    host-pack engine on the new plan."""
    from repro.train.elastic import ElasticController

    model = _ToyModel()
    codec = _codec("heter_aware")
    ctl = ElasticController(codec, true_speeds=np.array(_C4), c_init=np.array(_C4))
    eng = StepEngine(model, TrainConfig(), codec, backend="fused")
    params = model.init(jax.random.PRNGKey(0))
    eng.gradients(params, _partition_batch(codec.k), codec.decode_vector(range(codec.m)))
    plan0, v0, ones0 = eng._plan_ref, codec.version, eng._ones_support
    cache0 = codec.code.decode_cache_info()
    assert cache0.currsize > 0

    ctl.add_workers([2.5])

    assert codec.version == v0 + 1  # exactly once per transition
    assert codec.plan is not plan0
    assert codec.code.decode_cache_info().currsize == 0  # LRU died with old B
    a = codec.decode_vector(range(codec.m))
    pb = _partition_batch(codec.k, seed=3)
    g_new = eng.gradients(params, pb, a)
    assert eng._plan_ref is codec.plan  # device plan re-uploaded
    assert eng._ones_support is not ones0  # (m, k) mask resized with m
    assert eng._ones_support.shape == (codec.m, codec.k)
    g_host = StepEngine(
        model, TrainConfig(), codec, backend="fused", host_pack=True
    ).gradients(params, pb, a)
    _tree_close(g_new, g_host, atol=1e-6, rtol=1e-5)


def test_stale_version_would_be_caught():
    """Regression guard for the §8 invalidation contract: if remap_members
    ever stopped bumping Codec.version / rotating the plan object, the
    engine would keep serving the PRE-churn plan tensors and this test
    fails — the device pack would disagree with the codec's host pack."""
    from repro.train.elastic import ElasticController

    codec = _codec("heter_aware")
    ctl = ElasticController(codec, true_speeds=np.array(_C4), c_init=np.array(_C4))
    versions = [codec.version]
    plans = [codec.plan]
    for transition in (lambda: ctl.add_workers([3.0]), lambda: ctl.remove_workers([0])):
        transition()
        versions.append(codec.version)
        plans.append(codec.plan)
    # one bump per transition, never zero, never two; plan identity rotates
    assert versions == [versions[0], versions[0] + 1, versions[0] + 2]
    assert len({id(p) for p in plans}) == 3
    # and the plan VALUES actually track the live scheme (stale copy would
    # index partitions with the old worker set's ids)
    assert codec.plan.m == codec.m == 4
    np.testing.assert_array_equal(
        np.sort(np.unique(codec.plan.slot_pids[codec.plan.slot_mask > 0])),
        np.arange(codec.k),
    )


# ---------------------------------------------------------------------------
# flat Pallas encode/decode (interpret mode — CPU CI exercises the kernel)
# ---------------------------------------------------------------------------


def test_flat_pallas_encode_decode_matches_reference_protocol():
    """End-to-end coded_reduce composition in interpret mode: per-worker
    flat encode g̃_w = coded_reduce(g_stack[parts], B[w, parts]) then master
    decode g = coded_reduce(stack(g̃), a/k) == the paper protocol's decoded
    mean gradient — the spmd backend's math without needing a mesh."""
    from repro.core.aggregator import protocol_reference
    from repro.kernels.ops import coded_reduce

    model = _ToyModel()
    codec = _codec("heter_aware")
    params = model.init(jax.random.PRNGKey(1))
    pb = _partition_batch(codec.k, seed=9)
    scheme = codec.scheme

    def loss_fn(p, micro):
        mb = micro["x"].shape[0]
        w = jnp.full((mb,), 1.0 / mb, jnp.float32)
        return model.weighted_loss(p, {**micro, "weight": w})

    from jax.flatten_util import ravel_pytree

    _, unravel = ravel_pytree(params)
    grad_fn = jax.jit(jax.grad(loss_fn))
    part_flat = jnp.stack([
        ravel_pytree(grad_fn(params, jax.tree.map(lambda x, j=j: x[j], pb)))[0]
        for j in range(codec.k)
    ])  # (k, D)
    coded = []
    for w_idx in range(codec.m):
        parts = list(scheme.allocation.partitions[w_idx])
        g = part_flat[jnp.asarray(parts)]
        cw = jnp.asarray(scheme.B[w_idx, parts], jnp.float32)
        coded.append(coded_reduce(g, cw, impl="pallas_interpret"))
    a = codec.decode_vector([0, 1, 3])
    decoded_flat = coded_reduce(
        jnp.stack(coded), jnp.asarray(a / codec.k, jnp.float32), impl="pallas_interpret"
    )
    g_ref, _ = protocol_reference(loss_fn, params, pb, scheme, decode_vec=a)
    _tree_close(unravel(decoded_flat), g_ref, atol=1e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# trainer loop: double-buffered prefetch == manual step loop
# ---------------------------------------------------------------------------


def test_trainer_run_prefetch_matches_stepwise_loop():
    from repro.core.straggler import FixedDelayStragglers
    from repro.data.pipeline import SyntheticData
    from repro.models.lm import build_model
    from repro.configs import get_config
    from repro.train.trainer import CodedTrainer

    cfg = get_config("smollm-360m").reduced()
    tc = TrainConfig(lr=1e-3, warmup_steps=2, total_steps=6)

    def mk():
        tr = CodedTrainer(
            build_model(cfg), CodingConfig(scheme="heter_aware", s=1), tc, m=4,
            part_mb=2, straggler_model=FixedDelayStragglers(s=1, delay=2.0),
            true_speeds=np.array([1.0, 2.0, 3.0, 4.0]),
        )
        return tr, SyntheticData(cfg, k=tr.k, part_mb=2, seq_len=32)

    tr_a, data_a = mk()
    st_a = tr_a.init_state(jax.random.PRNGKey(0))
    seen = []
    st_a, last = tr_a.run(
        st_a, data_a, 4, on_step=lambda s, st, met: seen.append((s, met["loss"]))
    )
    assert [s for s, _ in seen] == [0, 1, 2, 3]

    tr_b, data_b = mk()
    st_b = tr_b.init_state(jax.random.PRNGKey(0))
    for step in range(4):
        st_b, met_b = tr_b.step(st_b, data_b.batch(step))
    assert last["loss"] == pytest.approx(met_b["loss"], rel=1e-6)
    _tree_close(st_a.params, st_b.params, atol=1e-7, rtol=1e-6)
