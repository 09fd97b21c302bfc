"""Multi-device SPMD checks, run as a subprocess with fake devices so the
main pytest process keeps its single real CPU device.

Usage: python tests/spmd_driver.py <check_name>
Exits 0 on success; prints diagnostics on failure.
"""

import os
import sys

# respect a pre-set XLA_FLAGS (scripts/run.sh builds one from CPU_DEVICES)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.launch.mesh import make_auto_mesh  # noqa: E402


def _toy():
    def loss_fn(params, batch):
        h = jnp.tanh(batch["x"] @ params["w1"])
        return jnp.mean((h @ params["w2"])[:, 0] - batch["y"]) ** 2 + jnp.mean(
            ((h @ params["w2"])[:, 0] - batch["y"]) ** 2
        )

    r = np.random.default_rng(0)
    params = {
        "w1": jnp.asarray(r.normal(size=(4, 16)), jnp.float32),
        "w2": jnp.asarray(r.normal(size=(16, 1)), jnp.float32),
    }
    return loss_fn, params, r


class _ToyModel:
    """Duck-typed model for the engine/trainer checks (init + weighted_loss)."""

    def init(self, rng):
        k1, k2 = jax.random.split(rng)
        return {
            "w1": jax.random.normal(k1, (4, 16), jnp.float32),
            "w2": jax.random.normal(k2, (16, 1), jnp.float32),
        }

    def weighted_loss(self, params, batch):
        pred = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
        return jnp.sum((pred[:, 0] - batch["y"]) ** 2 * batch["weight"])


def _pdata(k: int, step: int, mb: int = 2):
    """Deterministic partition-major batch for step ``step``."""
    r = np.random.default_rng(1000 + step)
    return {
        "x": r.normal(size=(k, mb, 4)).astype(np.float32),
        "y": r.normal(size=(k, mb)).astype(np.float32),
    }


def _leaves_equal(a, b) -> bool:
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    )


def check_faithful_spmd():
    """Flat wire format (DESIGN.md §6): per-worker Pallas encode of the
    ravelled gradient stack, ONE psum decode over the (D,) buffer —
    matches the per-partition ground truth, compressed path stays close."""
    from repro.core import Decoder, build_heter_aware
    from repro.core.aggregator import (
        faithful_spmd_step, make_plan, pack_coded_batch, wire_unraveler,
    )

    mesh = make_auto_mesh((4, 2), ("data", "model"))
    loss_fn, params, r = _toy()
    unravel, D = wire_unraveler(params)
    params = jax.device_put(
        params,
        {"w1": NamedSharding(mesh, P(None, "model")), "w2": NamedSharding(mesh, P("model", None))},
    )
    k, s, mb = 8, 1, 2
    scheme = build_heter_aware(k, s, [1, 2, 3, 2], rng=0)
    pb = {
        "x": jnp.asarray(r.normal(size=(k, mb, 4)), jnp.float32),
        "y": jnp.asarray(r.normal(size=(k, mb)), jnp.float32),
    }
    plan = make_plan(scheme)
    a = Decoder(scheme).decode_vector([0, 2, 3]) / k
    sb = jax.device_put(pack_coded_batch(pb, plan), NamedSharding(mesh, P("data")))
    coeff = jax.device_put(jnp.asarray(plan.slot_coeff * plan.slot_mask), NamedSharding(mesh, P("data")))
    a_dev = jax.device_put(jnp.asarray(a, jnp.float32), NamedSharding(mesh, P("data")))
    err = jax.device_put(jnp.zeros((4, 1), jnp.float32), NamedSharding(mesh, P("data")))

    gt = jax.tree.map(jnp.zeros_like, params)
    for j in range(k):
        g = jax.grad(loss_fn)(params, jax.tree.map(lambda x: x[j], pb))
        gt = jax.tree.map(lambda A, b: A + b / k, gt, g)

    step = jax.jit(faithful_spmd_step(loss_fn, mesh, ("data",), compress=False))
    flat, _ = step(params, sb, coeff, a_dev, err)
    assert flat.shape == (D,), flat.shape
    for x, y in zip(jax.tree.leaves(unravel(flat)), jax.tree.leaves(gt)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)

    # compressed wire format stays close + error feedback is populated
    err_c = jax.device_put(jnp.zeros((4, D), jnp.float32), NamedSharding(mesh, P("data")))
    step_c = jax.jit(faithful_spmd_step(loss_fn, mesh, ("data",), compress=True))
    fc, err2 = step_c(params, sb, coeff, a_dev, err_c)
    rel = max(
        float(np.max(np.abs(np.asarray(x) - np.asarray(y))) / (np.max(np.abs(np.asarray(y))) + 1e-9))
        for x, y in zip(jax.tree.leaves(unravel(fc)), jax.tree.leaves(gt))
    )
    assert rel < 0.05, rel
    assert float(np.abs(np.asarray(err2)).max()) > 0
    print("faithful_spmd ok")


def check_fused_sharded_equals_host():
    """The production fused step gives identical grads on a sharded mesh and
    on the host (single device)."""
    from repro.core import Decoder, build_heter_aware
    from repro.core.aggregator import fused_coded_value_and_grad, make_plan, pack_coded_batch, slot_weights

    loss_fn, params, r = _toy()
    k = 8
    scheme = build_heter_aware(k, 1, [1, 2, 3, 2], rng=0)
    pb = {
        "x": jnp.asarray(r.normal(size=(k, 2, 4)), jnp.float32),
        "y": jnp.asarray(r.normal(size=(k, 2)), jnp.float32),
    }
    plan = make_plan(scheme)
    w = jnp.asarray(slot_weights(plan, Decoder(scheme).decode_vector([1, 2, 3])))
    sb = pack_coded_batch(pb, plan)
    vg = jax.jit(fused_coded_value_and_grad(loss_fn))
    _, g_host = vg(params, sb, w)

    mesh = make_auto_mesh((4, 2), ("data", "model"))
    sb_sh = jax.device_put(sb, NamedSharding(mesh, P("data")))
    w_sh = jax.device_put(w, NamedSharding(mesh, P("data")))
    p_sh = jax.device_put(params, NamedSharding(mesh, P()))
    _, g_mesh = vg(p_sh, sb_sh, w_sh)
    for x, y in zip(jax.tree.leaves(g_mesh), jax.tree.leaves(g_host)):
        # sharded reductions reassociate float adds; bitwise equality is not
        # expected, 1e-4 relative is
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=2e-3, atol=2e-5)
    print("fused sharded ok")


def check_engine_spmd():
    """StepEngine's 'spmd' backend (shard_map protocol) matches the
    'reference' oracle on a real 4x2 mesh."""
    import jax.numpy as jnp
    from repro.configs.base import TrainConfig
    from repro.core import Codec, get_scheme
    from repro.train.engine import StepEngine

    class Toy:
        def init(self, rng):
            k1, k2 = jax.random.split(rng)
            return {
                "w1": jax.random.normal(k1, (4, 16), jnp.float32),
                "w2": jax.random.normal(k2, (16, 1), jnp.float32),
            }

        def weighted_loss(self, params, batch):
            pred = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
            return jnp.sum((pred[:, 0] - batch["y"]) ** 2 * batch["weight"])

    mesh = make_auto_mesh((4, 2), ("data", "model"))
    model = Toy()
    codec = Codec(get_scheme("heter_aware", m=4, k=8, s=1, c=[1, 2, 3, 2], rng=0))
    r = np.random.default_rng(0)
    pb = {
        "x": r.normal(size=(8, 2, 4)).astype(np.float32),
        "y": r.normal(size=(8, 2)).astype(np.float32),
    }
    a = codec.decode_vector([0, 2, 3])
    params = model.init(jax.random.PRNGKey(0))
    tc = TrainConfig()
    g_spmd = StepEngine(model, tc, codec, backend="spmd", mesh=mesh).gradients(params, pb, a)
    g_ref = StepEngine(model, tc, codec, backend="reference").gradients(params, pb, a)
    for x, y in zip(jax.tree.leaves(g_spmd), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)
    print("engine spmd ok")


def check_engine_spmd_inexact():
    """The 'spmd' backend matches the 'reference' oracle on an INEXACT
    partial-work step: the DecodeOutcome's support mask must zero the same
    contributions in the shard_map wire path as in the oracle's B rows
    (DESIGN.md §5 backend-equivalence claim, spmd leg)."""
    import jax.numpy as jnp
    from repro.configs.base import TrainConfig
    from repro.core import Codec, get_scheme
    from repro.train.engine import StepEngine

    class Toy:
        def init(self, rng):
            k1, k2 = jax.random.split(rng)
            return {
                "w1": jax.random.normal(k1, (4, 16), jnp.float32),
                "w2": jax.random.normal(k2, (16, 1), jnp.float32),
            }

        def weighted_loss(self, params, batch):
            pred = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
            return jnp.sum((pred[:, 0] - batch["y"]) ** 2 * batch["weight"])

    mesh = make_auto_mesh((4, 2), ("data", "model"))
    model = Toy()
    codec = Codec(get_scheme("partial_work", m=4, k=8, s=1, c=[1, 2, 3, 2], rng=0))
    r = np.random.default_rng(0)
    pb = {
        "x": r.normal(size=(8, 2, 4)).astype(np.float32),
        "y": r.normal(size=(8, 2)).astype(np.float32),
    }
    support = (r.uniform(size=(codec.m, codec.k)) < 0.6).astype(np.float64)
    outcome = codec.decode_partial(support)
    assert not outcome.exact and outcome.residual > 0  # really inexact
    params = model.init(jax.random.PRNGKey(0))
    tc = TrainConfig()
    g_spmd = StepEngine(model, tc, codec, backend="spmd", mesh=mesh).gradients(params, pb, outcome)
    g_ref = StepEngine(model, tc, codec, backend="reference").gradients(params, pb, outcome)
    for x, y in zip(jax.tree.leaves(g_spmd), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)
    print("engine spmd inexact ok")


def check_engine_spmd_wire():
    """Fused int8 wire kernels on the spmd backend (DESIGN.md §12): with
    compression on, wire_kernel=True (fused Pallas encode + int8 all_gather
    decode) and wire_kernel=False (coded_reduce + XLA quantize + f32 psum)
    must produce the same gradients — on an exact decode AND an inexact
    partial-work outcome — and both must stay within the compression
    tolerance of the uncompressed reference oracle."""
    import jax.numpy as jnp
    from repro.configs.base import TrainConfig
    from repro.core import Codec, get_scheme
    from repro.train.engine import StepEngine

    class Toy:
        def init(self, rng):
            k1, k2 = jax.random.split(rng)
            return {
                "w1": jax.random.normal(k1, (4, 16), jnp.float32),
                "w2": jax.random.normal(k2, (16, 1), jnp.float32),
            }

        def weighted_loss(self, params, batch):
            pred = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
            return jnp.sum((pred[:, 0] - batch["y"]) ** 2 * batch["weight"])

    mesh = make_auto_mesh((4, 2), ("data", "model"))
    model = Toy()
    r = np.random.default_rng(0)
    pb = {
        "x": r.normal(size=(8, 2, 4)).astype(np.float32),
        "y": r.normal(size=(8, 2)).astype(np.float32),
    }
    tc = TrainConfig()

    def engines(scheme_name):
        codec = Codec(get_scheme(scheme_name, m=4, k=8, s=1, c=[1, 2, 3, 2], rng=0))
        mk = lambda **kw: StepEngine(model, tc, codec, backend="spmd", mesh=mesh,
                                     compress=True, **kw)
        return codec, mk(wire_kernel=True), mk(wire_kernel=False)

    # exact decode
    codec, e_on, e_off = engines("heter_aware")
    params = model.init(jax.random.PRNGKey(0))
    a = codec.decode_vector([0, 2, 3])
    g_on = e_on.gradients(params, pb, a)
    g_off = e_off.gradients(params, pb, a)
    g_ref = StepEngine(model, tc, codec, backend="reference").gradients(params, pb, a)
    for x, y in zip(jax.tree.leaves(g_on), jax.tree.leaves(g_off)):
        # fused vs unfused quantize differ by at most 1 ulp of the scale
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4, atol=2e-5)
    rel = max(
        float(np.max(np.abs(np.asarray(x) - np.asarray(y))) / (np.max(np.abs(np.asarray(y))) + 1e-9))
        for x, y in zip(jax.tree.leaves(g_on), jax.tree.leaves(g_ref))
    )
    assert rel < 0.05, rel  # int8 wire stays within compression tolerance

    # inexact partial-work outcome: the support mask must thread through the
    # fused encode identically
    codec, e_on, e_off = engines("partial_work")
    support = (r.uniform(size=(codec.m, codec.k)) < 0.6).astype(np.float64)
    outcome = codec.decode_partial(support)
    assert not outcome.exact and outcome.residual > 0
    g_on = e_on.gradients(params, pb, outcome)
    g_off = e_off.gradients(params, pb, outcome)
    for x, y in zip(jax.tree.leaves(g_on), jax.tree.leaves(g_off)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4, atol=2e-5)

    # two steps on the SAME engine: error feedback accumulates in the fused
    # path too (second-step gradients still agree across wire kernels)
    g_on2 = e_on.gradients(params, pb, outcome)
    g_off2 = e_off.gradients(params, pb, outcome)
    for x, y in zip(jax.tree.leaves(g_on2), jax.tree.leaves(g_off2)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-4, atol=2e-5)
    assert float(np.abs(np.asarray(e_on._err)).max()) > 0
    print("engine spmd wire ok")


def check_engine_spmd_churn():
    """Membership-change spmd leg (DESIGN.md §8): the shard_map backend is
    mesh-pinned, so after an in-place shrink the engine is REBUILT on a mesh
    matching the new m — its first post-churn gradients must equal the
    reference oracle on the live (remapped) codec."""
    import jax.numpy as jnp
    from repro.configs.base import TrainConfig
    from repro.core import Codec, get_scheme
    from repro.train.elastic import ElasticController
    from repro.train.engine import StepEngine

    class Toy:
        def init(self, rng):
            k1, k2 = jax.random.split(rng)
            return {
                "w1": jax.random.normal(k1, (4, 16), jnp.float32),
                "w2": jax.random.normal(k2, (16, 1), jnp.float32),
            }

        def weighted_loss(self, params, batch):
            pred = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
            return jnp.sum((pred[:, 0] - batch["y"]) ** 2 * batch["weight"])

    model = Toy()
    speeds = np.array([1.0, 2.0, 3.0, 2.0, 1.0, 2.0, 3.0, 2.0])
    codec = Codec(get_scheme("heter_aware", m=8, k=16, s=1, c=speeds, rng=0))
    ctl = ElasticController(codec, true_speeds=speeds, c_init=speeds)
    ctl.remove_workers([1, 3, 5, 7])  # 8 -> 4 workers, slot plan remapped
    assert codec.m == 4

    mesh = make_auto_mesh((4, 2), ("data", "model"))
    r = np.random.default_rng(0)
    pb = {
        "x": r.normal(size=(codec.k, 2, 4)).astype(np.float32),
        "y": r.normal(size=(codec.k, 2)).astype(np.float32),
    }
    a = codec.decode_vector(range(codec.m))
    params = model.init(jax.random.PRNGKey(0))
    tc = TrainConfig()
    g_spmd = StepEngine(model, tc, codec, backend="spmd", mesh=mesh).gradients(params, pb, a)
    g_ref = StepEngine(model, tc, codec, backend="reference").gradients(params, pb, a)
    for x, y in zip(jax.tree.leaves(g_spmd), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)
    print("engine spmd churn ok")


def check_engine_spmd_elastic():
    """Device-donating elastic rebuild (DESIGN.md §13): the SAME spmd engine
    survives grow, shrink, fault-eviction, and re-admission in place.

    Pinned here: (a) post-transition grads equal the reference oracle on
    the live codec; (b) the rebuilt engine is BIT-equal to a fresh engine
    constructed directly at the new m (the rebuild is the identity on the
    numerics); (c) retained workers' int8 error-feedback rows carry across
    membership transitions (joiners zeroed) and across a pure rebalance
    (m unchanged, c changed — satellite of PR 10), proven by a 2-step
    error-feedback chain against a buffer-seeded twin; (d) the carried
    residual actually matters (a zero-err twin diverges)."""
    from repro.configs.base import TrainConfig
    from repro.core import Codec, get_scheme
    from repro.core.simulator import FaultEvent, FaultSchedule
    from repro.configs.base import CodingConfig
    from repro.train.elastic import ElasticController
    from repro.train.engine import StepEngine
    from repro.train.trainer import CodedTrainer

    model = _ToyModel()
    tc = TrainConfig()
    params = model.init(jax.random.PRNGKey(0))
    pb = _pdata(8, 0)

    def wire(ctl, eng):
        ctl.pre_transition = eng.check_membership
        ctl.on_transition = eng.note_membership

    def fresh_at(codec, m, **kw):
        return StepEngine(
            model, tc, codec, backend="spmd",
            mesh=make_auto_mesh((m, 1), ("data", "model")), **kw,
        )

    # ---- (a)+(b): exactness across grow and shrink (uncompressed wire) ----
    codec = Codec(get_scheme("heter_aware", m=4, k=8, s=1, c=[1, 2, 3, 2], rng=0))
    ctl = ElasticController(codec, true_speeds=np.array([1.0, 2.0, 3.0, 2.0]))
    eng = StepEngine(model, tc, codec, backend="spmd",
                     mesh=make_auto_mesh((4, 1), ("data", "model")))
    wire(ctl, eng)
    eng.gradients(params, pb, codec.decode_vector([0, 2, 3]))  # prime at m=4

    ctl.add_workers([2.5, 1.5])  # 4 -> 6, same engine
    a = codec.decode_vector(range(codec.m))
    g = eng.gradients(params, pb, a)
    rb = eng.last_rebuild
    assert rb is not None and rb.m_before == 4 and rb.m_after == 6
    assert rb.mesh_rebuilt and rb.program_rebuilt
    assert rb.err_rows_carried == 4 and rb.err_rows_zeroed == 2
    g_ref = StepEngine(model, tc, codec, backend="reference").gradients(params, pb, a)
    for x, y in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)
    assert _leaves_equal(g, fresh_at(codec, 6).gradients(params, pb, a))

    ctl.remove_workers([1])  # 6 -> 5, same engine
    a = codec.decode_vector(range(codec.m))
    g = eng.gradients(params, pb, a)
    rb = eng.last_rebuild
    assert rb.m_before == 6 and rb.m_after == 5 and rb.err_rows_carried == 5
    g_ref = StepEngine(model, tc, codec, backend="reference").gradients(params, pb, a)
    for x, y in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)
    assert _leaves_equal(g, fresh_at(codec, 5).gradients(params, pb, a))

    # ---- (c)+(d): error-feedback carry-over on the compressed wire ----
    codec = Codec(get_scheme("heter_aware", m=4, k=8, s=1, c=[1, 2, 3, 2], rng=0))
    ctl = ElasticController(codec, true_speeds=np.array([1.0, 2.0, 3.0, 2.0]))
    eng = StepEngine(model, tc, codec, backend="spmd", compress=True,
                     wire_kernel=False,
                     mesh=make_auto_mesh((4, 1), ("data", "model")))
    wire(ctl, eng)
    eng.gradients(params, pb, codec.decode_vector([0, 2, 3]))
    err0 = np.asarray(eng._err)  # (4, D) residuals, populated by the step
    assert np.abs(err0).max() > 0

    # membership carry: survivors keep rows bit-exactly, the joiner zeroes
    ctl.add_workers([2.5])  # 4 -> 5
    rb = eng.rebuild()
    assert rb.err_rows_carried == 4 and rb.err_rows_zeroed == 1
    err1 = np.asarray(eng._err)
    np.testing.assert_array_equal(err1[:4], err0)
    assert np.all(err1[4] == 0)

    # 2-step chain: the rebuilt engine's next step is bit-equal to a twin
    # seeded with the carried buffer, and diverges from a zero-err twin
    a = codec.decode_vector(range(codec.m))
    pb2 = _pdata(8, 1)
    twin = fresh_at(codec, 5, compress=True, wire_kernel=False)
    twin._err, twin._err_version = jnp.asarray(err1), codec.version
    cold = fresh_at(codec, 5, compress=True, wire_kernel=False)
    g = eng.gradients(params, pb2, a)
    assert _leaves_equal(g, twin.gradients(params, pb2, a))
    assert _leaves_equal(np.asarray(eng._err), np.asarray(twin._err))
    assert not _leaves_equal(g, cold.gradients(params, pb2, a))

    # pure rebalance (m unchanged, c changed): identities unchanged, the
    # WHOLE buffer carries — the pre-§13 engine zeroed it here
    err2 = np.asarray(eng._err)
    codec.rebalance(np.array([1.0, 1.0, 2.0, 3.0, 2.0]))
    rb = eng.rebuild()
    assert rb.err_rows_carried == 5 and rb.err_rows_zeroed == 0
    assert not rb.mesh_rebuilt and not rb.program_rebuilt
    np.testing.assert_array_equal(np.asarray(eng._err), err2)
    a = codec.decode_vector(range(codec.m))
    pb3 = _pdata(8, 2)
    twin = fresh_at(codec, 5, compress=True, wire_kernel=False)
    twin._err, twin._err_version = jnp.asarray(err2), codec.version
    g = eng.gradients(params, pb3, a)
    assert _leaves_equal(g, twin.gradients(params, pb3, a))

    # ---- fault eviction + re-admission through the full trainer ----
    sched = FaultSchedule([FaultEvent(kind="hang", worker=1, step=4, duration=5)])
    tr = CodedTrainer(
        _ToyModel(),
        CodingConfig(scheme="heter_aware", s=1, rebalance_every=3),
        TrainConfig(lr=1e-2, warmup_steps=2, total_steps=40),
        m=4, part_mb=2, backend="spmd",
        mesh=make_auto_mesh((4, 1), ("data", "model")),
        true_speeds=np.linspace(1.0, 2.0, 4), comm_time=0.01, rng=3,
        faults=sched,
    )
    state = tr.init_state(jax.random.PRNGKey(0))
    m_seen = []
    for step in range(24):
        state, met = tr.step(state, _pdata(tr.k, state.step))
        m_seen.append(tr.m)
    sup = tr.supervisor
    assert min(m_seen) == 3, m_seen  # evicted through the spmd rebuild...
    assert tr.m == 4  # ... and re-admitted after recovery
    assert len(sup.evictions) == 1 and len(sup.readmissions) == 1
    assert tr.engine.last_rebuild is not None
    assert all(bool(jnp.isfinite(v).all()) for v in jax.tree.leaves(state.params))
    # the post-churn engine still matches the oracle on the live codec
    a = tr.codec.decode_vector(range(tr.m))
    g = tr.engine.gradients(state.params, _pdata(tr.k, 99), a)
    g_ref = StepEngine(_ToyModel(), tc, tr.codec, backend="reference").gradients(
        state.params, _pdata(tr.k, 99), a
    )
    for x, y in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=1e-5)
    print("engine spmd elastic ok")


def check_spmd_trainer_resume():
    """Bit-exact mid-churn resume on the spmd backend (DESIGN.md §13
    acceptance): run A trains through join+leave churn in one go; run B
    checkpoints BETWEEN the join and the leave (m grown, compressed-wire
    error feedback live), restores into a FRESH trainer constructed at the
    original m, and must land on bit-identical params, optimizer state,
    and error-feedback buffer."""
    import json

    from repro.configs.base import CodingConfig, TrainConfig
    from repro.core.simulator import ChurnSchedule, MembershipEvent
    from repro.train.trainer import CodedTrainer

    def mk():
        return CodedTrainer(
            _ToyModel(),
            CodingConfig(scheme="heter_aware", s=1, rebalance_every=3,
                         compress=True, wire_kernel=False),
            TrainConfig(lr=1e-2, warmup_steps=2, total_steps=16),
            m=4, part_mb=2, backend="spmd",
            mesh=make_auto_mesh((4, 1), ("data", "model")),
            true_speeds=np.array([1.0, 2.0, 3.0, 2.0]),
            comm_time=0.01, rng=3,
            churn=ChurnSchedule([
                MembershipEvent(step=2, join_speeds=(2.5, 1.5)),
                MembershipEvent(step=4, leave=(1, 4)),
            ]),
        )

    steps, split = 6, 3

    tr_a = mk()
    st = tr_a.init_state(jax.random.PRNGKey(0))
    for step in range(steps):
        st, _ = tr_a.step(st, _pdata(tr_a.k, st.step))
    final_a = st

    tr_b = mk()
    st = tr_b.init_state(jax.random.PRNGKey(0))
    for step in range(split):
        st, _ = tr_b.step(st, _pdata(tr_b.k, st.step))
    assert tr_b.m == 6  # mid-churn: after the join, before the leave
    # JSON round-trip = what the on-disk manifest does to the extras
    extras = json.loads(json.dumps(tr_b.state_extras()))
    saved = jax.tree.map(lambda x: np.asarray(x), (st.params, st.opt))

    tr_c = mk()  # fresh process stand-in: constructed at the ORIGINAL m=4
    tr_c.load_state_extras(extras)
    assert tr_c.m == 6 and tr_c.engine._err is not None
    st_c = type(st)(params=jax.tree.map(jnp.asarray, saved[0]),
                    opt=jax.tree.map(jnp.asarray, saved[1]), step=split)
    for step in range(split, steps):
        st_c, _ = tr_c.step(st_c, _pdata(tr_c.k, st_c.step))

    assert _leaves_equal(st_c.params, final_a.params)
    assert _leaves_equal(st_c.opt, final_a.opt)
    assert _leaves_equal(tr_c.engine._err, tr_a.engine._err)
    assert tr_c.codec.version == tr_a.codec.version
    print("spmd trainer resume ok")


def check_dryrun_small():
    """Miniature dry-run: lower+compile a reduced arch on a 4x2 mesh with the
    same code path as launch/dryrun (which needs 512 devices)."""
    from functools import partial

    from repro.configs import get_config
    from repro.models.lm import build_model
    from repro.models.sharding import activation_axes
    from repro.optim.adam import adamw_init
    from repro.roofline.analysis import analyze_compiled
    from repro.train.steps import make_fused_train_step
    from repro.configs.base import TrainConfig

    cfg = get_config("llama3.2-1b").reduced()
    model = build_model(cfg)
    mesh = make_auto_mesh((4, 2), ("data", "model"))
    pshapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pspecs = model.param_specs(tp_axis="model", tp_size=2)
    params_in = jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
        pshapes, pspecs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    opt_shapes = jax.eval_shape(partial(adamw_init), pshapes)
    from repro.optim.adam import AdamWState

    opt_specs = AdamWState(step=P(), mu=pspecs, nu=pspecs, master=None)
    opt_in = jax.tree.map(
        lambda s, p: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=NamedSharding(mesh, p)),
        opt_shapes, opt_specs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
    )
    B, S = 8, 32
    batch = {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=NamedSharding(mesh, P("data"))),
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=NamedSharding(mesh, P("data"))),
        "weight": jax.ShapeDtypeStruct((B,), jnp.float32, sharding=NamedSharding(mesh, P("data"))),
    }
    step_fn = make_fused_train_step(model, TrainConfig(), accum_steps=1)
    with activation_axes(("data",), 4):
        with mesh:
            lowered = jax.jit(step_fn).lower(
                params_in, opt_in, batch, jax.ShapeDtypeStruct((), jnp.int32)
            )
            compiled = lowered.compile()
    rep = analyze_compiled(compiled, arch="llama-reduced", shape="tiny", mesh_name="4x2",
                           chips=8, model_flops=1.0)
    assert rep.flops_per_chip > 0
    assert compiled.memory_analysis() is not None
    print("dryrun small ok: flops/chip", rep.flops_per_chip, "bottleneck", rep.bottleneck)


def check_chip_smoke_spmd():
    """``chip_smoke.py --chips 4``'s phase (spmd vs fused gradients, compress
    off and on, spread over four devices) at reduced size on fake devices."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))
    import chip_smoke

    chip_smoke.spmd_phase(reduced=True, seq_len=16)


if __name__ == "__main__":
    {
        "faithful_spmd": check_faithful_spmd,
        "fused_sharded": check_fused_sharded_equals_host,
        "engine_spmd": check_engine_spmd,
        "engine_spmd_inexact": check_engine_spmd_inexact,
        "engine_spmd_wire": check_engine_spmd_wire,
        "engine_spmd_churn": check_engine_spmd_churn,
        "engine_spmd_elastic": check_engine_spmd_elastic,
        "spmd_trainer_resume": check_spmd_trainer_resume,
        "dryrun_small": check_dryrun_small,
        "chip_smoke_spmd": check_chip_smoke_spmd,
    }[sys.argv[1]]()
