"""SPMD elastic-rebuild latency benchmark (DESIGN.md §13 gate).

Standalone subprocess on the host CPU (needs its OWN fake-device topology,
so it must set XLA_FLAGS before jax imports — the parent gate runs it via
``benchmarks/scaling.py`` with ``JAX_PLATFORMS=cpu``): builds a
compressed-wire spmd engine on an (8, 1) mesh at m=8, warms the step,
then times one full shrink
(m=8→7) and one full grow (m=7→8) INCLUDING the post-transition
gradient step — i.e. mesh re-derivation + shard_map re-jit + err-row
carry + first step on the new program, the whole churn-to-first-step
path a production cluster would block on.

Prints one JSON object on stdout:

  {"spmd_rebuild_shrink_ms": ..., "spmd_rebuild_grow_ms": ...,
   "spmd_rebuild_ms": max of the two}

Env: BENCH_FAST currently changes nothing (the cost IS one compile);
accepted for interface uniformity with the other benchmarks.
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.configs.base import TrainConfig  # noqa: E402
from repro.core import Codec, get_scheme  # noqa: E402
from repro.launch.mesh import make_auto_mesh  # noqa: E402
from repro.train.elastic import ElasticController  # noqa: E402
from repro.train.engine import StepEngine  # noqa: E402

M, K, S = 8, 16, 1


class _ToyModel:
    def init(self, rng):
        import jax.numpy as jnp

        k1, k2 = jax.random.split(rng)
        return {
            "w1": jax.random.normal(k1, (4, 16), jnp.float32),
            "w2": jax.random.normal(k2, (16, 1), jnp.float32),
        }

    def weighted_loss(self, params, batch):
        import jax.numpy as jnp

        pred = jnp.tanh(batch["x"] @ params["w1"]) @ params["w2"]
        return jnp.sum((pred[:, 0] - batch["y"]) ** 2 * batch["weight"])


def _pdata(k: int, step: int, mb: int = 2):
    r = np.random.default_rng(1000 + step)
    return {
        "x": r.normal(size=(k, mb, 4)).astype(np.float32),
        "y": r.normal(size=(k, mb)).astype(np.float32),
    }


def main() -> int:
    model = _ToyModel()
    codec = Codec(get_scheme(
        "heter_aware", m=M, k=K, s=S, c=np.linspace(1.0, 3.0, M), rng=0
    ))
    ctl = ElasticController(codec, true_speeds=np.linspace(1.0, 3.0, M))
    eng = StepEngine(
        model, TrainConfig(), codec, backend="spmd", compress=True,
        wire_kernel=False, mesh=make_auto_mesh((M, 1), ("data", "model")),
    )
    ctl.pre_transition = eng.check_membership
    ctl.on_transition = eng.note_membership
    params = model.init(jax.random.PRNGKey(0))

    # warm: first step pays the initial compile, not the rebuild
    a = codec.decode_vector(range(codec.m))
    jax.block_until_ready(jax.tree.leaves(
        eng.gradients(params, _pdata(K, 0), a))[0])

    def churn_ms(transition, step) -> float:
        t0 = time.perf_counter()
        transition()
        a = codec.decode_vector(range(codec.m))
        g = eng.gradients(params, _pdata(K, step), a)
        jax.block_until_ready(jax.tree.leaves(g)[0])
        return (time.perf_counter() - t0) * 1e3

    shrink_ms = churn_ms(lambda: ctl.remove_workers([M - 1]), 1)
    grow_ms = churn_ms(lambda: ctl.add_workers([2.0]), 2)

    print(json.dumps({
        "spmd_rebuild_shrink_ms": shrink_ms,
        "spmd_rebuild_grow_ms": grow_ms,
        "spmd_rebuild_ms": max(shrink_ms, grow_ms),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
