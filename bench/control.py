"""Readings of the comparison's control and planted faults at a cell's own
size, for setting the cell's limits (not run by the benchmark's runs).

    python3 bench/control.py --workload <name> --seeds 11 12 13 \\
        [--variant fp8 half_batch]

For each seed the float32 reference's three steps are compared, by the
numbers of ``check.numbers``, with the same reference put in the
program's place and changed:

- ``fp8``: every matrix product in FP8 (e4m3 forward, e5m2 backward, per-
  tensor scaled), the precision next below the configuration's bfloat16;
- ``half_batch``: half of each batch left out, the mean over the rest;
- ``frozen``: a step that returns its state unchanged.

One JSON line per seed and variant on standard output.
"""

import argparse
import json
import sys

import check
import harness
from datagen import TokenBatches

VARIANTS = {"fp8": {"mm": "fp8"}, "half_batch": {"keep_rows": 0.5}, "frozen": {"frozen": True}}


def readings(cell, seed: int, variants) -> dict:
    import jax

    cfg, tr = cell.config["model"], cell.traffic
    ref = harness.load_module(harness.BENCH / "references" / f"{cell.config['reference']}.py")
    opt = check.Optim(**tr["optimizer"])
    dtype = {"bfloat16": jax.numpy.bfloat16, "float32": jax.numpy.float32}[cfg["dtype"]]
    key = jax.random.PRNGKey(harness.seed32(seed))
    p32 = jax.jit(lambda k: jax.tree.map(lambda x: x.astype(jax.numpy.float32),
                                         ref.init(cfg, k, dtype)))(key)
    from repro.core.codec import Codec
    from repro.configs.base import CodingConfig

    k = Codec.from_config(CodingConfig(scheme=tr["scheme"], s=tr["s"]), m=tr["m"],
                          c_init=tr["speeds"], rng=harness.seed32(seed) + 1).k
    mb, seq = tr["part_mb"], tr["seq"]
    data = TokenBatches(vocab=cfg["vocab"], k=k, mb=mb, seq=seq, seed=seed)
    batches = [data.batch(t)["tokens"].reshape(k * mb, seq) for t in range(check.CHECK_STEPS)]
    want = check.reference_steps(ref, cfg, p32, batches, opt, keep_grad=True)
    against = want.pop("grad_host")
    return {v: check.numbers(check.reference_steps(ref, cfg, p32, batches, opt, against=against,
                                                   **VARIANTS[v]), want)
            for v in variants}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--variant", nargs="+", choices=sorted(VARIANTS), default=sorted(VARIANTS))
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    for seed in args.seeds:
        for variant, vals in readings(cell, seed, args.variant).items():
            print(json.dumps({"workload": cell.name, "seed": seed, "variant": variant, **vals}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
