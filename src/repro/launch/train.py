"""Training launcher CLI.

Examples:
  # smoke-scale coded training with injected faults + checkpointing
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \\
      --steps 50 --scheme heter_aware --s 1 --m 4 --straggler fault

  # resume after a (simulated) cluster loss with a different worker count
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \\
      --steps 80 --m 6 --ckpt-dir /tmp/ck --resume

The first line printed names the devices the run found (platform, kind,
count); the last is a JSON summary, which ``main`` also returns.  The spmd
backend's Pallas kernels run compiled on a TPU and in interpret mode on any
other platform (tests and CPU runs).  ``chip_smoke.py`` at the repo root
drives this entry point on one TPU chip, and the spmd backend on four.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.approx import DEADLINE_MODES, DeadlinePolicy
from repro.checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from repro.configs import CodingConfig, TrainConfig, get_config
from repro.core.registry import scheme_names
from repro.core.straggler import (
    FixedDelayStragglers,
    NoStragglers,
    TransientStragglers,
)
from repro.data.pipeline import SyntheticData
from repro.launch.runtime import device_info, enable_compilation_cache
from repro.models.lm import build_model
from repro.obs.trace import Tracer
from repro.optim.adam import adamw_init
from repro.resilience import parse_fault_spec
from repro.train.engine import BACKENDS
from repro.train.trainer import CodedTrainer, TrainerState


def straggler_from_args(args):
    if args.straggler == "none":
        return NoStragglers()
    if args.straggler == "delay":
        return FixedDelayStragglers(s=args.s, delay=args.delay)
    if args.straggler == "fault":
        return FixedDelayStragglers(s=args.s, delay=np.inf)
    if args.straggler == "transient":
        return TransientStragglers()
    raise ValueError(args.straggler)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", help="smoke-scale config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--scheme", default="heter_aware", choices=list(scheme_names()))
    # 'spmd' needs one device per coded worker: launch through scripts/run.sh
    # with CPU_DEVICES=m (or a real accelerator topology) — the §13 elastic
    # rebuild then keeps the mesh live across membership changes
    ap.add_argument("--backend", default="fused", choices=list(BACKENDS),
                    help="gradient backend: fused (production) | reference "
                         "(oracle) | spmd (shard_map wire path; needs >= m "
                         "devices)")
    ap.add_argument("--s", type=int, default=1)
    ap.add_argument("--m", type=int, default=4, help="coded workers")
    ap.add_argument("--part-mb", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--straggler", default="none", choices=["none", "delay", "fault", "transient"])
    ap.add_argument("--delay", type=float, default=2.0)
    ap.add_argument("--deadline-mode", default="none", choices=["none", *DEADLINE_MODES],
                    help="inexact stepping: step at a deadline with whatever decoded "
                         "(none = the paper's exact semantics)")
    ap.add_argument("--target-residual", type=float, default=0.2,
                    help="bounded_residual mode: step once the decode's RMS residual "
                         "drops to this")
    ap.add_argument("--deadline-slack", type=float, default=1.5,
                    help="adaptive deadline = slack x EWMA-predicted exact iteration time")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="fixed deadline in (simulated) seconds; overrides adaptation")
    ap.add_argument("--speeds", default=None, help="comma-sep true worker speeds")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome/Perfetto trace of the run (open in "
                         "ui.perfetto.dev); enables the flight recorder")
    ap.add_argument("--log-jsonl", default=None, metavar="PATH",
                    help="write the structured event log (one train.step JSON "
                         "object per step + instants) for repro.launch.obs_report")
    ap.add_argument("--trace-capacity", type=int, default=1 << 16,
                    help="flight-recorder ring size (records); oldest dropped beyond it")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="inject failures (DESIGN.md §11), e.g. "
                         "'crash:3@40,hang:1@20+10,flaky:2@0..100:0.3,"
                         "corrupt:0@50..60'; enables the fault supervisor "
                         "(suspicion-driven eviction + re-admission)")
    ap.add_argument("--fault-seed", type=int, default=0,
                    help="RNG key for flaky/corrupt fault realizations")
    ap.add_argument("--compress", action="store_true",
                    help="int8 wire compression with error feedback on the "
                         "coded gradient (spmd wire format emulated on the "
                         "other backends)")
    ap.add_argument("--wire-kernel", default="auto", choices=["auto", "on", "off"],
                    help="fused Pallas int8 wire kernels for --compress: "
                         "auto = on only where the fused encode measured "
                         "faster than the unfused composition on this host "
                         "(DESIGN.md §12)")
    args = ap.parse_args(argv)
    print(json.dumps({"device": device_info()}), flush=True)
    enable_compilation_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    speeds = (
        np.array([float(x) for x in args.speeds.split(",")])
        if args.speeds
        else np.linspace(1.0, 2.0, args.m)
    )
    coding = CodingConfig(
        scheme=args.scheme, s=args.s, compress=args.compress,
        wire_kernel={"auto": None, "on": True, "off": False}[args.wire_kernel],
    )
    tc = TrainConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1), total_steps=args.steps, seed=args.seed)
    policy = None
    if args.deadline_mode != "none":
        policy = DeadlinePolicy(
            mode=args.deadline_mode, target_residual=args.target_residual,
            slack=args.deadline_slack, deadline_s=args.deadline_s,
        )
    tracer = (
        Tracer(capacity=args.trace_capacity)
        if (args.trace_out or args.log_jsonl)
        else None
    )
    faults = parse_fault_spec(args.faults) if args.faults else None
    mesh = None
    if args.backend == "spmd":
        from repro.launch.mesh import make_auto_mesh

        if len(jax.devices()) < args.m:
            raise SystemExit(
                f"--backend spmd needs >= {args.m} devices for m={args.m} "
                f"coded workers, found {len(jax.devices())}; launch via "
                f"CPU_DEVICES={args.m} ./scripts/run.sh ... (or more, so "
                f"membership can grow)"
            )
        mesh = make_auto_mesh((args.m, 1), ("data", "model"))
    trainer = CodedTrainer(
        model, coding, tc, m=args.m, part_mb=args.part_mb, mesh=mesh,
        straggler_model=straggler_from_args(args), true_speeds=speeds, rng=args.seed,
        backend=args.backend, deadline_policy=policy, trace=tracer,
        faults=faults, fault_seed=args.fault_seed,
    )
    data = SyntheticData(cfg, k=trainer.k, part_mb=args.part_mb, seq_len=args.seq_len, seed=args.seed)

    state = trainer.init_state(jax.random.PRNGKey(args.seed))
    start = 0
    ckpt = AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    if ckpt and args.resume:
        last = latest_step(args.ckpt_dir)
        if last is not None:
            like = {"params": state.params, "opt": state.opt}
            restored, meta = restore_checkpoint(args.ckpt_dir, last, like)
            state = TrainerState(params=restored["params"], opt=restored["opt"], step=last)
            start = last
            print(f"resumed from step {last} (saved with m={meta.get('m')}, now m={args.m})")

    t0 = time.time()
    totals = {"sim": 0.0, "last": time.perf_counter()}
    losses, step_wall_s = [], []

    def on_step(step, st, metrics):
        # runs inside the double-buffered trainer loop (batch t+1 is already
        # uploading while this fires — DESIGN.md §6); the engine has read
        # the step's metrics back, so the device work of the step is done
        now = time.perf_counter()
        step_wall_s.append(now - totals["last"])
        totals["last"] = now
        losses.append(metrics["loss"])
        totals["sim"] += (
            metrics["sim_iter_time"] if np.isfinite(metrics["sim_iter_time"]) else 0.0
        )
        if step % args.log_every == 0 or step == args.steps - 1:
            print(
                f"step {step:5d} loss {metrics['loss']:.4f} gnorm {metrics['grad_norm']:.3f} "
                f"sim_T {metrics['sim_iter_time']:.3f}s stragglers {metrics['n_stragglers']:.0f} "
                f"used {metrics['n_used']:.0f} residual {metrics['decode_residual']:.3f} "
                f"exact_frac {metrics['exact_fraction']:.2f}",
                flush=True,
            )
        if ckpt and (step + 1) % args.ckpt_every == 0:
            ckpt.save(step + 1, {"params": st.params, "opt": st.opt},
                      meta={"m": args.m, "scheme": args.scheme, "arch": args.arch})

    state, metrics = trainer.run(state, data, args.steps, start=start, on_step=on_step)
    sim_total = totals["sim"]
    if ckpt:
        ckpt.wait()
    if tracer is not None:
        if args.trace_out:
            tracer.write_chrome(args.trace_out)
            print(f"chrome trace: {args.trace_out} ({len(tracer)} records, "
                  f"{tracer.n_dropped} dropped) — open in ui.perfetto.dev")
        if args.log_jsonl:
            n = tracer.write_jsonl(args.log_jsonl)
            print(f"event log: {args.log_jsonl} ({n} lines) — analyse with "
                  f"python -m repro.launch.obs_report {args.log_jsonl}")
    # metrics is {} when the loop ran zero steps (e.g. --resume at --steps)
    summary = {
        "n_params": model.param_count(state.params),
        "final_loss": metrics.get("loss"), "wall_s": time.time() - t0,
        "sim_time_total_s": sim_total, "scheme": args.scheme, "m": args.m,
        "deadline_mode": args.deadline_mode,
        "exact_fraction": metrics.get("exact_fraction"),
        "steps_run": max(args.steps - start, 0),
        "losses": losses,
        "step_wall_s": step_wall_s,
        **(
            {"resilience": trainer.supervisor.summary(), "m_final": trainer.m}
            if trainer.supervisor is not None else {}
        ),
    }
    print(json.dumps(summary))
    return summary


if __name__ == "__main__":
    main()
