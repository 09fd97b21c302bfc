"""One run of one benchmark cell: set-up, the measured window, the traced
steps, the reference comparison and the result line.

Everything about a cell is found by name: ``BENCHMARK.json`` names the
configuration, the traffic mix and the per-layer metrics; the
configuration is ``configs/<config>.json`` with its reference
``references/<reference>.py`` (which also counts its model FLOPs), the mix
is ``traffic/<traffic>.json``, a metric, end-to-end or per-layer, is
``metrics/<metric>.py`` and the cell's limits on the numbers compared are
``limits/<workload>.json``, all under this directory.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(ROOT / "src"))

import check  # noqa: E402
import xplane  # noqa: E402
from datagen import TokenBatches  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"  # fixed: the path is part of the cache key
TRACE_DIR = ROOT / ".bench_trace"
TRACED_STEPS = 3
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class NoChip(SystemExit):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    config: dict  # configs/<config>.json
    traffic: dict  # traffic/<traffic>.json
    chips: int
    per_layer: list  # BENCHMARK.json per_layer entries this cell reports
    end_to_end: list  # BENCHMARK.json end_to_end entries this cell reports
    limits: dict | None  # limits/<workload>.json "limits", None before they exist


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload`` in ``root/BENCHMARK.json``."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bench = root / "bench"
    limits_path = bench / "limits" / f"{workload}.json"

    def mine(entry):
        return workload in entry.get("workloads", [workload])

    return Cell(
        name=workload,
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads((bench / "traffic" / f"{w['traffic']}.json").read_text()),
        chips=int(w["chips"]),
        per_layer=[m for m in spec["per_layer"] if mine(m)],
        end_to_end=[m for m in spec["end_to_end"] if mine(m)],
        limits=json.loads(limits_path.read_text())["limits"] if limits_path.exists() else None,
    )


def seed32(seed: int) -> int:
    """A 31-bit seed drawn from a seed of any size (PRNGKey keeps 32 bits)."""
    import numpy as np

    return int(np.random.SeedSequence(seed).generate_state(1)[0] >> 1)


def feed(vocab: int, k: int, mb: int, seq: int, seed: int) -> TokenBatches:
    """What the program is fed (a seam that the fault tests wrap)."""
    return TokenBatches(vocab=vocab, k=k, mb=mb, seq=seq, seed=seed)


def _annotate(obj, attr: str, label: str):
    """Wrap ``obj.attr`` in a profiler annotation (an instance attribute, so
    the program's own code is untouched)."""
    import jax

    fn = getattr(obj, attr)

    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(label):
            return fn(*a, **kw)

    setattr(obj, attr, wrapped)


def stragglers(spec: dict):
    """The traffic's straggler model; an unknown kind is an error."""
    from repro.core.straggler import FixedDelayStragglers

    kinds = {"fault": lambda: FixedDelayStragglers(s=spec["count"], delay=math.inf)}
    if spec["kind"] not in kinds:
        raise ValueError(f"unknown straggler kind {spec['kind']!r}; known: {sorted(kinds)}")
    return kinds[spec["kind"]]()


def read_metrics(entries: list, ctx: dict) -> dict:
    """Each metric of ``entries`` (``BENCHMARK.json`` entries) read from
    ``ctx`` by its reader ``metrics/<name>.py``; a reader that finds nothing
    to read returns None and its metric is left out."""
    out = {}
    for m in entries:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class _WindowClosed(Exception):
    pass


def run(cell: Cell, seed: int, seconds: float, trace: bool, *, t_start: float,
        require_chip: bool = True, log=sys.stderr) -> dict:
    """One run; returns the result line's object."""
    import jax
    import numpy as np

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    if require_chip and (device["platform"] != "tpu" or device["count"] < cell.chips):
        raise NoChip(f"needs {cell.chips} TPU chip(s); JAX found {device}")
    if device["platform"] == "tpu":
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from repro.configs.base import CodingConfig, ModelConfig, TrainConfig
    from repro.models.lm import build_model
    from repro.obs.trace import Tracer
    from repro.optim.adam import adamw_init
    from repro.train.trainer import CodedTrainer, TrainerState

    cfg, tr = cell.config["model"], cell.traffic
    ref = load_module(BENCH / "references" / f"{cell.config['reference']}.py")
    opt = check.Optim(**tr["optimizer"])
    s32 = seed32(seed)
    key = jax.random.PRNGKey(s32)
    dtype = {"bfloat16": jax.numpy.bfloat16, "float32": jax.numpy.float32}[cfg["dtype"]]
    if tr["seq"] > cell.config["seq_len"]:
        raise ValueError(f"traffic seq {tr['seq']} exceeds the configuration's {cell.config['seq_len']}")

    init = jax.jit(lambda k: ref.init(cfg, k, dtype))
    params = init(key)
    model = build_model(ModelConfig(**cfg))
    tracer = Tracer(capacity=1 << 20) if trace else None
    speeds = np.asarray(tr["speeds"], np.float64)
    trainer = CodedTrainer(
        model, CodingConfig(scheme=tr["scheme"], s=tr["s"]),
        TrainConfig(lr=opt.lr, warmup_steps=opt.warmup_steps, total_steps=opt.total_steps,
                    beta1=opt.beta1, beta2=opt.beta2, eps=opt.eps,
                    weight_decay=opt.weight_decay, grad_clip=opt.grad_clip, seed=s32),
        m=tr["m"], part_mb=tr["part_mb"], straggler_model=stragglers(tr["stragglers"]),
        # the code and the faulted workers are drawn from the run's seed
        true_speeds=speeds, c_init=speeds, rng=s32, backend=tr["backend"], trace=tracer,
    )
    k, mb, seq = trainer.k, tr["part_mb"], tr["seq"]
    data = feed(cfg["vocab"], k, mb, seq, seed)
    if trace:
        _annotate(data, "batch", "bench.data")
        _annotate(trainer, "step", "bench.step")
        _annotate(trainer.elastic, "tick", "host.tick")
        _annotate(trainer.elastic, "observe", "host.observe")
        _annotate(trainer.engine, "step", "host.engine_step")
    state = TrainerState(params=params, opt=jax.jit(adamw_init)(params), step=0)
    del params

    # -- set-up: the first steps, through the window's own call and feed ----
    got, copy_s = {"loss": []}, [0.0]

    def on_check(step, st_, metrics):
        got["loss"].append(float(metrics["loss"]))
        if step == 0:
            mu = st_.opt.mu
            got["grad"] = {n: v / (1 - opt.beta1) for n, v in
                           check.norms_dict(mu, check.leaf_norms(mu)).items()}
            t = time.perf_counter()  # the copy is the check's, not set-up
            got["grad_host"] = check.host_tree(mu, 1 / (1 - opt.beta1))
            copy_s[0] = time.perf_counter() - t
        if step == check.CHECK_STEPS - 1:
            kept = st_.opt.master if st_.opt.master is not None else st_.params
            got["change"] = check.norms_dict(kept, check.diff_norms(kept, init(key)))

    state, _ = trainer.run(state, data, check.CHECK_STEPS, on_step=on_check)
    compiles = []

    def on_event(event, *_a, **_kw):
        if event in COMPILE_EVENTS:
            compiles.append(event)

    # -- the measured window -------------------------------------------------
    if tracer is not None:
        tracer.clear()
    steps_s, skipped, latest = [], [0], {"state": state}
    jax.monitoring.register_event_duration_secs_listener(on_event)
    t0 = time.perf_counter()
    setup_s = t0 - t_start - copy_s[0]
    last = [t0]

    def on_window(step, st_, metrics):
        now = time.perf_counter()
        steps_s.append(now - last[0])
        last[0] = now
        skipped[0] += int(metrics.get("skipped", 0.0) > 0)
        latest["state"] = st_
        if now - t0 >= seconds:
            raise _WindowClosed

    try:
        trainer.run(state, data, 1 << 30, start=check.CHECK_STEPS, on_step=on_window)
    except _WindowClosed:
        pass
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    window_s = last[0] - t0
    n_steps = len(steps_s)
    state = latest["state"]
    print(f"window: {n_steps} steps in {window_s} s, {skipped[0]} skipped, "
          f"{len(compiles)} compile events inside it", file=log)

    ctx = {
        "steps_s": steps_s, "window_s": window_s, "skipped": skipped[0], "setup_s": setup_s,
        "tokens_per_step": k * mb * seq, "chips": cell.chips, "device": device,
        # forward and backward (twice the forward) over the unique batch only
        "flops_per_step": 3.0 * k * mb * ref.forward_flops(cfg, seq),
        "peaks": _peaks(device["kind"]) if device["platform"] == "tpu" else None,
    }
    if tracer is not None:
        ctx["spans"] = tracer.records(kind="span")
    if tracer is not None and device["platform"] == "tpu":
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # Python call events would slow the host under trace
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        with jax.profiler.TraceAnnotation(xplane.WINDOW_MARK):
            first = check.CHECK_STEPS + n_steps
            trainer.run(state, data, first + TRACED_STEPS, start=first)
        jax.profiler.stop_trace()
        ctx["profiled_steps"] = TRACED_STEPS
        ctx["trace"] = xplane.reduce(xplane.load(str(TRACE_DIR)))
    used = devs[: cell.chips]
    mem = [d.memory_stats() or {} for d in used]
    peak = max(m.get("peak_bytes_in_use", 0) for m in mem)
    ctx["memory"] = {"peak_bytes_in_use": peak, "bytes_limit": min(m.get("bytes_limit", 0) for m in mem)}
    device["memory_peak_bytes"] = peak
    latest.clear()
    del state, trainer, data, on_check, on_window
    gc.collect()

    # -- the reference, once the program's state is freed ---------------------
    p32 = jax.jit(lambda k: jax.tree.map(lambda x: x.astype(jax.numpy.float32), ref.init(cfg, k, dtype)))(key)
    truth = TokenBatches(vocab=cfg["vocab"], k=k, mb=mb, seq=seq, seed=seed)
    batches = [truth.batch(t)["tokens"].reshape(k * mb, seq) for t in range(check.CHECK_STEPS)]
    want = check.reference_steps(ref, cfg, p32, batches, opt, against=got.pop("grad_host"))
    got["grad_diff"] = want.pop("grad_diff")
    vals = check.numbers(got, want)
    print(f"losses {got['loss']} reference {want['loss']}; worst leaves {check.worst_leaves(got, want)}", file=log)
    correct, compared = check.verdict(vals, cell.limits)

    if trace:
        red = ctx.get("trace")
        if red is not None:
            device["busy_s"] = red["busy_ns"] * 1e-9
            device["window_s"] = red["window_ns"] * 1e-9
        metrics = read_metrics(cell.per_layer, ctx)
        if "step_ms_p90" in metrics:
            print(f"step_ms_p90 over {n_steps} window steps", file=log)
    else:
        metrics = read_metrics(cell.end_to_end, ctx)
    result = {"correct": correct, "attempted": n_steps, "failed": skipped[0],
              "metrics": metrics, "device": device}
    if ctx.get("trace") is not None:
        result["breakdown"] = xplane.breakdown(ctx["trace"])
    result["checks"] = compared
    for name, c in compared.items():
        print(f"{name} {c['value']} limit {c['limit']}", file=log)
    return result


def _peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; known: {sorted(table)}")
    return table[kind]
