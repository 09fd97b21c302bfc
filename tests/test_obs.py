"""Observability layer (DESIGN.md §10): flight-recorder tracing, shared
summaries, straggler forensics, and the zero-overhead-when-off contract.

The acceptance trace is a real CodedTrainer run with wrong initial speed
estimates, an elastic rebalance cadence, scheduled churn, and (separately)
a deadline policy that guarantees inexact decodes — every marker the layer
promises must actually appear, the Chrome export must be strict JSON with
sane nesting, and serving spans must equal the RequestRecord timestamps
verbatim.  Tracing OFF must leave numerics bit-equal and record nothing.
"""

import json
import math
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.approx.deadline import DeadlinePolicy
from repro.configs.base import CodingConfig, TrainConfig
from repro.core.simulator import ChurnSchedule, MembershipEvent
from repro.core.straggler import FixedDelayStragglers, NoStragglers
from repro.launch import obs_report
from repro.obs import NULL_TRACER, StragglerForensics, Summary, Tracer, pct
from repro.train.trainer import CodedTrainer

M = 5


class _Probe:
    """Tiny LM-contract model: obs tests measure instrumentation, not math."""

    d = 8

    def init(self, rng):
        return {"w": jax.random.normal(rng, (self.d, 1), jnp.float32)}

    def weighted_loss(self, params, batch):
        pred = jnp.mean(batch["x"], axis=1) @ params["w"]
        return jnp.sum(pred[:, 0] ** 2 * batch["weight"])


def _mk(trace=None, *, m=M, straggler=None, policy=None, churn=None,
        rebalance_every=0, rng=0):
    coding = CodingConfig(scheme="heter_aware", s=1, rebalance_every=rebalance_every)
    tc = TrainConfig(lr=1e-3, warmup_steps=2, total_steps=64)
    tr = CodedTrainer(
        _Probe(), coding, tc, m=m, part_mb=2,
        straggler_model=straggler or NoStragglers(),
        true_speeds=np.linspace(1.0, 3.0, m), rng=rng,
        deadline_policy=policy, churn=churn, trace=trace,
    )
    r = np.random.default_rng(0)
    pb = {"x": r.normal(size=(tr.k, 2, 8, _Probe.d)).astype(np.float32)}
    return tr, pb


def _run(tr, pb, steps):
    state = tr.init_state(jax.random.PRNGKey(0))
    out = []
    for _ in range(steps):
        state, metrics = tr.step(state, pb)
        out.append(metrics)
    return state, out


# ---------------------------------------------------------------------------
# shared summary stats
# ---------------------------------------------------------------------------


def test_pct_bit_equal_to_np_percentile():
    xs = np.random.default_rng(0).normal(size=257)
    for q in (0, 12.5, 50, 99, 100):
        assert pct(xs, q) == float(np.percentile(xs, q))
        assert pct(list(xs), q) == float(np.percentile(np.asarray(list(xs)), q))
    assert math.isnan(pct([], 50))
    assert math.isnan(pct(np.empty(0), 99))


def test_summary_exact_matches_numpy():
    xs = np.random.default_rng(1).exponential(size=100)
    s = Summary()
    s.extend(xs)
    row = s.summary()
    assert row["n"] == 100 and s.exact
    assert row["mean"] == pytest.approx(float(xs.mean()))
    assert row["p50"] == float(np.percentile(xs, 50))
    assert row["p99"] == float(np.percentile(xs, 99))
    assert row["min"] == float(xs.min()) and row["max"] == float(xs.max())
    assert math.isnan(Summary().summary()["p50"])


def test_summary_reservoir_bounds_memory_deterministically():
    xs = np.random.default_rng(2).normal(size=1000)
    a, b = Summary(reservoir=64, seed=7), Summary(reservoir=64, seed=7)
    a.extend(xs)
    b.extend(xs)
    assert len(a._xs) == 64 and not a.exact
    assert a.n == 1000 and a.total == pytest.approx(float(xs.sum()))
    assert a.min() == float(xs.min()) and a.max() == float(xs.max())  # exact
    assert a.percentile(50) == b.percentile(50)  # seeded → deterministic


# ---------------------------------------------------------------------------
# tracer core
# ---------------------------------------------------------------------------


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    with NULL_TRACER.span("anything", tid=3, foo=1) as sp:
        assert sp.set(bar=2) is sp
    NULL_TRACER.instant("x")
    NULL_TRACER.counter("x", 1.0)
    NULL_TRACER.event("x", a=1)
    assert NULL_TRACER.clock() == 0.0


def test_ring_capacity_evicts_oldest():
    tr = Tracer(capacity=8)
    for i in range(20):
        tr.instant("e", t=float(i), clock="sim", i=i)
    assert len(tr) == 8
    assert tr.n_dropped == 12
    kept = [r["args"]["i"] for r in tr.records()]
    assert kept == list(range(12, 20))  # newest window survives
    tr.clear()
    assert len(tr) == 0 and tr.n_dropped == 0
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_jsonl_roundtrip_preserves_records(tmp_path):
    tr = Tracer()
    tr.span_at("s", 0.0, 1.5, clock="sim", tid=2, k="v")
    tr.instant("i", t=float("inf"), clock="sim")  # honest inf in JSONL
    tr.event("e", arr=np.arange(3), scalar=np.float64(2.5))
    path = tmp_path / "log.jsonl"
    n = tr.write_jsonl(str(path))
    assert n == 3
    back = obs_report.load_records(str(path))
    assert [r["name"] for r in back] == ["s", "i", "e"]
    assert back[0]["t1"] == 1.5 and back[0]["args"] == {"k": "v"}
    assert back[1]["t"] == float("inf")
    assert back[2]["args"]["arr"] == [0, 1, 2]  # numpy coerced
    # filtered export
    assert tr.write_jsonl(str(path), kinds=("event",)) == 1


# ---------------------------------------------------------------------------
# tentpole acceptance: instrumented trainer run → valid nested Chrome trace
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_run():
    """Rebalance + churn + exact/skip dynamics in ONE traced run: wrong
    initial estimates (c starts at ones vs true 1..3) with rebalance_every=2
    guarantees an elastic re-encode; a scheduled join at step 4 guarantees a
    churn transition."""
    tracer = Tracer()
    tr, pb = _mk(
        tracer, rebalance_every=2,
        churn=ChurnSchedule([MembershipEvent(step=4, join_speeds=(2.5,))]),
    )
    _run(tr, pb, 8)
    return tr, tracer


def test_chrome_trace_is_strict_json(traced_run, tmp_path):
    _, tracer = traced_run
    path = tmp_path / "trace.json"
    tracer.write_chrome(str(path))
    with open(path) as f:
        doc = json.loads(f.read(), parse_constant=lambda c: pytest.fail(
            f"non-RFC constant {c} in Chrome export"))
    evs = doc["traceEvents"]
    assert {e["ph"] for e in evs} <= {"M", "X", "i", "C"}
    for e in evs:
        if e["ph"] == "X":
            assert e["dur"] >= 0.0
            assert math.isfinite(e["ts"]) and math.isfinite(e["dur"])
    names = {e["name"] for e in evs}
    assert "process_name" in names  # clock-domain metadata present


def test_trace_spans_nest_and_markers_present(traced_run):
    _, tracer = traced_run
    spans = tracer.records("span")
    names = {r["name"] for r in tracer.records()}
    # step phases on the wall clock
    for phase in ("step", "step.resolve", "phase.upload", "phase.dispatch",
                  "phase.readback"):
        assert phase in names, f"missing {phase}"
    # the guaranteed markers
    assert "elastic.rebalance" in names
    assert "churn" in names
    assert "elastic.membership" in names
    assert "deadline.resolve" in names
    assert "arrive" in names
    # sim iteration windows: sequential, non-overlapping, positive
    iters = [r for r in spans if r["name"] == "sim.iteration"]
    assert len(iters) == 8
    for a, b in zip(iters, iters[1:]):
        assert a["t1"] <= b["t0"] + 1e-12
    # wall phase spans nest inside their step span
    steps = [r for r in spans if r["name"] == "step"]
    assert len(steps) == 8
    for ph in (r for r in spans if r["name"].startswith("phase.")):
        assert any(s["t0"] - 1e-9 <= ph["t0"] and ph["t1"] <= s["t1"] + 1e-9
                   for s in steps), "phase span outside every step span"
    # per-worker arrivals land on worker tracks within the iteration window
    by_step = {r["args"]["step"]: r for r in iters}
    for arr in (r for r in tracer.records("instant") if r["name"].startswith("arrive")):
        it = by_step[arr["args"]["step"]]
        assert it["t0"] - 1e-9 <= arr["t"] <= it["t1"] + 1e-9
        assert arr["tid"] == arr["args"]["worker"] + 1


def test_forensics_track_rebalance_and_churn(traced_run):
    tr, _ = traced_run
    fx = tr.forensics
    assert fx is not None
    assert len(fx.rebalances) >= 1
    assert len(fx.transitions) == 1 and fx.transitions[0]["m_after"] == M + 1
    assert fx.m == M + 1  # ledger restarted at the post-churn worker count
    assert len(fx.epochs) == 1  # pre-churn table archived


def test_inexact_decodes_are_blamed():
    """s+1 infinite stragglers under a fixed deadline: every step decodes
    best-effort → decode.inexact instants + per-worker blame."""
    tracer = Tracer()
    tr, pb = _mk(
        tracer,
        straggler=FixedDelayStragglers(s=2, delay=np.inf),
        policy=DeadlinePolicy(mode="fixed_deadline", deadline_s=5.0),
    )
    _, metrics = _run(tr, pb, 5)
    assert all(m["exact"] == 0.0 for m in metrics)
    inexact = tracer.records("instant", "decode.inexact")
    assert len(inexact) == 5
    fx = tr.forensics
    assert fx.hurt_steps == 5
    table = fx.blame_table()
    assert sum(r["blame"] for r in table) > 0
    assert table[0]["blame"] >= table[-1]["blame"]  # sorted most-blamed first
    # offline rebuild from the event log agrees with the live ledger
    recs = [json.loads(line) for line in tracer.iter_jsonl()]
    fx2 = StragglerForensics.from_records(recs)
    assert fx2.steps == fx.steps and fx2.hurt_steps == fx.hurt_steps
    assert [r["blame"] for r in fx2.blame_table()] == [r["blame"] for r in table]


# ---------------------------------------------------------------------------
# zero-overhead-when-off: no events, bit-equal numerics
# ---------------------------------------------------------------------------


def test_tracing_off_records_nothing_and_is_bit_equal():
    kw = dict(straggler=FixedDelayStragglers(s=1, delay=2.0), rebalance_every=3)
    t_off, pb = _mk(None, **kw)
    t_on, _ = _mk(Tracer(), **kw)
    assert t_off.tracer is NULL_TRACER and t_off.forensics is None
    assert t_off.engine.tracer is NULL_TRACER
    assert t_off.elastic.tracer is NULL_TRACER

    s_off, m_off = _run(t_off, pb, 6)
    s_on, m_on = _run(t_on, pb, 6)
    assert m_off == m_on  # identical keys AND bit-equal float values
    for a, b in zip(jax.tree.leaves(s_off.params), jax.tree.leaves(s_on.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert len(t_on.tracer) > 0  # the traced twin actually recorded


# ---------------------------------------------------------------------------
# spans reach the profiler: every wall span is a TraceAnnotation
# ---------------------------------------------------------------------------


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs (thread, event,
    name) for every annotation entered and left."""

    def __init__(self):
        self.log = []
        self._lock = threading.Lock()

    def __call__(self, name, **kw):
        rec = self

        class _Ann:
            def __enter__(self):
                with rec._lock:
                    rec.log.append((threading.get_ident(), "enter", name))

            def __exit__(self, *exc):
                with rec._lock:
                    rec.log.append((threading.get_ident(), "exit", name))

        return _Ann()


class _Batches:
    def __init__(self, pb):
        self.pb = pb

    def batch(self, step):
        return self.pb


WALL_SPANS = ("step", "step.resolve", "step.observe", "phase.upload",
              "phase.dispatch", "phase.readback", "prefetch.wait")


def _annotated_run(monkeypatch, trace):
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    tr, pb = _mk(trace)
    tr.run(tr.init_state(jax.random.PRNGKey(0)), _Batches(pb), 2)
    return ann.log


def test_spans_enter_profiler_annotations_nested_per_thread(monkeypatch):
    log = _annotated_run(monkeypatch, Tracer())
    main = threading.get_ident()
    entered = [(t, n) for t, ev, n in log if ev == "enter"]
    for name in WALL_SPANS:
        assert entered.count((main, name)) == 2, name
    uploads = [t for t, n in entered if n == "prefetch.upload"]
    assert len(uploads) == 2 and main not in uploads
    stacks = {}
    for t, ev, name in log:  # every annotation closes in LIFO order on its thread
        st = stacks.setdefault(t, [])
        if ev == "enter":
            st.append(name)
        else:
            assert st and st.pop() == name
    assert all(not st for st in stacks.values())
    steps = [n for t, ev, n in log if t == main and ev == "enter"]
    # the step's phases run inside it; the wait for the next batch outside
    depth, inside = 0, set()
    for t, ev, name in log:
        if t != main:
            continue
        if name == "step":
            depth += 1 if ev == "enter" else -1
        elif ev == "enter" and depth:
            inside.add(name)
    assert inside == set(WALL_SPANS) - {"step", "prefetch.wait"}
    assert steps[0] == "prefetch.wait"


def test_tracing_off_enters_no_annotation(monkeypatch):
    log = _annotated_run(monkeypatch, None)
    assert log == []


def test_ring_span_records_the_annotated_interval(monkeypatch):
    ann = _Annotations()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", ann)
    tracer = Tracer()
    with tracer.span("outer", tid=2, k=1) as sp:
        sp.set(j=2)
        with tracer.span("inner"):
            pass
    assert [(ev, n) for _, ev, n in ann.log] == [
        ("enter", "outer"), ("enter", "inner"), ("exit", "inner"), ("exit", "outer")]
    inner, outer = tracer.records("span")
    assert outer["clock"] == "wall" and outer["tid"] == 2
    assert outer["args"] == {"k": 1, "j": 2}
    assert outer["t0"] <= inner["t0"] <= inner["t1"] <= outer["t1"]


# ---------------------------------------------------------------------------
# serving spans == RequestRecord, verbatim
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serving_traced():
    from repro.configs import get_config
    from repro.core.straggler import FixedDelayStragglers as FDS
    from repro.models.lm import build_model
    from repro.serve import ReplicaPool, Request, ServingEngine
    from repro.train.serve import LMServer

    cfg = get_config("smollm-360m").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pool = ReplicaPool(
        np.linspace(1.0, 3.0, 6), s=2, k=12, comm_time=0.01,
        straggler_model=FDS(s=2, delay=4.0),
        policy=DeadlinePolicy.for_slo(ttft_slo_s=0.5), seed=0,
    )
    tracer = Tracer()
    eng = ServingEngine(
        LMServer(model), params, n_slots=2, cache_len=24,
        replicas=pool, decode_dt=0.01, trace=tracer,
    )
    rng = np.random.default_rng(3)
    reqs = [
        Request(rid=i, tokens=rng.integers(0, cfg.vocab, (8,)),
                max_new_tokens=4, arrival_t=0.05 * i)
        for i in range(5)
    ]
    # one guaranteed rejection: prompt longer than the slot cache
    rejected = Request(rid=99, tokens=rng.integers(0, cfg.vocab, (64,)),
                       max_new_tokens=2, arrival_t=0.0)
    assert eng.submit(rejected) is False
    comps, metrics = eng.run(reqs)
    return comps, metrics, tracer


def test_serving_spans_match_request_records(serving_traced):
    comps, metrics, tracer = serving_traced
    spans = {
        (r["name"], r["tid"]): r for r in tracer.records("span")
    }
    assert len(comps) == 5
    for c in comps:
        rec = c.record
        tid = rec.rid
        top = spans[("request", tid)]
        assert top["clock"] == "sim"
        assert top["t0"] == rec.arrival_t and top["t1"] == rec.done_t
        q = spans[("request.queue", tid)]
        assert (q["t0"], q["t1"]) == (rec.arrival_t, rec.admit_t)
        p = spans[("request.prefill", tid)]
        assert (p["t0"], p["t1"]) == (rec.admit_t, rec.prefill_done_t)
        assert p["args"]["exact"] == rec.prefill_exact
        d = spans[("request.decode", tid)]
        assert (d["t0"], d["t1"]) == (rec.prefill_done_t, rec.done_t)
        # nesting: queue ⊆ request, prefill ⊆ request, decode ⊆ request
        for child in (q, p, d):
            assert top["t0"] <= child["t0"] and child["t1"] <= top["t1"] + 1e-12
        ft = [r for r in tracer.records("instant", "request.first_token")
              if r["tid"] == tid]
        assert len(ft) == 1 and ft[0]["t"] == rec.first_token_t
        if not rec.prefill_exact:
            assert any(r["tid"] == tid
                       for r in tracer.records("instant", "prefill.inexact"))
    rejects = tracer.records("instant", "request.reject")
    assert len(rejects) == 1 and rejects[0]["args"]["rid"] == 99
    assert metrics.summary()["n_rejected"] == 1.0
    active = tracer.records("counter", "serve.active")
    assert active and all(r["args"]["value"] >= 1.0 for r in active)


# ---------------------------------------------------------------------------
# obs_report CLI
# ---------------------------------------------------------------------------


def test_obs_report_end_to_end(traced_run, tmp_path, capsys):
    _, tracer = traced_run
    path = tmp_path / "run.jsonl"
    n = tracer.write_jsonl(str(path))
    assert n == len(tracer)
    obs_report.main([str(path), "--top-k", "3"])
    out = capsys.readouterr().out
    assert "span breakdown" in out
    assert "phase.dispatch" in out and "phase.readback" in out
    assert "sim.iteration" in out
    assert "straggler forensics" in out
    assert "top blame" in out
    # aggregation helpers agree with the raw records
    records = obs_report.load_records(str(path))
    rows = obs_report.phase_table(records)
    for name in ("phase.dispatch", "phase.readback"):
        row = next(r for r in rows if r["phase"] == name)
        assert row["n"] == 8 and row["clock"] == "wall"
    rep = obs_report.blame_report(records, top_k=2)
    assert rep["summary"]["steps"] > 0 and len(rep["blame"]) <= 2
