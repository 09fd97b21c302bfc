"""The comparison that decides ``correct`` for a training cell.

The program's first three steps (taken in set-up, through the same trainer
call and feed as the window) are compared with a plain float32 reference
that follows the same three steps from the same weights and tokens:

- ``first_loss_gap``: the first step's |loss − reference| / reference.  Only
  the first: at lr 1e-3 with no warm-up the third step overshoots (the
  loss rises from 8 to as much as 13 on some seeds), and there the two
  trajectories part by up to 5 % with no fault in either.  The first step's
  gap has no limit: neither the FP8 control nor a planted fault reads three
  times the program's on it, so it is printed for the record only;
- ``grad_gap``: the first gradient as the optimizer got it (its first moment
  after one step, divided by 1 − β1), by the worst leaf: the gap between
  the program's and the reference's norm of the leaf, over the larger of
  the reference's norm of that leaf and of the median leaf;
- ``grad_diff``: the same gradient by the worst leaf, but the norm of its
  difference from the reference's over the same denominator.  A leaf's
  norm moves with rounding only to second order, so the norm gap barely
  tells bfloat16 from FP8; the difference tells them apart;
- ``change_gap``: the same for the parameters' change over the three steps,
  leaving out the leaves whose reference gradient is under a thousandth of
  the median leaf's (Adam moves those by round-off alone).

An exactly decoded gradient code returns the gradient of the mean loss over
the unique batch, so the reference is plain data-parallel AdamW: it knows
nothing of partitions, slots or decode vectors.  The optimizer and its
learning-rate schedule are written out here from their published form.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

CHECK_STEPS = 3
NUMBERS = ("first_loss_gap", "grad_gap", "grad_diff", "change_gap")
UNMOVED = 1e-3  # of the median leaf's reference gradient norm


@dataclasses.dataclass(frozen=True)
class Optim:
    """AdamW with global-norm clipping and a linear-warmup cosine schedule."""

    lr: float
    warmup_steps: int
    total_steps: int
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    min_ratio: float = 0.1

    def lr_at(self, t: int) -> float:
        if t < self.warmup_steps:
            return self.lr * t / max(self.warmup_steps, 1)
        prog = min(max((t - self.warmup_steps) / max(self.total_steps - self.warmup_steps, 1), 0.0), 1.0)
        return self.lr * (self.min_ratio + (1 - self.min_ratio) * 0.5 * (1 + math.cos(math.pi * prog)))


def leaf_names(tree) -> list[str]:
    return [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


@jax.jit
def leaf_norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))) for x in jax.tree.leaves(tree)]


@jax.jit
def diff_norms(a, b):
    return [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))
    ]


def norms_dict(tree, values) -> dict[str, float]:
    return dict(zip(leaf_names(tree), (float(v) for v in values)))


# ---------------------------------------------------------------------------
# matrix products of the reference and of its lower-precision control
# ---------------------------------------------------------------------------


def mm_f32(x, w):
    return jnp.matmul(x, w.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)


def _q(x, dtype):
    """Round to ``dtype`` under one per-tensor scale (amax to the format's max)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(dtype).max) / amax, 1.0)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def mm_fp8(x, w):
    """FP8 training recipe: e4m3 operands forward, e5m2 gradients backward,
    per-tensor scaled, accumulated in float32."""
    return mm_f32(_q(x, jnp.float8_e4m3fn), _q(w.astype(jnp.float32), jnp.float8_e4m3fn))


def _mm_fp8_fwd(x, w):
    return mm_fp8(x, w), (x, w)


def _mm_fp8_bwd(res, g):
    x, w = res
    gq = _q(g, jnp.float8_e5m2)
    xq, wq = _q(x, jnp.float8_e4m3fn), _q(w.astype(jnp.float32), jnp.float8_e4m3fn)
    dx = jnp.matmul(gq, wq.T, precision=jax.lax.Precision.HIGHEST)
    dw = jnp.einsum("...i,...j->ij", xq, gq, precision=jax.lax.Precision.HIGHEST)
    return dx, dw.astype(w.dtype)


mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)
MATMULS = {"f32": mm_f32, "fp8": mm_fp8}


# ---------------------------------------------------------------------------
# the reference's three steps
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _step_fns(ref, cfg_json: str, mm_name: str, opt: Optim):
    """The reference's jitted programs, built once per (model, precision)."""
    cfg, mm = json.loads(cfg_json), MATMULS[mm_name]

    def block_loss(p, toks, n):
        return jnp.sum(ref.seq_losses(cfg, p, toks, mm)) / n

    @jax.jit
    def adam(p, g, m, v, t, lr):
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(1.0, opt.grad_clip / (gn + 1e-12)), g)
        m = jax.tree.map(lambda m, g: opt.beta1 * m + (1 - opt.beta1) * g, m, g)
        v = jax.tree.map(lambda v, g: opt.beta2 * v + (1 - opt.beta2) * g * g, v, g)
        c1, c2 = 1 - opt.beta1 ** (t + 1.0), 1 - opt.beta2 ** (t + 1.0)
        p = jax.tree.map(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + opt.eps) + opt.weight_decay * p),
            p, m, v,
        )
        return p, g, m, v

    grad_block = jax.jit(jax.value_and_grad(block_loss), static_argnums=2)
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
    return grad_block, add, adam


def host_tree(tree, scale: float = 1.0) -> list:
    """The leaves of a device tree copied to the host as float32, times
    ``scale``."""
    return [np.asarray(x, np.float32) * np.float32(scale) for x in jax.tree.leaves(tree)]


@jax.jit
def _diff_norm(x, y):
    return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - y)))


def reference_steps(ref, cfg: dict, params, batches, opt: Optim, *, mm: str = "f32",
                    rows_per_block: int = 2, keep_rows: float = 1.0, frozen: bool = False,
                    against: list | None = None, keep_grad: bool = False) -> dict:
    """Run ``len(batches)`` AdamW steps of ``ref.seq_losses`` from float32
    ``params`` on token arrays (n, seq), with the matrix products ``mm``
    (a key of :data:`MATMULS`).  Returns the losses, the first clipped
    gradient's leaf norms and the parameters' change leaf norms; with
    ``against`` (host leaves, as :func:`host_tree` gives them) also the leaf
    norms of the first gradient's difference from them, and with
    ``keep_grad`` the first gradient itself on the host.
    ``keep_rows`` < 1 plants the half-batch fault: the loss is the mean over
    that share of the rows.  ``frozen`` plants a step that returns its state
    unchanged: no update, and a first moment that stays zero.  The gradient
    is summed over blocks of ``rows_per_block`` rows, so that it fits beside
    the program's peak."""
    grad_block, add, adam = _step_fns(ref, json.dumps(cfg, sort_keys=True), mm, opt)
    p0 = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, out = [], {}
    with jax.default_matmul_precision("highest"):
        for t, toks in enumerate(batches):
            n = int(round(toks.shape[0] * keep_rows))
            toks = jnp.asarray(toks[:n])
            loss, g = 0.0, None
            for i in range(0, n, rows_per_block):
                lb, gb = grad_block(params, toks[i:i + rows_per_block], n)
                loss, g = loss + float(lb), (gb if g is None else add(g, gb))
            if frozen:
                g = jax.tree.map(jnp.zeros_like, g)
            else:
                params, g, m, v = adam(params, g, m, v, jnp.float32(t), jnp.float32(opt.lr_at(t)))
            losses.append(loss)
            if t == 0:
                out["grad"] = norms_dict(g, leaf_norms(g))
                if against is not None:
                    mine = jax.tree.leaves(g)
                    if [x.shape for x in mine] != [y.shape for y in against]:
                        raise ValueError("the gradients compared have different leaves")
                    out["grad_diff"] = norms_dict(g, [float(_diff_norm(x, y)) for x, y in zip(mine, against)])
                if keep_grad:
                    out["grad_host"] = host_tree(g)
            del g
        out["change"] = norms_dict(params, diff_norms(params, p0))
    return {"loss": losses, **out}


# ---------------------------------------------------------------------------
# the numbers compared
# ---------------------------------------------------------------------------


def _leaf_gap(got: dict, want: dict, keep=None) -> float:
    names = [k for k in want if keep is None or k in keep]
    base = float(np.median([want[k] for k in names]))
    return max(abs(got[k] - want[k]) / max(want[k], base) for k in names)


def worst_leaves(got: dict, want: dict) -> dict[str, str]:
    """The leaf that sets ``grad_gap`` and ``change_gap`` (for the log)."""
    out = {}
    for key in ("grad", "change"):
        base = float(np.median(list(want[key].values())))
        out[key] = max(want[key], key=lambda k: abs(got[key][k] - want[key][k]) / max(want[key][k], base))
    return out


def numbers(got: dict, want: dict) -> dict[str, float]:
    """``got`` and ``want`` as returned by :func:`reference_steps`; ``got``
    also holds ``grad_diff``, its first gradient's difference from
    ``want``'s by leaf."""
    med = float(np.median(list(want["grad"].values())))
    moved = {k for k, g in want["grad"].items() if g >= UNMOVED * med}
    vals = {
        "first_loss_gap": abs(got["loss"][0] - want["loss"][0]) / abs(want["loss"][0]),
        "grad_gap": _leaf_gap(got["grad"], want["grad"]),
        "grad_diff": max(d / max(want["grad"][k], med) for k, d in got["grad_diff"].items()),
        "change_gap": _leaf_gap(got["change"], want["change"], keep=moved),
    }
    return {k: (v if math.isfinite(v) else float("inf")) for k, v in vals.items()}


def verdict(vals: dict, limits: dict | None) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}).  Only the numbers that have a
    limit in ``limits/<workload>.json`` are compared; no limits at all: not
    correct."""
    limits = limits or {}
    out = {k: {"value": vals[k], "limit": limits.get(k)} for k in NUMBERS}
    ok = bool(limits) and all(vals[k] <= lim for k, lim in limits.items())
    return ok, out
