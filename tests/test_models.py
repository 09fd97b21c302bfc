"""Per-arch smoke tests (reduced configs) + serving equivalence + SSD math."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, cell_runnable, get_config
from repro.data.pipeline import SyntheticData
from repro.models.lm import build_model, layer_plan, plan_period


def _smoke_batch(cfg, B=2, S=32, seed=0):
    r = np.random.default_rng(seed)
    if cfg.frontend == "audio":
        return {
            "frames": jnp.asarray(r.normal(size=(B, S, cfg.d_model)), jnp.float32),
            "labels": jnp.asarray(r.integers(0, cfg.vocab, (B, S)), jnp.int32),
            "weight": jnp.ones((B,), jnp.float32),
        }
    b = {
        "tokens": jnp.asarray(r.integers(0, cfg.vocab, (B, S)), jnp.int32),
        "weight": jnp.ones((B,), jnp.float32),
    }
    b["labels"] = b["tokens"]
    if cfg.frontend == "vision":
        b["patches"] = jnp.asarray(r.normal(size=(B, cfg.n_patches, cfg.d_model)) * 0.02, jnp.float32)
    return b


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_forward_and_grad(arch):
    """Reduced config: one forward + train gradient, shapes + no NaNs."""
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _smoke_batch(cfg)
    logits, aux = model.forward(params, batch)
    S_total = batch.get("tokens", batch.get("frames")).shape[1]
    if cfg.frontend == "vision":
        S_total += cfg.n_patches
    assert logits.shape == (2, S_total, cfg.vocab)
    assert not bool(jnp.any(jnp.isnan(logits)))
    loss, grads = jax.value_and_grad(model.weighted_loss)(params, batch)
    assert np.isfinite(float(loss))
    assert all(not bool(jnp.any(jnp.isnan(g))) for g in jax.tree.leaves(grads))
    # one optimizer step moves the loss
    from repro.optim.adam import adamw_init, adamw_update

    opt = adamw_init(params)
    new_params, _ = adamw_update(params, grads, opt, lr=1e-3)
    loss2 = model.weighted_loss(new_params, batch)
    assert float(loss2) < float(loss)


@pytest.mark.parametrize("arch", [a for a in ARCHS if get_config(a).supports_decode])
def test_arch_prefill_decode_matches_forward(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    r = np.random.default_rng(2)
    B, S = 2, 16
    toks = jnp.asarray(r.integers(0, cfg.vocab, (B, S + 3)), jnp.int32)
    batch = {"tokens": toks, "labels": toks, "weight": jnp.ones((B,))}
    if cfg.frontend == "vision":
        batch["patches"] = jnp.asarray(r.normal(size=(B, cfg.n_patches, cfg.d_model)) * 0.02, jnp.float32)
    logits_full, _ = model.forward(params, batch)
    pre_batch = dict(batch)
    pre_batch["tokens"] = toks[:, :S]
    extra = cfg.n_patches if cfg.frontend == "vision" else 0
    lp, cache = model.prefill(params, pre_batch, cache_len=S + 8 + extra)
    outs = [lp]
    for t in range(3):
        lg, cache = model.decode_step(params, toks[:, S + t : S + t + 1], cache)
        outs.append(lg)
    off = cfg.n_patches if cfg.frontend == "vision" else 0
    for i, lg in enumerate(outs):
        ref = logits_full[:, off + S - 1 + i]
        np.testing.assert_allclose(np.asarray(lg), np.asarray(ref), atol=3e-2, rtol=3e-2)


def test_layer_plans():
    jamba = get_config("jamba-1.5-large-398b")
    plan = layer_plan(jamba)
    assert plan_period(plan) == 8
    assert sum(p.mixer == "attn" for p in plan) == jamba.n_layers // 8
    assert sum(p.mlp == "moe" for p in plan) == jamba.n_layers // 2
    assert plan_period(layer_plan(get_config("mamba2-370m"))) == 1
    assert all(p.mixer == "mamba" and p.mlp == "none" for p in layer_plan(get_config("mamba2-370m")))


def test_param_counts_match_spec():
    expected = {
        "mamba2-370m": 0.37e9, "smollm-360m": 0.36e9, "llama3.2-1b": 1.24e9,
        "chatglm3-6b": 6.2e9, "qwen2.5-14b": 14.8e9, "mixtral-8x7b": 46.7e9,
        "jamba-1.5-large-398b": 398e9, "hubert-xlarge": 1.26e9,
    }
    import math

    for arch, n_exp in expected.items():
        model = build_model(get_config(arch))
        shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        n = sum(math.prod(x.shape) for x in jax.tree.leaves(shapes))
        assert abs(n - n_exp) / n_exp < 0.05, f"{arch}: {n/1e9:.2f}B != {n_exp/1e9:.2f}B"


def test_cell_skip_rules():
    cells = {(a, s.name): cell_runnable(get_config(a), s)[0] for a in ARCHS for s in SHAPES.values()}
    assert sum(cells.values()) == 32  # documented in DESIGN.md §5
    assert not cells[("hubert-xlarge", "decode_32k")]
    assert not cells[("qwen2.5-14b", "long_500k")]
    assert cells[("mixtral-8x7b", "long_500k")]  # SWA
    assert cells[("mamba2-370m", "long_500k")]
    assert cells[("jamba-1.5-large-398b", "long_500k")]


def test_synthetic_data_determinism():
    cfg = get_config("smollm-360m").reduced()
    d1 = SyntheticData(cfg, k=4, part_mb=2, seq_len=16, seed=7)
    d2 = SyntheticData(cfg, k=4, part_mb=2, seq_len=16, seed=7)
    b1, b2 = d1.batch(3), d2.batch(3)
    for k in b1:
        np.testing.assert_array_equal(b1[k], b2[k])
    # partition function is addressable: partition j == batch slice j
    p2 = d1.partition(3, 2)
    np.testing.assert_array_equal(p2["tokens"], b1["tokens"][2])


# ---------------------------------------------------------------------------
# SSD math: the chunked form against the sequential scan
# ---------------------------------------------------------------------------


def _ssd_inputs(H, G, seed, B=2, S=48, P=8, N=16):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.normal(size=(B, S, H, P)), jnp.float32)
    dA = -jnp.asarray(r.uniform(0.01, 0.3, size=(B, S, H)), jnp.float32)
    Bm = jnp.asarray(r.normal(size=(B, S, G, N)), jnp.float32)
    Cm = jnp.asarray(r.normal(size=(B, S, G, N)), jnp.float32)
    h0 = jnp.asarray(r.normal(size=(B, H, P, N)), jnp.float32)
    wy = jnp.asarray(r.normal(size=(B, S, H, P)), jnp.float32)
    wh = jnp.asarray(r.normal(size=(B, H, P, N)), jnp.float32)
    return (x, dA, Bm, Cm, h0), (wy, wh)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("with_h0", [False, True], ids=["h0_zero", "h0_given"])
@pytest.mark.parametrize("heads_groups", [(4, 1), (4, 2), (4, 4)], ids=lambda hg: f"H{hg[0]}G{hg[1]}")
def test_ssd_chunked_matches_sequential(heads_groups, with_h0):
    """Output, final state and the gradients for x, dA, B, C and h0 of the
    chunked SSD, whose groups serve rep = H // G heads each, equal the
    O(S) scan that gives every head its own copy of B and C."""
    from repro.models.ssm import ssd_chunked, ssd_sequential

    H, G = heads_groups
    args, (wy, wh) = _ssd_inputs(H, G, seed=10 * H + G + with_h0)
    if not with_h0:
        args = args[:4] + (None,)
    argnums = (0, 1, 2, 3, 4) if with_h0 else (0, 1, 2, 3)

    def loss(ssd):
        def f(x, dA, Bm, Cm, h0):
            y, h = ssd(x, dA, Bm, Cm, h0)
            return jnp.sum(y * wy) + jnp.sum(h * wh), (y, h)
        return jax.grad(f, argnums=argnums, has_aux=True)

    with jax.default_matmul_precision("highest"):
        g_ch, (y_ch, h_ch) = loss(lambda *a: ssd_chunked(*a[:4], 16, a[4]))(*args)
        g_sq, (y_sq, h_sq) = loss(ssd_sequential)(*args)
    assert _rel(y_ch, y_sq) < 1e-5
    assert _rel(h_ch, h_sq) < 1e-5
    for name, a, b in zip(("x", "dA", "B", "C", "h0"), g_ch, g_sq):
        assert a.shape == b.shape
        assert _rel(a, b) < 1e-5, name


@pytest.mark.parametrize("n_groups", [1, 2])
def test_mamba_forward_pads_to_chunk(n_groups):
    """A sequence that is not a chunk multiple: the zero-padded prefill
    gives the outputs and the cache that one-token decode builds step by
    step, and the input gradient of an unpadded chunking."""
    from repro.models.ssm import init_mamba, mamba_decode, mamba_forward

    dims = dict(d_inner=32, n_heads=4, d_state=8, n_groups=n_groups)
    params = init_mamba(jax.random.PRNGKey(3), 16, **dims)
    B, S = 2, 20  # chunk 16 pads to 32
    x = jnp.asarray(np.random.default_rng(4).normal(size=(B, S, 16)), jnp.float32)
    decode = jax.jit(lambda x, cache: mamba_decode(params, x, cache, **dims))
    grad = jax.jit(jax.grad(lambda x, c: jnp.sum(jnp.sin(mamba_forward(params, x, chunk=c, **dims)[0]))),
                   static_argnums=1)
    with jax.default_matmul_precision("highest"):
        out, cache = mamba_forward(params, x, chunk=16, return_cache=True, **dims)
        step = {"h": jnp.zeros_like(cache["h"]), "conv": jnp.zeros_like(cache["conv"])}
        outs = []
        for t in range(S):
            o, step = decode(x[:, t : t + 1], step)
            outs.append(o)
        g16, g5 = grad(x, 16), grad(x, 5)
    assert _rel(out, jnp.concatenate(outs, axis=1)) < 1e-5
    assert _rel(cache["h"], step["h"]) < 1e-5
    np.testing.assert_array_equal(np.asarray(cache["conv"]), np.asarray(step["conv"]))
    assert _rel(g16, g5) < 1e-5
