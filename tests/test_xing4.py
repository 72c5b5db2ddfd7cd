"""``models/xing4.py`` (latent attention, the hyper-connected residual,
sigmoid routing with a shared expert over a held range of experts) against
the plain reference of its family, ``perfbench/families/xing4/reference.py``,
on the CPU at a small size: float32 against float32, seeded weights. And the
latent cache layout through what handles blocks (export and import between
two engines; the prefix cache, COW, preemption and the tier run on both
layouts in ``test_inference.py`` / ``test_kv_tier.py``)."""

import dataclasses
import math
import os
import re
import sys
import threading

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(HERE, "perfbench"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import rehearsal  # noqa: E402
import xing4_controls as controls  # noqa: E402
from perfbench import families  # noqa: E402
from perfbench.families.xing4 import reference  # noqa: E402
from ray_tpu.models import latent, xing4  # noqa: E402
from ray_tpu.models.interface import model_of  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402
from ray_tpu.ops import mhc as mhc_ops  # noqa: E402
from ray_tpu.ops import moe as moe_ops  # noqa: E402

CONFIG = "xing4.0-29b-a4b-ep8"
TOL = 2e-4
BS = 8


@pytest.fixture(scope="module")
def model():
    return rehearsal.tiny_config(CONFIG)


@pytest.fixture(scope="module")
def cfg(model):
    return families.of(model).model_config(model, max_seq_len=model["max_position_embeddings"])


@pytest.fixture(scope="module")
def params(cfg):
    return xing4.init_params(cfg, jax.random.PRNGKey(5))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(11).integers(1, 256, size=(2, 60)).astype(np.int32)


def _rel(have, want):
    return float(np.max(np.abs(np.asarray(have) - np.asarray(want))) / np.max(np.abs(np.asarray(want))))


# -- the whole model through the latent paged cache -------------------------------------------

def _prefill(cfg, params, cache, row_tokens, table, chunks, bucket=40):
    step = jax.jit(lambda p, c, *a: xing4.paged_prefill_step(cfg, p, c, *a), donate_argnums=(1,))
    start = 0
    for c in chunks:
        chunk = np.zeros(bucket, np.int32)
        chunk[:c] = row_tokens[start : start + c]
        cache, logits, _ = step(params, cache, chunk, table, np.int32(start), np.int32(c))
        start += c
    return cache, np.asarray(logits)


@pytest.mark.parametrize("chunks", [(37,), (13, 24), (16, 16, 5), (7, 9, 11, 10), (32, 5)],
                         ids=lambda c: "the_table_s_width-" + "+".join(map(str, c)))
def test_chunked_prefill_decode_and_verify_match_the_reference(model, cfg, params, tokens, chunks):
    """Chunks whose boundaries split a block of 8, then a decode step, then
    a verify window of 3, all through the latent cache, against the
    reference's full forward pass: logits, not tokens. The absorbed path
    gathers the slot's context as wide as the table."""
    n = sum(chunks)
    table = np.zeros(8, np.int32)
    table[:8] = np.arange(1, 9)
    cache = xing4.cache_layout(cfg, BS).init(16)
    cache, got_prefill = _prefill(cfg, params, cache, tokens[0], table, chunks)
    tables = np.zeros((4, 8), np.int32)
    tables[1] = table  # slot 0 and 2, 3 are padding
    decode = jax.jit(lambda p, c, *a: xing4.paged_decode_step(cfg, p, c, *a), donate_argnums=(1,))
    toks, pos = np.zeros(4, np.int32), np.zeros(4, np.int32)
    toks[1], pos[1] = tokens[0, n], n
    cache, got_decode, counters = decode(params, cache, toks, pos, tables, np.ones(4, np.int32))
    assert int(counters["load"].sum()) == cfg.moe_top_k * cfg.n_moe_layers  # one real row
    verify = jax.jit(lambda p, c, *a: xing4.paged_verify_step(cfg, p, c, *a), donate_argnums=(1,))
    window = np.zeros((4, 4), np.int32)
    window[1, :3] = tokens[0, n + 1 : n + 4]
    ctx, true = np.zeros(4, np.int32), np.zeros(4, np.int32)
    ctx[1], true[1] = n + 1, 3
    cache, got_verify, _ = verify(params, cache, window, tables, ctx, true)
    picks = [(0, n - 1), (0, n)] + [(0, n + 1 + i) for i in range(3)]
    want = reference.logits_at(model, params, tokens, picks)
    have = [got_prefill, np.asarray(got_decode)[1]] + [np.asarray(got_verify)[1, i] for i in range(3)]
    for h, w in zip(have, want):
        assert _rel(h, w) < TOL


def test_forward_matches_the_reference_and_the_counts(model, cfg, params, tokens):
    full = np.asarray(jax.jit(lambda p, t: xing4.forward(cfg, p, t))(params, jnp.asarray(tokens)))
    picks = [(0, 59), (1, 3), (1, 40)]
    for (i, t), want in zip(picks, reference.logits_at(model, params, tokens, picks)):
        assert _rel(full[i, t], want) < TOL
    fam = families.of(model)
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert fam.param_count(model) == xing4.param_count(cfg) == n
    layout = xing4.cache_layout(cfg, BS)
    assert fam.kv_bytes_per_token(model, 4) == layout.bytes_per_token
    assert layout.describe() == {"kind": "latent", "row_width": 24, "bytes_per_token": 4 * 24 * 4}
    assert layout.payload_shape(3) == (1, 4, 3, BS * 24)  # a block of rows stored as one row


@pytest.mark.parametrize("window", [1, 4])
def test_absorbed_attention_equals_expanded_on_the_same_rows(cfg, params, window):
    """The two latent paths are the same mathematics: ``W_kvb`` absorbed
    into the query and the output against K and V expanded from the rows."""
    rng = np.random.default_rng(window)
    p = {k: v[0] for k, v in params["moe"].items()}
    B, S = 3, 40
    q_nope = jnp.asarray(rng.standard_normal((B, window, cfg.n_heads, cfg.qk_nope_head_dim)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((B, window, cfg.n_heads, cfg.qk_rope_head_dim)), jnp.float32)
    rows = jnp.asarray(rng.standard_normal((B, S, cfg.latent_width)), jnp.float32)
    pos = jnp.asarray(rng.integers(window, S, size=(B, 1)) - np.arange(window)[::-1][None], jnp.int32)
    e = latent.attend_expanded(cfg, p, q_nope, q_rope, rows, jnp.arange(S) <= pos[:, :, None])
    # absorbed, as the paged step calls it: the context before the window, the window's own rows beside it
    own = jnp.take_along_axis(rows, pos[:, :, None], axis=1)
    before = jnp.broadcast_to((jnp.arange(S) < pos[:, :1])[:, None, :], (B, window, S))
    q_row = latent.absorb_query(cfg, p, q_nope, q_rope)
    a = latent.absorb_output(cfg, p, latent.attend_rows(cfg, q_row, rows, before, own))
    assert a.shape == (B, window, cfg.n_heads, cfg.v_head_dim)
    assert _rel(a, e) < 1e-5
    assert xing4.absorbs(cfg, window)


@pytest.mark.parametrize("block", [8, 16])
def test_the_expanded_path_attends_a_block_of_queries_at_a_time(cfg, params, monkeypatch, block):
    """A prefill chunk's queries attend ``_QUERY_BLOCK`` at a time (the
    float32 scores of a whole chunk over the full table would be the
    program's largest temporary): the same numbers as all at once."""
    rng = np.random.default_rng(block)
    p = {k: v[0] for k, v in params["moe"].items()}
    B, C, S = 2, 32, 48
    q_nope = jnp.asarray(rng.standard_normal((B, C, cfg.n_heads, cfg.qk_nope_head_dim)), jnp.float32)
    q_rope = jnp.asarray(rng.standard_normal((B, C, cfg.n_heads, cfg.qk_rope_head_dim)), jnp.float32)
    rows = jnp.asarray(rng.standard_normal((B, S, cfg.latent_width)), jnp.float32)
    mask = jnp.arange(S) <= (10 + jnp.arange(C))[None, :, None] + jnp.zeros((B, 1, 1), jnp.int32)
    whole = latent.attend_expanded(cfg, p, q_nope, q_rope, rows, mask)
    monkeypatch.setattr(latent, "_QUERY_BLOCK", block)  # where the lifted function reads it
    blocks = latent.attend_expanded(cfg, p, q_nope, q_rope, rows, mask)
    assert _rel(blocks, whole) < 1e-6


def test_the_window_decides_the_latent_path_at_the_published_widths():
    cfg = xing4.Xing4Config()
    assert [xing4.absorbs(cfg, c) for c in (1, 4, 170, 171, 256, 1024)] == [True, True, True, False, False, False]
    model = rehearsal._load("configs", f"{CONFIG}.json")
    fam = families.of(model)
    assert fam.absorb_break_even_window(model) == pytest.approx(170.67, abs=0.01)
    assert fam.attention_flops_per_pair(model, True) == 69632 and fam.attention_flops_per_pair(model, False) == 20480
    assert fam.expansion_flops_per_position(model) == 8388608
    path = xing4.MODEL.attention_path
    assert path(cfg, 1, None) == ("latent.absorbed", "slots") and path(cfg, 1024, None) == ("latent.expanded", "table")
    # on a TPU, over the cache as the layout stores it at these widths, decode
    # and a verify window of up to 8 read their own live blocks through the
    # kernel over latent rows; a cache stored a block a row keeps the gather
    bf16 = xing4.Xing4Config(dtype=jnp.bfloat16)
    cache = jax.eval_shape(lambda: xing4.cache_layout(bf16, 16).init(8))
    assert cache["latent"].shape == (40, 8, 8, 1152)
    assert [path(bf16, c, cache, backend="tpu") for c in (1, 8)] == [("latent.paged", "blocks")] * 2
    assert path(bf16, 16, cache, backend="tpu") == ("latent.absorbed", "slots")  # 512 query rows: the gather
    assert path(bf16, 1, cache, backend="cpu") == ("latent.absorbed", "slots")
    rows = {"latent": jax.ShapeDtypeStruct((40, 8, 9216), jnp.bfloat16)}
    assert path(bf16, 1, rows, backend="tpu") == ("latent.absorbed", "slots")


# -- YaRN ------------------------------------------------------------------------------------

@pytest.mark.parametrize("published", [True, False], ids=["published", "toy"])
def test_yarn_table_and_softmax_scale_against_the_formula(model, cfg, published):
    c = xing4.Xing4Config() if published else cfg
    dr, theta, factor, orig = c.qk_rope_head_dim, c.rope_theta, c.rope_factor, c.rope_original_max
    want = []
    for i in range(dr // 2):
        base = theta ** (-2 * i / dr)
        dim = lambda rot: dr * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))  # noqa: E731
        low, high = max(math.floor(dim(c.rope_beta_fast)), 0), min(math.ceil(dim(c.rope_beta_slow)), dr - 1)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        want.append(base / factor * ramp + base * (1 - ramp))
    got = np.asarray(xing4.yarn_inv_freq(c))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[0] == pytest.approx(1.0) and got[-1] == pytest.approx(theta ** (-(dr - 2) / dr) / factor, rel=1e-6)
    m = 0.1 * math.log(factor) + 1
    assert xing4.softmax_scale(c) == pytest.approx((c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5 * m * m)
    if published:
        assert xing4.softmax_scale(c) == pytest.approx(192 ** -0.5 * (0.1 * math.log(64) + 1) ** 2)
    else:
        z = reference.sizes(model)
        np.testing.assert_allclose(reference.yarn_inv_freq(z), got, rtol=1e-6)
        assert reference.softmax_scale(z) == pytest.approx(xing4.softmax_scale(c))


# -- mHC -------------------------------------------------------------------------------------

@pytest.mark.parametrize("group, sub", [("dense", "hc_attn"), ("dense", "hc_mlp"), ("moe", "hc_attn"), ("moe", "hc_mlp")])
def test_sinkhorn_is_doubly_stochastic_and_the_seeded_maps_matter(cfg, params, group, sub):
    p = {k: v[1] for k, v in params[group].items()}
    X = jnp.asarray(np.random.default_rng(3).standard_normal((2, 50, cfg.hc_mult, cfg.dim)), jnp.float32)
    pre, post, res = xing4.mhc_maps(cfg, p[f"{sub}_phi"], p[f"{sub}_b"], p[f"{sub}_alpha"], X)
    res = np.asarray(res)
    assert np.abs(res.sum(-1) - 1).max() < 1e-5 and np.abs(res.sum(-2) - 1).max() < 1e-5
    # tens of per cent away from the identity and from 1/n; pre and post away from constants
    n = cfg.hc_mult
    assert np.abs(res - np.eye(n)).mean() > 0.1 and np.abs(res - 1 / n).mean() > 0.1
    assert np.asarray(pre).std() > 0.02 and np.asarray(post).std() > 0.04  # they move with the token


def test_the_residual_is_x_plus_f_of_x_when_the_maps_are_forced(cfg):
    """``H_res = I``, ``H_pre = H_post = e_1``: stream 0 becomes ``x + F(norm(x))``, the others stay."""
    n, D = cfg.hc_mult, cfg.dim
    maps = 2 * n + n * n
    e1 = np.where(np.arange(n) == 0, 1.0, 0.0)
    b = np.concatenate([
        np.where(e1 > 0, 40.0, -40.0),              # sigmoid -> e_1
        np.where(e1 > 0, 0.0, -40.0),               # 2 sigmoid -> e_1
        np.where(np.eye(n) > 0, 30.0, -30.0).reshape(-1),  # exp, Sinkhorn -> I
    ])
    norm = np.random.default_rng(0).uniform(0.5, 1.5, D).astype(np.float32)
    p = {"hc_attn_phi": jnp.zeros((n * D, maps)), "hc_attn_b": jnp.asarray(b, jnp.float32),
         "hc_attn_alpha": jnp.ones(3), "attn_norm": jnp.asarray(norm)}
    X = jnp.asarray(np.random.default_rng(1).standard_normal((1, 6, n, D)), jnp.float32)
    F = lambda h: (jnp.tanh(h) * 3.0, None)  # noqa: E731
    out, _ = xing4._hyper(cfg, p, "hc_attn", "attn_norm", X, F)
    x = np.asarray(X)
    want0 = x[..., 0, :] + 3.0 * np.tanh(x[..., 0, :] / np.sqrt((x[..., 0, :] ** 2).mean(-1, keepdims=True) + cfg.norm_eps) * norm)
    np.testing.assert_allclose(np.asarray(out)[..., 0, :], want0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out)[..., 1:, :], x[..., 1:, :], atol=1e-5)


def _seeded_sublayer(n, D, seed):
    """One sublayer's maps as ``init_params`` draws them, and a norm vector."""
    rng = np.random.default_rng(seed)
    maps = 2 * n + n * n
    phi = rng.standard_normal((n * D, maps)) / np.sqrt(n * D)
    phi[:, 2 * n:] *= 0.25
    b = rng.standard_normal(maps)
    b[2 * n:] = 0.2 * b[2 * n:] + np.eye(n).reshape(-1)
    p = {"hc_attn_phi": phi, "hc_attn_b": b, "hc_attn_alpha": rng.uniform(0.5, 1.5, 3),
         "attn_norm": rng.uniform(0.5, 1.5, D)}
    return {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}


def _numpy_hyper(c, p, X, iters):
    """The sublayer around ``F`` = the identity in plain float64 NumPy, written
    from the module's docstring: ``(H_pre, H_post, H_res, X_out)``."""
    n = c.hc_mult
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    X = np.asarray(X, np.float64)
    x = X.reshape(*X.shape[:-2], -1)
    xbar = x / np.sqrt((x * x).mean(-1, keepdims=True) + c.norm_eps)
    z = xbar @ p["hc_attn_phi"]
    a, b = p["hc_attn_alpha"], p["hc_attn_b"]
    sigmoid = lambda v: 1.0 / (1.0 + np.exp(-v))  # noqa: E731
    pre = sigmoid(a[0] * z[..., :n] + b[:n])
    post = 2.0 * sigmoid(a[1] * z[..., n:2 * n] + b[n:2 * n])
    res = np.exp(np.clip(a[2] * z[..., 2 * n:] + b[2 * n:], -c.hc_res_clamp, c.hc_res_clamp))
    res = res.reshape(*z.shape[:-1], n, n)
    for _ in range(iters):
        res = res / (res.sum(-1, keepdims=True) + c.hc_eps)
        res = res / (res.sum(-2, keepdims=True) + c.hc_eps)
    h = (pre[..., None] * X).sum(-2)
    y = h / np.sqrt((h * h).mean(-1, keepdims=True) + c.norm_eps) * p["attn_norm"]
    out = np.einsum("...ij,...jd->...id", res, X) + post[..., None] * y[..., None, :]
    return pre, post, res, out


@pytest.fixture(params=["jnp", "kernel"])
def sinkhorn_body(request, monkeypatch):
    """Both bodies of ``ops/mhc.py::sinkhorn``: ``lax.fori_loop`` over ``[T]``
    arrays (what the CPU and ``forward`` run) and the Pallas kernel as a TPU
    takes it, here in Pallas' TPU interpreter."""
    if request.param == "kernel":
        monkeypatch.setattr(mhc_ops, "kernel_serves", lambda logits, backend=None: True)
    return request.param


MHC_TOL = 2e-5
#: bytes a compiled sublayer of a 1024-token chunk may access by XLA's count
#: (618 MB before PR 50, 329 after it: of those 88 are ONE prefetch of the 29 MB
#: state into VMEM, counted as its operand and its result tuple, and the last
#: mix's convolution counts its operands twice; PERF.md, PR 50)
MHC_CHUNK_BYTES = 350e6


@pytest.mark.parametrize("lead", [(1, 50), (32, 1), (7, 1)], ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("n", [2, 4])
def test_the_lane_dense_residual_equals_a_float64_numpy_one(cfg, sinkhorn_body, n, lead):
    """The maps with the tokens on the minor axis, the rounds as explicit adds
    under one loop, the product scaled after the fact: against NumPy's float64
    Sinkhorn and mix written here. One round instead of twenty is told apart."""
    c = dataclasses.replace(cfg, hc_mult=n)
    p = _seeded_sublayer(n, c.dim, 7 + n)
    rng = np.random.default_rng([n, *lead])
    X = rng.standard_normal((*lead, 1, c.dim)) + 0.5 * rng.standard_normal((*lead, n, c.dim))
    X = jnp.asarray(X, jnp.float32)
    maps = xing4.mhc_maps(c, p["hc_attn_phi"], p["hc_attn_b"], p["hc_attn_alpha"], X)
    out, extra = xing4._hyper(c, p, "hc_attn", "attn_norm", X, lambda h: (h, "extra"))
    assert extra == "extra" and out.shape == X.shape and out.dtype == X.dtype
    assert [m.shape for m in maps] == [(*lead, n), (*lead, n), (*lead, n, n)]
    *want_maps, want = _numpy_hyper(c, p, X, c.hc_sinkhorn_iters)
    for have, ref in zip(maps, want_maps):
        assert have.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(have), ref, atol=MHC_TOL, rtol=0)
    assert np.abs(np.asarray(maps[2]).sum(-1) - 1).max() < 1e-5
    assert np.abs(np.asarray(maps[2]).sum(-2) - 1).max() < 1e-5
    assert _rel(out, want) < MHC_TOL
    assert _rel(out, _numpy_hyper(c, p, X, 1)[-1]) > 50 * MHC_TOL  # one round is another residual


def test_phi_goes_to_the_product_as_three_bfloat16_pieces_that_sum_to_it():
    """What the chip's product of an unconverted bfloat16 state rests on
    (``_state_dot``): under ``jit`` the three pieces are bfloat16 and their
    float32 sum is the float32 array to the bit, over a wide range of exponents."""
    rng = np.random.default_rng(8)
    w = rng.standard_normal((4, 64, 24)) * np.exp(rng.uniform(-20.0, 20.0, (4, 64, 24)))
    w = jnp.asarray(w, jnp.float32)
    pieces = jax.jit(xing4._bf16_pieces)(w)
    assert pieces.dtype == jnp.bfloat16 and pieces.shape == (4, 64, 72)
    hi, mid, lo = (np.asarray(pieces[..., k * 24:(k + 1) * 24], np.float32) for k in range(3))
    np.testing.assert_array_equal(hi + (mid + lo), np.asarray(w))
    assert np.abs(mid).max() > 0 and np.abs(lo).max() > 0


def test_the_residual_has_a_gradient_that_equals_a_finite_difference(cfg, sinkhorn_body):
    """What ``forward`` needs of ``_hyper``: ``jax.grad`` of a scalar of it, with
    respect to the state and to the maps' weights, finite, and equal to a central
    difference along a random direction. Through the kernel the gradient is the
    ``jnp`` body's (``jax.custom_vjp``)."""
    n, D = cfg.hc_mult, cfg.dim
    p = _seeded_sublayer(n, D, 3)
    rng = np.random.default_rng(4)
    X = jnp.asarray(rng.standard_normal((2, 5, 1, D)) + 0.5 * rng.standard_normal((2, 5, n, D)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((2, 5, n, D)) / np.sqrt(D), jnp.float32)

    def scalar(p, X):
        out, _ = xing4._hyper(cfg, p, "hc_attn", "attn_norm", X, lambda h: (jnp.tanh(h), None))
        return jnp.sum(out * w)

    grads = jax.grad(scalar, argnums=(0, 1))(p, X)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree_util.tree_leaves(grads))
    direction = jax.tree_util.tree_map(  # a hundredth of each leaf's size a step
        lambda a: jnp.asarray(rng.standard_normal(a.shape), jnp.float32) * jnp.sqrt(jnp.mean(a * a)), (p, X)
    )
    along = sum(float(jnp.vdot(g, d)) for g, d in zip(*map(jax.tree_util.tree_leaves, (grads, direction))))
    step = 1e-2
    moved = lambda s: jax.tree_util.tree_map(lambda a, d: a + s * d, (p, X), direction)  # noqa: E731
    central = float(scalar(*moved(step)) - scalar(*moved(-step))) / (2 * step)
    assert abs(along) > 0.1 and abs(along - central) < 1e-3 * abs(along)


# -- the residual's sublayer, compiled for a described v5e (no chip: nothing runs) ------------

@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip, as ``tests/test_olmoe.py::one_chip``:
    made inside a fixture, never at import."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _device_operations(text: str) -> int:
    """Fusions + copies + kernels of a compiled module's entry computation, a
    loop counted as its body x its trips."""
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
            bodies[name] = []
        elif name is not None and " = " in line:
            bodies[name].append(line)

    def count(body):
        total = 0
        for line in bodies[body]:
            op = re.search(r" (fusion|copy|custom-call|while)\(", line.split(" = ", 1)[1])
            if op is None:
                continue
            if op.group(1) == "while":
                trips = re.search(r'"known_trip_count":\{"n":"(\d+)"', line)
                total += count(re.search(r"body=%?([\w.\-]+)", line).group(1)) * int(trips.group(1))
            else:
                total += 1
        return total

    return count("ENTRY")


@pytest.mark.parametrize("lead, most_bytes", [((1, 1024), MHC_CHUNK_BYTES), ((32, 1), 40e6)], ids=["chunk_1024", "decode_32"])
def test_a_sublayer_of_the_residual_compiles_to_a_few_operations_for_the_chip(one_chip, monkeypatch, lead, most_bytes):
    """ONE sublayer around ``F`` = the identity at the published widths (the
    state bf16 ``[.., 4, 3584]``: 29.4 MB a chunk), compiled for the real chip:
    the gauge that says the lane-dense maps, the one kernel of the rounds and
    the unconverted product engaged, and the one a JAX upgrade would trip. The
    parent of PR 50 read 135 operations (91 fusions + 44 copies: each of the 40
    normalisations a reduce over a trailing axis of 4 that ends a fusion and
    flips a layout) and 618 MB accessed, with two float32 images of the state
    (the ``convert`` and its re-tiled ``copy``); PR 50 reads 21 operations (16
    fusions + 4 copies + the kernel), 329 MB and no such image. The compile has
    a time limit of its own, so that a form that unrolls into minutes fails
    here and not in a deployment's warm-up."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # steer the branches; the test's business
    c = xing4.Xing4Config(dtype=jnp.bfloat16)
    n, D = c.hc_mult, c.dim
    maps = 2 * n + n * n
    shape = lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
    p = {"hc_attn_phi": shape((n * D, maps)), "hc_attn_b": shape((maps,)), "hc_attn_alpha": shape((3,)),
         "attn_norm": shape((D,), jnp.bfloat16)}
    sublayer = jax.jit(lambda p, X: xing4._hyper(c, p, "hc_attn", "attn_norm", X, lambda h: (h, None))[0])
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # such a compile cannot be read back without a chip
    done = {}
    try:
        worker = threading.Thread(
            target=lambda: done.update(compiled=sublayer.lower(p, shape((*lead, n, D), jnp.bfloat16)).compile()),
            daemon=True,
        )
        worker.start()
        worker.join(60)
        assert "compiled" in done, "the sublayer did not compile for the chip within 60 s"
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
    compiled = done["compiled"]
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1 and "mhc_sinkhorn" in text
    assert _device_operations(text) <= 40
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    assert cost["bytes accessed"] <= most_bytes
    # whole float32 images of the state among the entry computation's values
    entry = text[text.index("ENTRY"):]
    images = [
        m for m in re.finditer(r" = f32\[([\d,]+)\]\S* (?:fusion|copy|convert)\(", entry)
        if math.prod(map(int, m.group(1).split(","))) >= math.prod(lead) * n * D
    ]
    assert len(images) <= 1
    assert compiled.out_info.shape == (*lead, n, D) and compiled.out_info.dtype == jnp.bfloat16


# -- the router, and the controls -------------------------------------------------------------

def _program_gates(cfg, p, h):
    g, e, _ = moe_ops.route(p["router"], h, top_k=cfg.moe_top_k, renormalize=True, scoring="sigmoid",
                            bias=p["router_bias"], scale=cfg.routed_scaling_factor)
    dense = jnp.sum(jnp.where(e[..., None] == jnp.arange(cfg.n_routed_experts), g[..., None], 0.0), axis=-2)
    return np.asarray(dense)


@pytest.fixture(scope="module")
def routed(cfg, params):
    p = {k: v[0] for k, v in params["moe"].items()}
    # a bias large enough to change the kept set of most rows at this size
    p["router_bias"] = 0.3 * jnp.asarray(np.random.default_rng(2).standard_normal(cfg.n_routed_experts), jnp.float32)
    h = jnp.asarray(np.random.default_rng(4).standard_normal((200, cfg.dim)), jnp.float32)
    return p, h


def test_the_router_chooses_with_the_bias_and_gates_without_it(model, cfg, routed):
    p, h = routed
    z = reference.sizes(model)
    want, margin = reference.gates(z, p["router"], p["router_bias"], h)
    sure = np.asarray(margin) > 1e-4
    have = _program_gates(cfg, p, h)
    assert np.abs(have - np.asarray(want))[sure].max() < 1e-5
    # by hand: the kept are the top-k of s + b, a gate is 2 s_e / sum of the kept s
    s = 1 / (1 + np.exp(-(np.asarray(h, np.float64) @ np.asarray(p["router"], np.float64))))
    kept = np.argsort(-(s + np.asarray(p["router_bias"], np.float64)), axis=-1)[:, : cfg.moe_top_k]
    for t in np.flatnonzero(sure)[:50]:
        by_hand = np.zeros(cfg.n_routed_experts)
        by_hand[kept[t]] = 2 * s[t, kept[t]] / s[t, kept[t]].sum()
        np.testing.assert_allclose(have[t], by_hand, atol=1e-5)
    assert np.allclose(have.sum(-1), cfg.routed_scaling_factor, atol=1e-5)
    out, aux = moe_ops.dropless_moe_ffn(
        {k: p[k] for k in ("router", "router_bias", "w_gate", "w_up", "w_down")}, h,
        top_k=cfg.moe_top_k, renormalize=True, scoring="sigmoid", scale=2.0, held=cfg.held_experts)
    # by hand: rows whose kept set is not the top-k of s alone (rows without a margin aside)
    unbiased = np.argsort(-s, axis=-1)[:, : cfg.moe_top_k]
    changed = np.array([set(a) != set(b) for a, b in zip(kept, unbiased)])
    assert 0 < changed.sum() and abs(int(aux["bias_changed"]) - changed.sum()) <= (~sure).sum()
    assert int(aux["load"].sum()) == 200 * cfg.moe_top_k


FFN_CONTROLS = ["bias_out_of_the_choice", "bias_in_the_gate", "gates_not_normalised",
                "shared_expert_0_times", "shared_expert_2_times", "weights_fp8"]


@pytest.mark.parametrize("variant", [None] + FFN_CONTROLS, ids=lambda v: v or "the_reference")
def test_the_expert_ffn_matches_the_reference_and_no_control(model, cfg, routed, variant):
    """The program's FFN of an expert layer (shared expert, router, sort,
    grouped matmuls over the held experts, combine, the valid mask) against
    the reference's on the same activations: within the tolerance of the
    reference, 50 tolerances away from every control."""
    p, h = routed
    valid = jnp.arange(200) < 180
    have = np.asarray(xing4._ffn(cfg, p, h[None], valid[None], True)[0][0])
    want, margin = controls.expert_ffn(model, p, h[:180], variant)
    sure = np.asarray(margin) > 1e-4
    err = np.max(np.abs(have[:180] - np.asarray(want)), axis=-1) / np.maximum(np.max(np.abs(np.asarray(want)), axis=-1), 1e-30)
    assert np.all(np.isfinite(have))
    if variant is None:
        assert err[sure].max() < TOL
    else:
        assert err[sure].max() > 50 * TOL


MODEL_CONTROLS = ["sinkhorn_1_round", "post_without_its_2", "scale_without_m2", "key_rope_unrotated",
                  "bias_out_of_the_choice", "shared_expert_2_times"]


@pytest.mark.parametrize("variant", MODEL_CONTROLS)
def test_a_control_of_the_whole_model_is_told_from_it(model, cfg, params, tokens, variant):
    # the seeded bias changes the choice for some tokens only: ten times it, so that leaving it out shows
    params = {**params, "moe": {**params["moe"], "router_bias": 10 * params["moe"]["router_bias"]}}
    full = np.asarray(jax.jit(lambda p, t: xing4.forward(cfg, p, t))(params, jnp.asarray(tokens[:1])))
    picks = [(0, 59), (0, 30)]
    wrong = controls.logits_at(model, params, tokens[:1], picks, variant)
    assert min(_rel(full[i, t], w) for (i, t), w in zip(picks, wrong)) > 50 * TOL


# -- the shares of an expert-parallel deployment sum to the whole layer -------------------------

@pytest.mark.parametrize("side", ["program", "reference"])
def test_eight_shares_of_eight_experts_sum_to_the_uncut_layer(side):
    """Guide section 4's test: 64 experts over 8 ranks, each told its range
    of 8; the routed parts of all shares, with the shared expert counted
    ONCE, add up to the uncut reference's whole layer."""
    E, k, D, Fm, T = 64, 4, 64, 32, 96
    rng = np.random.default_rng(7)
    f = lambda *s: jnp.asarray(rng.standard_normal(s) / math.sqrt(s[-2] if len(s) > 1 else 1), jnp.float32)  # noqa: E731
    whole = {"router": f(D, E), "router_bias": 0.3 * f(E), "w_gate": f(E, D, Fm), "w_up": f(E, D, Fm),
             "w_down": f(E, Fm, D), "shared_gate": f(D, Fm), "shared_up": f(D, Fm), "shared_down": f(Fm, D)}
    h = f(T, D) * math.sqrt(T)
    toy = {"hc_mult": 2, "num_attention_heads": 1, "qk_nope_head_dim": 1, "qk_rope_head_dim": 2, "v_head_dim": 1,
           "kv_lora_rank": 1, "rms_norm_eps": 1e-6, "rope_theta": 1e4, "max_position_embeddings": 8,
           "num_experts_per_tok": k, "routed_scaling_factor": 2, "norm_topk_prob": True, "hc_sinkhorn_iters": 1,
           "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30, "first_k_dense_replace": 0}
    uncut, _ = reference.expert_ffn(reference.sizes({**toy, "deployment": {"held_experts": [0, E]}}), whole, h)
    shared = reference.mlp(whole["shared_gate"], whole["shared_up"], whole["shared_down"], h)
    total = np.asarray(shared)
    for lo in range(0, E, 8):
        share = {**whole, **{n: whole[n][lo : lo + 8] for n in ("w_gate", "w_up", "w_down")}}
        if side == "program":
            routed, aux = moe_ops.dropless_moe_ffn(
                {n: share[n] for n in ("router", "router_bias", "w_gate", "w_up", "w_down")}, h,
                top_k=k, renormalize=True, scoring="sigmoid", scale=2.0, held=(lo, lo + 8))
            assert int(aux["load"].sum()) == T * k  # the load is over all 64, whatever is held
        else:
            z = reference.sizes({**toy, "deployment": {"held_experts": [lo, lo + 8]}})
            routed = reference.expert_ffn(z, share, h)[0] - shared
        total = total + np.asarray(routed)
    assert _rel(total, uncut) < 1e-5

@pytest.mark.parametrize("real", [1, 2, 4], ids=lambda n: f"{n}_of_4_slots_real")
def test_the_absorbed_path_reads_nothing_for_a_padding_slot(cfg, params, tokens, real):
    """A decode batch padded to its bucket of 4: the real slots' logits are
    those of a bucket that holds them alone, and the runner counts the table's
    width for the real slots only (``AttentionPath.reads == "slots"``)."""
    from ray_tpu.inference.model_runner import PagedModelRunner

    def runner(bucket):
        r = PagedModelRunner(cfg, params, num_blocks=40, block_size=BS, prefill_buckets=(40,),
                             decode_buckets=(bucket,))
        full = r.max_blocks_per_seq
        rows = [list(range(1 + 8 * slot, 9 + 8 * slot)) + [0] * (full - 8) for slot in range(real)]
        for slot in range(real):
            r.prefill_chunk(tokens[slot % 2, : 30 + slot].tolist(), rows[slot], 0)
        ctx = [31 + slot for slot in range(real)]
        return r, r.decode([7] * real, [c - 1 for c in ctx], rows, ctx)

    padded, have = runner(4)
    alone, want = runner(real)
    np.testing.assert_allclose(have, want, rtol=0, atol=1e-5)
    width = padded.max_blocks_per_seq * BS
    assert padded.decode_width["gathered_tokens"] == real * width == alone.decode_width["gathered_tokens"]
    assert padded.decode_width["live_tokens"] == sum(31 + slot for slot in range(real))


# -- the kernel over latent rows, forced through Pallas' interpreter -------------------------------

#: widths at which the layout stores a block in whole tiles (two rows of 64
#: fill a lane row of 128; blocks of 16 are 8 such rows), so that the kernel
#: can read the cache as it is stored; everything else the toy's
TILED = dict(kv_lora_rank=48, qk_rope_head_dim=16)


@pytest.fixture
def paged_forced(monkeypatch):
    """The predicate as it reads on a TPU: every window that absorbs takes
    the kernel (which then runs in Pallas' TPU interpreter)."""
    monkeypatch.setattr(latent, "paged_serves", lambda cfg, window, cache, backend=None: latent.absorbs(cfg, window))


@pytest.fixture(scope="module")
def tiled(cfg):
    c = dataclasses.replace(cfg, **TILED)
    return c, xing4.init_params(c, jax.random.PRNGKey(6))


def _decode_and_verify(cfg, params, tokens, lens=(37, 16, 21)):
    """Three sequences prefilled into a shuffled pool, a padding slot between
    them, then a decode step and a verify window of 3: the logits of both and
    the cache after each."""
    bs = 16
    cache = xing4.cache_layout(cfg, bs).init(20)
    assert cache["latent"].shape[2:] == (8, 128)
    tables = np.zeros((4, 4), np.int32)
    ids = np.random.default_rng(3).permutation(np.arange(1, 13)).reshape(3, 4)
    for i, row in enumerate((0, 2, 3)):  # slot 1 is padding
        tables[row] = ids[i]
        cache, _ = _prefill(cfg, params, cache, tokens[i % 2], tables[row], (lens[i],))
    real = np.array([0, 2, 3])
    toks, pos = np.zeros(4, np.int32), np.zeros(4, np.int32)
    toks[real], pos[real] = [tokens[i % 2, n] for i, n in enumerate(lens)], lens
    decode = jax.jit(lambda p, c, *a: xing4.paged_decode_step(cfg, p, c, *a))
    cache_d, logits_d, _ = decode(params, cache, toks, pos, tables, np.ones(4, np.int32))
    window, ctx, true = np.zeros((4, 4), np.int32), np.zeros(4, np.int32), np.zeros(4, np.int32)
    for i, (row, n) in enumerate(zip(real, lens)):
        window[row, :3], ctx[row], true[row] = tokens[i % 2, n + 1 : n + 4], n + 1, 3
    verify = jax.jit(lambda p, c, *a: xing4.paged_verify_step(cfg, p, c, *a))
    cache_v, logits_v, _ = verify(params, cache_d, window, tables, ctx, true)
    live = np.asarray(logits_v)[real][:, :3]
    return np.asarray(logits_d)[real], live, np.asarray(cache_d["latent"])[:, 1:], np.asarray(cache_v["latent"])[:, 1:]


def test_decode_and_verify_through_the_kernel_equal_the_gather(tiled, tokens, request):
    """``paged_decode_step`` and ``paged_verify_step`` with the absorbed path
    through ``ops/latent_paged.py`` (contexts that end mid-block, at a block's
    edge, one slot padding): the logits and every block of the cache but the
    null block equal the gather's."""
    cfg, params = tiled
    want = _decode_and_verify(cfg, params, tokens)
    assert xing4.MODEL.attention_path(cfg, 1, None) == ("latent.absorbed", "slots")
    request.getfixturevalue("paged_forced")
    assert xing4.MODEL.attention_path(cfg, 1, None) == ("latent.paged", "blocks")
    have = _decode_and_verify(cfg, params, tokens)
    for h, w in zip(have, want):
        assert np.isfinite(h).all() and _rel(h, w) < TOL


def test_the_runner_compiles_one_decode_program_and_counts_live_blocks(tiled, tokens, paged_forced):
    """Where the absorbed path reads blocks the table's width costs nothing:
    ONE decode program, handed the full table, and
    ``decode_width["gathered_tokens"]`` is each real slot's live blocks (a
    padding slot reads none)."""
    from ray_tpu.inference.model_runner import PagedModelRunner

    cfg, params = tiled
    cfg = dataclasses.replace(cfg, max_seq_len=4096)
    runner = PagedModelRunner(cfg, params, num_blocks=264, block_size=16, prefill_buckets=(48,), decode_buckets=(4,))
    assert runner.attention_paths[1] == ("latent.paged", "blocks")
    rows = [list(range(1 + 4 * i, 5 + 4 * i)) + [0] * 252 for i in range(2)]
    for i, n in enumerate((37, 16)):
        runner.prefill_chunk(tokens[i, :n].tolist(), rows[i], 0)
    logits = runner.decode([7, 8], [37, 16], rows, [38, 17])
    assert np.isfinite(np.asarray(logits)).all()
    dw = runner.decode_width
    assert (dw["launches"], dw["width_tokens"], dw["live_tokens"]) == (1, 4096, 38 + 17)
    assert dw["gathered_tokens"] == (3 + 2) * 16
    assert runner.compile_count() == 1 + 1  # the chunk's program and ONE decode program


# -- export and import between two engines, on both layouts -------------------------------------

@pytest.mark.parametrize("layout", ["kv", "latent"])
def test_kv_export_then_import_on_a_second_engine(layout):
    """Engine A prefills and exports its blocks; engine B imports them and
    serves the same prompt from a prefix hit, token for token as a cold
    engine does. The payload has the cache layout's shape."""
    from ray_tpu.inference.engine import EngineConfig, InferenceEngine

    cfg = LlamaConfig.tiny() if layout == "kv" else xing4.Xing4Config.tiny()
    params = model_of(cfg).init_params(cfg, jax.random.PRNGKey(0))
    ec = EngineConfig(num_blocks=32, block_size=8, prefill_buckets=(8, 32), decode_buckets=(1, 4),
                      max_decode_batch=4, max_new_tokens_default=6, warmup=False, kv_transfer_enabled=True)
    prompt = [int(t) for t in np.random.default_rng(9).integers(1, 200, size=27)]
    a, b, cold = (InferenceEngine(cfg, params, ec).start() for _ in range(3))
    try:
        payload = a.prefill_kv(prompt)
        kv = payload["kv"]
        assert kv.shape == a.runner.cache_layout.payload_shape(3) and payload["block_size"] == 8
        assert a.stats()["kv_layout"]["kind"] == layout
        assert b.import_kv_blocks(payload["tokens"], kv) == 24
        warm = list(b.generate(prompt, max_new_tokens=6))
        assert warm == list(cold.generate(prompt, max_new_tokens=6))
        assert b.blocks.prefix_stats()["tokens_saved_total"] >= 16  # 3 blocks imported; the last is copied on write
    finally:
        for e in (a, b, cold):
            e.stop()
