"""The one paged block behind the three serving steps (``models/llama.py``:
``_paged_layers`` over ``models/paged_kv.py::attention``), at ``LlamaConfig.tiny()`` on
the CPU in float32 with seeded weights, for the dense block and for a MoE
block with QK-norm. The three entry points differ in the rank of their
arguments, in how ``valid`` is found and in which rows get logits: each
relation below pins one of them to another, or to ``forward``.

Tolerance. Both sides of every comparison are float32 from the same weights
and differ at most in the order of a summation (a window of one against a
batch row, a padded table against a full causal softmax); logits are O(1),
and 2e-4 of the largest is some hundred float32 roundings through two
layers (the limit of ``tests/test_olmoe.py``, for the same reason)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama as L
from ray_tpu.ops import paged_attention as paged_attn

REL_TOL = 2e-4
BLOCK, NUM_BLOCKS, WIDTH = 4, 24, 8  # tokens a block, blocks in the pool, blocks a table row

CONFIGS = {
    "dense": {},
    "moe_qk_norm": dict(mlp_hidden=32, qk_norm=True, moe_experts=4, moe_top_k=2,
                        moe_renormalize=False),
}


def _rel(have, want):
    want = np.asarray(want)
    return float(np.abs(np.asarray(have) - want).max() / np.abs(want).max())


def _tokens(seed, n, cfg):
    return np.random.RandomState(seed).randint(1, cfg.vocab_size, size=n).astype(np.int32)


def _row(*blocks):
    row = np.zeros(WIDTH, np.int32)
    row[: len(blocks)] = blocks
    return row


def _loads(out):
    """The expert loads of a step's output; None for a dense config."""
    return np.asarray(out[2]) if len(out) == 3 else None


@pytest.fixture(scope="module", params=list(CONFIGS))
def model(request):
    """``(cfg, params, steps, cache)``: the three steps jitted (nothing is
    donated, so one cache can be read by two steps), and a cache in which
    request A (blocks 1-4) has 13 tokens prefilled in chunks of 8 and
    request B (blocks 5, 6) has 6."""
    cfg = L.LlamaConfig.tiny(**CONFIGS[request.param])
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    steps = {
        name: jax.jit(partial(getattr(L, f"paged_{name}_step"), cfg))
        for name in ("prefill", "verify", "decode")
    }
    cache = L.init_paged_kv_cache(cfg, NUM_BLOCKS, BLOCK)
    a, b = _tokens(1, 13, cfg), _tokens(2, 6, cfg)
    pad = lambda t: np.pad(t, (0, 8 - len(t)))  # noqa: E731
    for toks, row, ctx in ((a[:8], _row(1, 2, 3, 4), 0), (a[8:], _row(1, 2, 3, 4), 8),
                           (b, _row(5, 6), 0)):
        cache = steps["prefill"](params, cache, pad(toks), row, np.int32(ctx),
                                 np.int32(len(toks)))[0]
    return cfg, params, steps, cache


def test_decode_is_verify_with_windows_of_one(model):
    """Decode is the body at ``C = 1``: the same logits, cache and expert
    loads as a verify step whose windows hold one token. Slot 2 is padding
    the way each runner call pads (decode: a null table; verify:
    ``true_len`` 0): it reaches no expert in either."""
    cfg, params, steps, cache = model
    tokens = np.array([7, 9, 0], np.int32)
    positions = np.array([13, 6, 0], np.int32)
    tables = np.stack([_row(1, 2, 3, 4), _row(5, 6), _row()])
    dec = steps["decode"](params, cache, tokens, positions, tables,
                          np.array([14, 7, 1], np.int32))
    ver = steps["verify"](params, cache, tokens[:, None], tables, positions,
                          np.array([1, 1, 0], np.int32))
    assert dec[1].shape == (3, cfg.vocab_size) and ver[1].shape == (3, 1, cfg.vocab_size)
    assert _rel(dec[1][:2], ver[1][:2, 0]) < REL_TOL
    for kv in ("k", "v"):  # block 0 holds the padding slots' trash
        np.testing.assert_allclose(dec[0][kv][:, 1:], ver[0][kv][:, 1:], rtol=0, atol=1e-5)
        assert np.abs(np.asarray(dec[0][kv][:, 1:]) - np.asarray(cache[kv][:, 1:])).max() > 0
    assert (_loads(dec) is None) == (cfg.moe_experts == 0)
    if cfg.moe_experts:
        assert (_loads(dec) == _loads(ver)).all()
        assert _loads(dec).sum(axis=1).tolist() == [2 * cfg.moe_top_k] * cfg.n_layers


def test_prefill_is_the_last_valid_row_of_a_verify_batch_of_one(model):
    """Prefill is the body at ``B = 1`` with ONE row through the head: a
    second chunk of 8 with 5 valid tokens over 8 cached ones gives the
    logits of row ``true_len - 1`` of the verify step on the same window."""
    cfg, params, steps, cache = model
    chunk = np.pad(_tokens(3, 5, cfg), (0, 3))
    row = _row(7, 8, 9, 10)
    cache = steps["prefill"](params, cache, _tokens(4, 8, cfg), row, np.int32(0), np.int32(8))[0]
    pre = steps["prefill"](params, cache, chunk, row, np.int32(8), np.int32(5))
    ver = steps["verify"](params, cache, chunk[None], row[None], np.array([8], np.int32),
                          np.array([5], np.int32))
    assert pre[1].shape == (cfg.vocab_size,) and ver[1].shape == (1, 8, cfg.vocab_size)
    assert _rel(pre[1], ver[1][0, 4]) < REL_TOL
    for kv in ("k", "v"):
        np.testing.assert_allclose(pre[0][kv][:, 1:], ver[0][kv][:, 1:], rtol=0, atol=1e-5)
    if cfg.moe_experts:
        assert (_loads(pre) == _loads(ver)).all()
        assert _loads(pre).sum(axis=1).tolist() == [5 * cfg.moe_top_k] * cfg.n_layers


def test_chunked_prefill_then_three_decodes_is_forward_on_the_whole_sequence(model):
    """Request A of the fixture's cache (13 tokens in chunks of 8 and 5),
    then three decode steps fed the sequence's own next tokens: the logits
    at positions 12 to 15 are ``forward``'s on all 16 tokens at once."""
    cfg, params, steps, cache = model
    seq = np.concatenate([_tokens(1, 13, cfg), _tokens(5, 3, cfg)])
    want = np.asarray(L.forward(cfg, params, seq[None]))[0]
    row = _row(1, 2, 3, 4)
    # the fixture's last chunk of A again (ctx 8, 5 valid): its K/V are rewritten in place
    out = steps["prefill"](params, cache, np.pad(seq[8:13], (0, 3)), row, np.int32(8), np.int32(5))
    assert _rel(out[1], want[12]) < REL_TOL
    for p in (13, 14, 15):
        out = steps["decode"](params, out[0], seq[p : p + 1], np.array([p], np.int32), row[None],
                              np.array([p + 1], np.int32))
        assert _rel(out[1][0], want[p]) < REL_TOL


STEP_ARGS = {  # every entry point on one request of block table (1, 2), nothing cached
    "prefill": lambda z: (z(8), _row(1, 2), np.int32(0), np.int32(8)),
    "verify": lambda z: (z((2, 4)), np.stack([_row(1, 2), _row()]), z(2), np.array([4, 0], np.int32)),
    "decode": lambda z: (z(2), z(2), np.stack([_row(1, 2), _row()]), np.ones(2, np.int32)),
}


@pytest.mark.parametrize("step", list(STEP_ARGS))
def test_every_entry_point_attends_through_the_one_seam(monkeypatch, step):
    """``paged_kv.attention`` is the only door to the cache for attention: each
    entry point calls it once a layer, and what it returns is what the step
    computes with (a kernel put in its place is all three steps' kernel)."""
    cfg = L.LlamaConfig.tiny()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    cache = L.init_paged_kv_cache(cfg, NUM_BLOCKS, BLOCK)
    args = STEP_ARGS[step](lambda shape: np.ones(shape, np.int32))
    fn = getattr(L, f"paged_{step}_step")
    real, calls = L.paged_kv.attention, []

    def counting(q, k_cache, v_cache, layer, block_tables, pos, *valid, **of_the_group):
        calls.append((layer, q.shape, block_tables.shape, pos.shape))
        return real(q, k_cache, v_cache, layer, block_tables, pos, *valid, **of_the_group)

    monkeypatch.setattr(L.paged_kv, "attention", counting)
    logits = fn(cfg, params, cache, *args)[1]
    assert [c[0] for c in calls] == list(range(cfg.n_layers))
    for _, q, tables, pos in calls:  # one form for all three: [B, C, H, hd], [B, M], [B, C]
        assert q == (*pos, cfg.n_heads, cfg.head_dim) and tables == (pos[0], WIDTH)

    monkeypatch.setattr(L.paged_kv, "attention", lambda q, *rest, **said: jnp.zeros_like(q))
    assert _rel(fn(cfg, params, cache, *args)[1], logits) > 1e-2


# -- the two ways through the one door (ISSUE 30) ---------------------------------------

def _tile_model():
    """Tiny, but with whole tiles: ``head_dim`` 128 and 8 KV heads, so that on
    a TPU the predicate looks at the WINDOW."""
    cfg = L.LlamaConfig.tiny(dim=1024, n_heads=8, n_kv_heads=8, max_seq_len=8 * WIDTH)
    shapes = jax.eval_shape(partial(L.init_params, cfg), jax.ShapeDtypeStruct((2,), jnp.uint32))
    return cfg, shapes, jax.eval_shape(partial(L.init_paged_kv_cache, cfg, NUM_BLOCKS, 8))


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


@pytest.mark.parametrize("chunk", [256, 1024])
def test_a_prefill_chunk_lowers_with_no_pallas_call_even_on_a_tpu(monkeypatch, chunk):
    """A chunk-sized window keeps the gather whatever the backend: selected on
    ``C`` at trace time, so prefill's program is what it was before there was
    a kernel (its StableHLO at the benchmark's widths is the parent's, PERF.md
    PR 30)."""
    cfg, params, cache = _tile_model()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # steer the predicate; the test's business
    assert paged_attn.kernel_serves(1, cfg.n_heads, cache["k"])
    assert not paged_attn.kernel_serves(chunk, cfg.n_heads, cache["k"])
    monkeypatch.setattr(
        paged_attn, "paged_attention",
        lambda *a, **kw: pytest.fail("a prefill chunk reached the paged-attention kernel"),
    )
    text = jax.jit(partial(L.paged_prefill_step, cfg)).lower(
        params, cache, _i32(chunk), _i32(WIDTH), _i32(), _i32()
    ).as_text()
    assert "custom_call" not in text and "gather" in text


@pytest.mark.parametrize("step", ["verify", "decode"])
def test_the_kernel_path_is_still_the_one_door(monkeypatch, step):
    """Where the predicate says kernel, ``paged_kv.attention`` is still called
    once a layer and is the only caller of the kernel: once a layer, with the
    WHOLE cache (no layer of it sliced off outside) and the layer's index."""
    cfg, params, cache = _tile_model()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    doors, kernels = [], []
    real = L.paged_kv.attention

    def door(q, k_cache, v_cache, layer, block_tables, pos, *valid, **of_the_group):
        doors.append(layer)
        return real(q, k_cache, v_cache, layer, block_tables, pos, *valid, **of_the_group)

    def kernel(q, k_cache, v_cache, layer, block_tables, pos, n_kv, keeps):
        assert (n_kv, keeps) == (cfg.n_kv_heads, 0)
        kernels.append((layer, len(doors), k_cache.shape, v_cache.shape, q.shape, pos.shape))
        return jnp.zeros_like(q)

    monkeypatch.setattr(L.paged_kv, "attention", door)
    monkeypatch.setattr(paged_attn, "paged_attention", kernel)
    args = {
        "verify": (_i32(2, 4), _i32(2, WIDTH), _i32(2), _i32(2)),
        "decode": (_i32(2), _i32(2), _i32(2, WIDTH), _i32(2)),
    }[step]
    jax.eval_shape(partial(getattr(L, f"paged_{step}_step"), cfg), params, cache, *args)
    assert doors == list(range(cfg.n_layers))
    window = 4 if step == "verify" else 1
    assert kernels == [
        (layer, layer + 1, cache["k"].shape, cache["v"].shape,
         (2, window, cfg.n_heads, cfg.head_dim), (2, window))
        for layer in range(cfg.n_layers)
    ]
