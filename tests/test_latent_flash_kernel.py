"""The flash kernel of Xing4's prefill chunk (``ops/latent_flash.py`` behind
``models/xing4.py::_attend_flash``) against the materialised softmax of
``_attend_expanded`` on the same inputs, on the CPU in Pallas' interpreter at
tiny widths and tiny tiles (the generic one, as ``ops/attention.py``'s tests:
under the TPU interpreter's simulated DMA threads one case in five hung here,
a callback's own JAX call waiting on the CPU client the test's call held).

Every case runs the KERNEL over poisoned latent rows and the materialised
softmax over the clean ones: every row past the live context (``ctx_len +
true_len`` on) is NaN, so K and V expanded from it are NaN too. One read past
the mask and the output is not finite. The table is ``KEYS`` positions wide
with a chunk of null positions behind it, as ``_latent_attention`` lays a
chunk out: a padded chunk that ends at the table's end spills past it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import latent, xing4
from ray_tpu.ops import latent_flash

KEYS, TILE = 96, 16

#: where the chunk's first query stands, by what it is to the key tiles
CONTEXTS = {
    "none": lambda C: 0,
    "one": lambda C: 1,
    "a_tile_less_one": lambda C: TILE - 1,
    "a_tile": lambda C: TILE,
    "a_tile_and_one": lambda C: TILE + 1,
    "the_table_full": lambda C: KEYS - C,  # a whole chunk ends on the table's last position
    "spills_past_the_table": lambda C: KEYS - C // 2,  # only a padded chunk fits
}
TRUE_LENS = {"one": lambda C: 1, "mid_chunk": lambda C: C // 2 - 3, "whole_chunk": lambda C: C}


def _case(cfg, C, ctx_len, true_len, dtype, seed=0):
    rng = np.random.default_rng(seed)
    H, W = cfg.n_heads, cfg.latent_width
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)  # noqa: E731
    p = {"w_kvb": normal(cfg.kv_lora_rank, H, cfg.qk_nope_head_dim + cfg.v_head_dim) * 0.3}
    q_nope, q_rope = normal(C, H, cfg.qk_nope_head_dim), normal(C, H, cfg.qk_rope_head_dim)
    rows = normal(KEYS + C, W)
    poisoned = rows.at[ctx_len + true_len:].set(jnp.nan)
    return p, q_nope, q_rope, rows, poisoned


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("C", [32, 64], ids=["chunk32", "chunk64"])
@pytest.mark.parametrize("true_len", TRUE_LENS, ids=lambda k: f"len_{k}")
@pytest.mark.parametrize("ctx", CONTEXTS, ids=lambda k: f"ctx_{k}")
def test_the_kernel_is_the_materialised_softmax(monkeypatch, ctx, true_len, C, dtype):
    """``dk`` 16 + 8 shared against ``dv`` 32; two query tiles a chunk of 32,
    four of 64; six key tiles. Real rows agree under a float32 tolerance
    (bf16: the rounding of the probabilities), rows past ``true_len`` are
    finite, and no NaN planted past the live context reaches either."""
    monkeypatch.setattr(latent_flash, "_QUERY_TILE", TILE)
    monkeypatch.setattr(latent_flash, "_KEY_TILE", TILE)
    cfg = xing4.Xing4Config.tiny(v_head_dim=32, dtype=dtype)
    ctx_len, n = CONTEXTS[ctx](C), TRUE_LENS[true_len](C)
    if ctx_len + n > KEYS:
        n = KEYS - ctx_len  # the last chunk of a request that fills the table: padded
    p, q_nope, q_rope, rows, poisoned = _case(cfg, C, ctx_len, n, dtype)
    have = latent.attend_flash(cfg, p, q_nope, q_rope, poisoned[:KEYS], jnp.int32(ctx_len), jnp.int32(n))
    mask = jnp.arange(KEYS + C) <= (ctx_len + jnp.arange(C))[None, :, None]
    want = latent.attend_expanded(cfg, p, q_nope[None], q_rope[None], rows[None], mask)[0]
    assert have.shape == want.shape == (C, cfg.n_heads, 32) and have.dtype == want.dtype == dtype
    have, want = np.asarray(have, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(have).all()
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(have[:n], want[:n], atol=tol * np.abs(want[:n]).max(), rtol=0)
    # a query tile with no real query ran nothing
    first_padding_tile = -(-n // TILE) * TILE
    assert (have[first_padding_tile:] == 0).all()


@pytest.mark.parametrize(
    "H, group, d, ctx_len, true_len",
    [(2, 1, 16, 20, 30), (8, 4, 64, 20, 30), (8, 4, 64, 0, 1), (8, 4, 64, 32, 32), (4, 1, 64, 17, 9)],
    ids=["heads_of_16", "heads_of_64_in_pairs", "pairs_one_real_row", "pairs_the_table_full", "pairs_ungrouped"],
)
def test_the_kernel_without_a_shared_key_part(H, group, d, ctx_len, true_len):
    """Keys that are a head's own alone (``k_shared`` None): the same kernel,
    one product a score. Heads of 64 go through in PAIRS (two key heads side
    by side in one row of 128 lanes, a query head in its own half of a row of
    zeros): the numbers of the grouped softmax a head, and nothing planted
    past the live context reaches them."""
    rng = np.random.default_rng(1)
    C, S = 32, 64
    q, k, v = (
        jnp.asarray(rng.standard_normal(s), jnp.float32)
        for s in ((H, C, d), (H // group, S, d), (H // group, S, d))
    )
    live = ctx_len + true_len
    have = latent_flash.flash_attention(
        q, k.at[:, live:].set(jnp.nan), v.at[:, live:].set(jnp.nan), ctx_len, true_len,
        scale=0.25, block_q=16, block_k=16, group=group,
    )
    s = jnp.einsum("hck,hsk->hcs", q, jnp.repeat(k, group, axis=0)) * 0.25
    see = jnp.arange(S)[None, :] <= jnp.minimum(ctx_len + jnp.arange(C), live - 1)[:, None]
    want = jnp.einsum(
        "hcs,hsk->hck", jax.nn.softmax(jnp.where(see, s, -1e30), axis=-1), jnp.repeat(v, group, axis=0)
    )
    assert have.shape == want.shape == (H, C, d)
    np.testing.assert_allclose(np.asarray(have)[:, :true_len], np.asarray(want)[:, :true_len], atol=2e-5)
    assert np.isfinite(np.asarray(have)).all()


def _latent_cache(cfg, dtype=jnp.bfloat16, block_size=16, num_blocks=8):
    return {"latent": jnp.zeros((cfg.n_layers, num_blocks, block_size * cfg.latent_width), dtype)}


@pytest.mark.parametrize(
    "what, serves",
    [
        (dict(), True),
        (dict(window=256), True),
        (dict(backend="cpu"), False),
        (dict(backend="gpu"), False),
        (dict(dtype=jnp.float16), False),
        (dict(window=200), False),  # not whole (16, 128) registers
        (dict(window=1536), False),  # not whole query tiles
        (dict(keys=8192 + 512), False),  # not whole key tiles
        (dict(keys=512), True),  # a table shorter than a key tile is one tile
        (dict(dk=96), False),
        (dict(dv=64), False),
        (dict(dk=64, dv=64, ds=0), False),  # narrow heads: in pairs alone
        (dict(dk=64, dv=64, ds=0, kv_heads=8), True),  # LFM2's chunk
        (dict(dk=64, dv=64, ds=0, kv_heads=7), False),
        (dict(dk=64, dv=64, ds=64, kv_heads=8), False),
        (dict(dk=64, dv=64, ds=0, kv_heads=8, backend="cpu"), False),
        (dict(ds=32), False),
        (dict(ds=0), True),
        (dict(dtype=jnp.float32), True),
    ],
    ids=lambda v: "-".join(f"{k}={getattr(x, '__name__', x)}" for k, x in v.items()) if isinstance(v, dict) else None,
)
def test_which_shapes_and_backends_the_kernel_serves(what, serves):
    at = dict(window=1024, keys=8192, dk=128, dv=128, ds=64, dtype=jnp.bfloat16, backend="tpu")
    at.update(what)
    assert latent_flash.kernel_serves(**at) is serves


def test_attention_path_answers_both_ways():
    """On a TPU at the published widths a prefill chunk takes the kernel and
    reads its live key tiles; on the CPU, or where the widths are not whole
    tiles, the materialised softmax over the table. Decode absorbs either
    way. ``key_tile`` is what the runner rounds a ``live`` read up to."""
    cfg = xing4.Xing4Config(dtype=jnp.bfloat16)
    path, cache = xing4.MODEL.attention_path, _latent_cache(xing4.Xing4Config(dtype=jnp.bfloat16, n_layers=1, n_dense_layers=1))
    for window in (256, 1024):
        assert path(cfg, window, cache, backend="tpu") == ("latent.flash", "live")
        assert path(cfg, window, cache, backend="cpu") == ("latent.expanded", "table")
        assert path(cfg, window, None) == ("latent.expanded", "table")  # the CPU never looks at the cache
        assert xing4.MODEL.key_tile(cfg, window, cache) == 1024
    # decode absorbs: on a TPU over the cache as the layout stores it, through
    # the kernel over latent rows (each slot's own live blocks); elsewhere,
    # and over a cache stored a block a row, a slot at a time by the gather
    assert path(cfg, 1, cache, backend="cpu") == ("latent.absorbed", "slots")
    stored = jax.eval_shape(lambda: xing4.cache_layout(cfg, 16).init(8))
    assert path(cfg, 1, stored, backend="tpu") == ("latent.paged", "blocks")
    assert path(cfg, 1, cache, backend="tpu") == ("latent.absorbed", "slots")  # ``cache``: a block a row
    assert path(cfg, 1024, stored, backend="tpu") == ("latent.flash", "live")
    toy = xing4.Xing4Config.tiny(kv_lora_rank=32)  # a chunk of 32 expands, at widths that are no whole lanes
    assert path(toy, 32, _latent_cache(toy, jnp.float32, 4), backend="tpu") == ("latent.expanded", "table")
    odd = xing4.Xing4Config(dtype=jnp.bfloat16, max_seq_len=8192 + 16)
    assert path(odd, 1024, cache, backend="tpu") == ("latent.expanded", "table")
