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

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "tools"))

import lowered_text  # noqa: E402
from ray_tpu.models import glm_dsa, latent, xing4  # noqa: E402
from ray_tpu.ops import latent_flash  # noqa: E402

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


# -- a selecting chunk's kernel: the expanded form under a mask, K and V expanded in VMEM (ISSUE 60; built in PR 59) --

#: (kr, dn, dr, dv): GLM-5's own widths, and a pair in their ratio whose key and value are whole lane tiles
SELECTING_WIDTHS = {"glm5_192_64_256": (512, 192, 64, 256), "scaled_96_32_128": (256, 96, 32, 128)}


def _selecting_case(widths, C, S, ctx_len, true_len, dtype, seed=0, share=0.3):
    """A chunk of ``C`` queries at ``ctx_len`` over a table of ``S`` positions
    under a seeded selection (causal, a query's own position always chosen),
    the rows past the live length clean and poisoned."""
    kr, dn, dr, dv = SELECTING_WIDTHS[widths]
    cfg = glm_dsa.GlmDsaConfig.tiny(
        kv_lora_rank=kr, qk_nope_head_dim=dn, qk_rope_head_dim=dr, v_head_dim=dv, n_heads=2, dtype=dtype
    )
    rng = np.random.default_rng(seed)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)  # noqa: E731
    p = {"w_kvb": normal(kr, cfg.n_heads, dn + dv) * kr**-0.5}
    q_nope, q_rope, rows = normal(C, cfg.n_heads, dn), normal(C, cfg.n_heads, dr), normal(S, kr + dr)
    pos = ctx_len + np.arange(C)
    mask = (np.arange(S)[None, :] <= pos[:, None]) & (rng.random((C, S)) < share)
    mask[np.arange(C), pos] = True
    return cfg, p, q_nope, q_rope, rows, rows.at[ctx_len + true_len:].set(jnp.nan), mask


def _masked_softmax(cfg, p, q_nope, q_rope, rows, mask):
    """What the kernel is held to: ``attend_masked`` between ``absorb_query``
    and ``absorb_output`` (the chunk's path wherever the kernel does not
    serve), ``[C, H, dv]`` float32."""
    q_row = latent.absorb_query(cfg, p, q_nope[None], q_rope[None])[0]
    o_lat = latent.attend_masked(cfg, q_row, rows, jnp.asarray(mask))
    return np.asarray(latent.absorb_output(cfg, p, o_lat[None])[0], np.float32)


def _close(have, want, dtype):
    tol = 2e-5 if dtype == jnp.float32 else 4e-2  # bf16: K, V and the probabilities rounded, against the absorbed form's rows
    np.testing.assert_allclose(have, want, atol=tol * np.abs(want).max(), rtol=0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("widths", SELECTING_WIDTHS)
@pytest.mark.parametrize(
    "ctx_len, true_len",
    [(0, 32), (16, 32), (21, 32), (37, 9), (40, 1), (64, 32), (80, 32)],
    ids=["no_context", "ends_on_a_tile", "ends_inside_a_tile", "a_padded_tail", "one_real_query",
         "the_table_full", "a_padded_chunk_spills_past_the_table"],
)
def test_the_selecting_kernel_is_the_masked_softmax(monkeypatch, ctx_len, true_len, widths, dtype):
    """The kernel over POISONED rows (every row past the live length NaN: K
    and V expanded from them would be NaN) against the materialised softmax of
    the absorbed form over the clean ones, six key tiles of 16: the real
    queries agree, and every output is finite. A padded chunk that spills past
    the table has fewer real queries than fit."""
    monkeypatch.setattr(latent_flash, "_KEY_TILE", TILE)
    C = 32
    true_len = min(true_len, KEYS - ctx_len)
    cfg, p, q_nope, q_rope, rows, poisoned, mask = _selecting_case(widths, C, KEYS + C, ctx_len, true_len, dtype)
    have = latent.attend_selected(
        cfg, p, q_nope, q_rope, poisoned[:KEYS], jnp.asarray(mask[:, :KEYS]), jnp.int32(ctx_len), jnp.int32(true_len)
    )
    assert have.shape == (C, cfg.n_heads, cfg.v_head_dim) and have.dtype == dtype
    have = np.asarray(have, np.float32)
    assert np.isfinite(have).all()
    _close(have[:true_len], _masked_softmax(cfg, p, q_nope, q_rope, rows, mask)[:true_len], dtype)


@pytest.mark.parametrize("widths", SELECTING_WIDTHS)
@pytest.mark.parametrize("ctx_len, true_len", [(0, 32), (21, 32), (37, 9)], ids=["no_context", "inside_a_tile", "a_padded_tail"])
def test_a_mask_of_every_seen_position_is_the_unmasked_call(monkeypatch, ctx_len, true_len, widths):
    """A selection wider than the context chooses every position a query
    sees: the selecting kernel then gives what the chunk's flash kernel gives
    every other latent model (``attend_flash``: K and V expanded by XLA, the
    causal mask built in the kernel), float32."""
    monkeypatch.setattr(latent_flash, "_KEY_TILE", TILE)
    monkeypatch.setattr(latent_flash, "_QUERY_TILE", 32)
    C = 32
    cfg, p, q_nope, q_rope, rows, _, _ = _selecting_case(widths, C, KEYS, ctx_len, true_len, jnp.float32)
    seen = np.arange(KEYS)[None, :] <= (ctx_len + np.arange(C))[:, None]
    have = latent.attend_selected(cfg, p, q_nope, q_rope, rows, jnp.asarray(seen), jnp.int32(ctx_len), jnp.int32(true_len))
    want = latent.attend_flash(cfg, p, q_nope, q_rope, rows, jnp.int32(ctx_len), jnp.int32(true_len))
    np.testing.assert_allclose(np.asarray(have)[:true_len], np.asarray(want)[:true_len], atol=2e-5)


@pytest.mark.parametrize("widths", SELECTING_WIDTHS)
@pytest.mark.parametrize("first_tile", [1, 2, 4], ids=lambda t: f"first_chosen_key_in_tile_{t}")
def test_a_first_chosen_key_in_a_later_tile_wipes_what_the_tiles_before_it_summed(monkeypatch, first_tile, widths):
    """A query that chooses nothing in the key tiles before ``first_tile``
    (and one whose ONLY key is its own position, in the last live tile): its
    running maximum is still the masked value when those tiles end, so their
    every key counts with weight 1 in ``l`` and ``acc``; the first real score
    must wipe them with ``alpha`` = 0, exactly."""
    monkeypatch.setattr(latent_flash, "_KEY_TILE", TILE)
    C, ctx_len = 32, 60
    cfg, p, q_nope, q_rope, rows, poisoned, mask = _selecting_case(widths, C, KEYS, ctx_len, C, jnp.float32, seed=first_tile)
    mask[5, : first_tile * TILE] = False
    mask[9, :] = False
    mask[9, ctx_len + 9] = True
    have = latent.attend_selected(cfg, p, q_nope, q_rope, poisoned, jnp.asarray(mask), jnp.int32(ctx_len), jnp.int32(C))
    want = _masked_softmax(cfg, p, q_nope, q_rope, rows, mask)
    _close(np.asarray(have, np.float32), want, jnp.float32)
    # the one key's value row, whatever the score
    w_v = np.asarray(p["w_kvb"], np.float32)[..., cfg.qk_nope_head_dim:]
    own = np.einsum("r,rhk->hk", np.asarray(rows, np.float32)[ctx_len + 9, : cfg.kv_lora_rank], w_v)
    np.testing.assert_allclose(np.asarray(have, np.float32)[9], own, atol=2e-5 * np.abs(own).max())


@pytest.mark.parametrize(
    "what, serves",
    [
        (dict(), True),
        (dict(keys=4096), True),
        (dict(keys=512), True),  # a table shorter than a key tile is one tile
        (dict(window=512), True),
        (dict(backend="cpu"), False),
        (dict(dtype=jnp.float32), False),  # compiled and timed in bf16 alone
        (dict(window=2048), False),  # the chunk is ONE query tile
        (dict(window=1000), False),  # the mask's tile: whole int8 registers
        (dict(keys=32768 + 512), False),  # not whole key tiles
        (dict(dn=128), False),  # a key of 128 + 64: one and a half lane tiles
        (dict(dv=192), False),
        (dict(kr=448), False),
    ],
    ids=lambda v: "-".join(f"{k}={getattr(x, '__name__', x)}" for k, x in v.items()) or "glm5" if isinstance(v, dict) else None,
)
def test_which_shapes_and_backends_the_selecting_kernel_serves(what, serves):
    at = dict(window=1024, keys=32768, kr=512, dn=192, dr=64, dv=256, dtype=jnp.bfloat16, backend="tpu")
    at.update(what)
    assert latent_flash.selected_serves(**at) is serves


def test_a_selecting_models_attention_path_answers_both_ways(monkeypatch):
    """GLM-5's chunk of 1024 on a TPU takes the kernel and says so
    (``latent.sparse_flash``, read up to its live key tiles); on the CPU, and
    at the toy widths anywhere, the materialised softmax under the mask; a
    decode or verify window reads its slots' live blocks through the paged
    kernels on a TPU (PR 62) and gathers by token off it. A model that does
    not select is never asked."""
    cfg = glm_dsa.GlmDsaConfig(dtype=jnp.bfloat16, max_seq_len=32768)
    cache = {"latent": jax.ShapeDtypeStruct((7, 8, 8, 2 * 576), jnp.bfloat16),
             "index": jax.ShapeDtypeStruct((7, 8, 16, 128), jnp.bfloat16)}
    path = glm_dsa.MODEL.attention_path
    assert path(cfg, 1024, cache, backend="tpu") == ("latent.sparse_flash", "live")
    assert path(cfg, 1024, cache, backend="cpu") == ("latent.sparse_masked", "table")
    assert path(cfg, 1024, None) == ("latent.sparse_masked", "table")  # the CPU never looks at the cache
    assert path(cfg, 2, cache, backend="tpu") == ("latent.sparse_paged", "blocks")
    assert path(cfg, 2, cache, backend="cpu") == path(cfg, 2, None) == ("latent.sparse", "table")
    assert glm_dsa.MODEL.key_tile(cfg, 1024, cache) == 1024
    toy = glm_dsa.GlmDsaConfig.tiny(kv_lora_rank=32, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16)
    toy_cache = {"latent": jax.ShapeDtypeStruct((4, 8, 8 * 36), jnp.float32),
                 "index": jax.ShapeDtypeStruct((4, 8, 8 * 16), jnp.float32)}
    assert not latent.absorbs(toy, 40)
    assert path(toy, 40, toy_cache, backend="tpu") == ("latent.sparse_masked", "table")
    assert path(toy, 2, toy_cache, backend="tpu") == ("latent.sparse", "table")  # a block one row: no kernel
    assert not latent.selected_serves(xing4.Xing4Config(dtype=jnp.bfloat16), 1024, _latent_cache(xing4.Xing4Config.tiny()), backend="tpu")
    # what the runner counts as expanded: the key tiles up to the chunk's end where the kernel expands them, the rungs elsewhere
    assert glm_dsa.MODEL.gather_rungs(cfg, 1024, cache)[:3] == (4096, 8192, 12288)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert glm_dsa.MODEL.gather_rungs(cfg, 1024, cache)[:5] == (1024, 2048, 3072, 4096, 5120)


@pytest.mark.parametrize(
    "window, digest",
    [
        (0, "2ddd256ef3c7acf4fdfc9254f306fb7e3eafb1926b38ccd871dd5dd2e4b53fd8"),
        (384, "9eb901dc1c4a254ba13fa8e7e40384609e9e484a95e0dfe9b2dfc1cd3b500aee"),
    ],
    ids=["plain_with_a_shared_key_part", "grouped_heads_and_a_window"],
)
def test_a_call_without_a_mask_lowers_to_the_text_it_lowered_to_before_the_selecting_kernel(window, digest):
    """The eleven cells whose chunk goes through ``flash_attention`` must not
    drift with the selecting chunk's kernel: ``_call``'s text lowered for a TPU
    (as ``tests/tools/lowered_text.py`` lowers and hashes a program: no source
    locations) at one plain shape with a shared key part and one with grouped
    heads behind a window is the text of commit c1315f4, the parent of PR 60
    (hashed there by the same lines). A change MEANT for that kernel renews
    the digests and says so."""
    H, C, S, dk, ds, dv = 4, 256, 1024, 128, 64, 128
    shape = lambda *s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt)  # noqa: E731
    kv = H // 2 if window else H
    shared = (None, None) if window else (shape(H, C, ds), shape(S, ds))
    with lowered_text.described("tpu"):
        text = latent_flash._call.trace(
            shape(H, C, dk), shape(kv, S, dk), shape(kv, S, dv), *shared, shape(dt=jnp.int32), shape(dt=jnp.int32),
            scale=0.125, block_q=256, block_k=256, interpret=False, group=H // kv, window=window,
        ).lower(lowering_platforms=("tpu",)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == digest
