"""A latent prefill chunk gathers, overlays and expands K and V only over the
key tiles up to its own last real query (ISSUE 53): ``models/latent.py::
latent_attention``'s expanded branch takes one of ``key_rungs``' widths (every
whole number of key tiles up to the table), chosen on the device from the
traced context and length, inside ONE program.

Held against the form it replaces, kept HERE as the tests' reference
(:func:`table_wide`: the table gathered whole, the window's rows laid over it,
K and V expanded from all of it), on the CPU at toy widths, through the flash
kernel in Pallas' interpreter and through the materialised softmax both: a
table of eight key tiles of 16 (the benchmark's: eight of 1024), a chunk of two
tiles, a block of 8.

The program's ``out`` is read off a cache whose rows past the chunk's rung are
NaN: the table-wide form would expand them (the materialised softmax then
multiplies ``0 x NaN`` into every output), the rungs never gather them. The
wrong twins say what the comparison can tell: a rung chosen from the context
alone is a tile short wherever the chunk's real rows cross a tile's edge, and a
table without the null columns behind it lays a chunk that spills past its end
a block early."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import deepseek_v3, kimi_linear, latent, llama, xing4
from ray_tpu.models.interface import model_of
from ray_tpu.ops import latent_flash

BS, TILE, C, M = 8, 16, 32, 16
KEYS = M * BS  # 128: eight key tiles
K = KEYS // TILE
LAYERS, LAYER, BLOCKS = 2, 1, 24
#: the toy table's rungs, and the published table's: every whole number of the kernel's key tiles
RUNGS = tuple(range(TILE, KEYS + 1, TILE))
RUNGS_8192 = tuple(range(1024, 8192 + 1, 1024))

CONFIGS = {
    # a chunk of 32 EXPANDS at these ranks (``latent.absorbs``), as 256 and 1024 do at the published ones
    "xing4": lambda: xing4.Xing4Config.tiny(kv_lora_rank=32, max_seq_len=KEYS),
    "deepseek_v3": lambda: deepseek_v3.DeepseekV3Config.tiny(kv_lora_rank=64, max_seq_len=KEYS),  # dv = 1.5 dn
    "kimi_linear": lambda: kimi_linear.KimiLinearConfig.tiny(kv_lora_rank=32, max_seq_len=KEYS),  # no rotary part
}


@pytest.fixture(autouse=True)
def toy_tiles(monkeypatch):
    monkeypatch.setattr(latent_flash, "_QUERY_TILE", TILE)
    monkeypatch.setattr(latent_flash, "_KEY_TILE", TILE)


def table_wide(cfg, p, q_nope, q_rope, row, cache, layer, block_tables, pos, true_lens, flash, pad=None):
    """The expanded branch as it was before the rungs: every row of the table
    gathered, the window's rows laid over them, K and V expanded from ALL of
    them. ``pad``: null columns behind the table (the program's ``nblk``;
    0 is the wrong twin whose overlay clamps)."""
    (B, C), (L, N, *block) = pos.shape, cache["latent"].shape
    W, bs, nblk = cfg.latent_width, latent.block_size_of(cfg, cache), latent.blocks_of_window(cfg, cache, pos.shape[1])
    tables = jnp.pad(block_tables, ((0, 0), (0, nblk if pad is None else pad)))
    first = pos[:, 0]
    rows = cache["latent"].reshape(L * N, *block)[layer * N + tables].reshape(B, -1, W)
    rows = jax.vmap(lambda r, n, a: jax.lax.dynamic_update_slice(r, n, (a, 0)))(rows, row, first)
    blocks = jax.vmap(lambda r, a: jax.lax.dynamic_slice(r, (a // bs * bs, 0), (nblk * bs, W)))(rows, first)
    keys = block_tables.shape[1] * bs
    if flash:
        out = latent.attend_flash(cfg, p, q_nope[0], q_rope[0], rows[0, :keys], first[0], true_lens[0])[None]
    else:
        # (the null columns were keys of padded queries alone: cut here, so
        # that a narrower table is the wrong twin of a rung on both paths)
        mask = jnp.arange(keys, dtype=jnp.int32) <= pos[:, :, None]
        out = latent.attend_expanded(cfg, p, q_nope, q_rope, rows[:, :keys], mask)
    return out, blocks


@functools.lru_cache(maxsize=None)
def _inputs(name: str, dtype=jnp.float32):
    cfg = CONFIGS[name]()
    assert not latent.absorbs(cfg, C) and latent.absorbs(cfg, 1)
    rng = np.random.default_rng(53)
    normal = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)  # noqa: E731
    H, W = cfg.n_heads, cfg.latent_width
    cache = {"latent": normal(LAYERS, BLOCKS, BS * W)}  # every block holds something: stale rows too
    table = jnp.asarray(rng.permutation(np.arange(1, BLOCKS))[:M], jnp.int32)[None]
    p = {"w_kvb": normal(cfg.kv_lora_rank, H, cfg.qk_nope_head_dim + cfg.v_head_dim) * 0.3}
    q_nope, q_rope, row = normal(1, C, H, cfg.qk_nope_head_dim), normal(1, C, H, cfg.qk_rope_head_dim), normal(1, C, W)
    return cfg, p, q_nope, q_rope, row, cache, table


@functools.lru_cache(maxsize=None)
def _programs(name: str, flash: bool, dtype=jnp.float32):
    """ONE jitted program a form: the context and the length are traced, as a
    runner's prefill program has them."""
    cfg, p, q_nope, q_rope, row, _, table = _inputs(name, dtype)

    def call(fn, **kw):
        def run(cache, first, true_len, table=table):
            pos = first + jnp.arange(C, dtype=jnp.int32)[None]
            return fn(cfg, p, q_nope, q_rope, row, cache, LAYER, table, pos, true_len[None], flash=flash, **kw)
        return jax.jit(run)

    return call(latent.latent_attention), call(table_wide), call(table_wide, pad=0)


def _poisoned_past(cache, table, width: int):
    """The cache with every row the table holds at a position ``>= width`` NaN
    (in every layer)."""
    dead = np.asarray(table)[0, width // BS:]
    return {"latent": cache["latent"].at[:, dead].set(jnp.nan)}


def _compare(name, flash, first, true_len, dtype=jnp.float32):
    cfg, *_, cache, table = _inputs(name, dtype)
    program, reference, _ = _programs(name, flash, dtype)
    first, true_len = jnp.int32(first), jnp.int32(true_len)
    rung = next((w for w in RUNGS if w >= int(first + true_len)), KEYS)
    want, want_blocks = reference(cache, first, true_len)
    _, have_blocks = program(cache, first, true_len)
    have, _ = program(_poisoned_past(cache, table, rung), first, true_len)
    assert have.shape == want.shape == (1, C, cfg.n_heads, cfg.v_head_dim) and have.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(have_blocks, np.float32), np.asarray(want_blocks, np.float32))
    have, want = (np.asarray(a, np.float32)[0] for a in (have, want))
    n = int(true_len)
    assert np.isfinite(have).all() and np.isfinite(want[:n]).all()
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(have[:n], want[:n], rtol=0, atol=tol * np.abs(want[:n]).max())
    return rung


#: where the chunk's LAST real query stands in key tile ``n``; "one_past" is the next tile's first row
LANDS = {"first_row": lambda n: (n - 1) * TILE, "last_row": lambda n: n * TILE - 1, "one_past": lambda n: n * TILE}


@pytest.mark.parametrize("flash", [True, False], ids=["kernel", "materialised"])
@pytest.mark.parametrize("lands", LANDS)
@pytest.mark.parametrize("n", range(1, K + 1), ids=lambda n: f"tile{n}")
def test_every_rung_gives_the_table_wide_chunk(n, lands, flash):
    """A whole chunk (or, where the context is shorter than one, the prompt's
    first rows) whose last real query lands on tile ``n``'s first row, its
    last, and one past it. ``one_past`` of the last tile is the chunk that
    spills past the table's end: only its padded rows do."""
    last = LANDS[lands](n)
    if last >= KEYS:  # a whole chunk that ends on the table's end, one padding row more behind it
        first, true_len = KEYS - C + 1, C - 1
    else:
        true_len = min(C, last + 1)
        first = last + 1 - true_len
    rung = _compare("xing4", flash, first, true_len)
    assert rung == min(n + (lands == "one_past"), K) * TILE  # the key tiles the kernel reads


@pytest.mark.parametrize("flash", [True, False], ids=["kernel", "materialised"])
@pytest.mark.parametrize(
    "first, true_len",
    [(KEYS - C, C), (KEYS - C // 2, C // 2), (KEYS - 3, 3), (40, 5), (47, 1), (48, 1), (0, 1), (16, C // 2 - 3)],
    ids=["ends_on_the_table", "half_spills_past_the_table", "three_rows_left", "short_in_a_tile",
         "one_row_ends_a_tile", "one_row_starts_a_tile", "one_row", "short_on_a_tile_edge"],
)
def test_a_padded_chunk_takes_the_rung_of_its_real_rows(first, true_len, flash):
    """``true_len`` short of ``C``: the rung is the last REAL query's, the
    padding rows behind it are laid past the rung (into the next blocks' room,
    or the null columns behind the table) and are no key for anybody."""
    _compare("xing4", flash, first, true_len)


@pytest.mark.parametrize("flash", [True, False], ids=["kernel", "materialised"])
@pytest.mark.parametrize("name", ["deepseek_v3", "kimi_linear"])
def test_the_other_models_head_widths(name, flash):
    """DeepSeek-V3's value head (one and a half nope parts wide) and
    Kimi-Linear's configuration (its shared key part unrotated: the model's
    business) through the same rungs, a mid-table chunk and the last."""
    assert _compare(name, flash, 2 * TILE + 5, C) == 5 * TILE
    assert _compare(name, flash, KEYS - C, C - 7) == KEYS


def test_bfloat16_rows_through_the_kernel():
    assert _compare("xing4", True, 3 * TILE, C, dtype=jnp.bfloat16) == 5 * TILE


@pytest.mark.parametrize("flash", [True, False], ids=["kernel", "materialised"])
def test_a_rung_from_the_context_alone_is_a_tile_short(flash):
    """The wrong twin: the rung of ``first`` alone. Where the chunk's real rows
    stay inside that rung it is the right one; where they cross its edge the
    last queries lose their own keys, and the comparison reads it."""
    cfg, *_, cache, table = _inputs("xing4")
    _, reference, _ = _programs("xing4", flash)

    def twin(first, true_len):
        narrow = table[:, : next(w for w in RUNGS if w > first) // BS]
        return np.asarray(reference(cache, jnp.int32(first), jnp.int32(true_len), narrow)[0])[0, :true_len]

    def want(first, true_len):
        return np.asarray(reference(cache, jnp.int32(first), jnp.int32(true_len))[0])[0, :true_len]

    inside = (TILE + 3, TILE - 3)  # ends ON the last row of ``first``'s tile
    np.testing.assert_allclose(twin(*inside), want(*inside), rtol=0, atol=2e-6 * np.abs(want(*inside)).max())
    across = (TILE + 3, TILE - 2)  # one row over the edge
    assert np.abs(twin(*across) - want(*across)).max() > 1e-2 * np.abs(want(*across)).max()
    np.testing.assert_allclose(twin(*across)[:-1], want(*across)[:-1], rtol=0, atol=2e-6 * np.abs(want(*across)).max())
    assert _compare("xing4", flash, *across) == 3 * TILE  # the program's rung holds the row


def test_a_table_without_null_columns_lays_the_last_chunk_a_block_early():
    """The other wrong twin: the last rung needs the ``nblk`` null columns
    behind the table. Without them the overlay of a chunk that spills past
    the table's end clamps, and rows and blocks come out shifted."""
    cfg, *_, cache, table = _inputs("xing4")
    program, reference, clamped = _programs("xing4", False)
    first, true_len = jnp.int32(KEYS - C // 2), jnp.int32(C // 2)
    want, want_blocks = reference(cache, first, true_len)
    bad, bad_blocks = clamped(cache, first, true_len)
    n = int(true_len)
    assert np.abs(np.asarray(bad)[0, :n] - np.asarray(want)[0, :n]).max() > 1e-2 * np.abs(np.asarray(want)[0, :n]).max()
    assert not np.array_equal(np.asarray(bad_blocks)[0, : C // 2], np.asarray(want_blocks)[0, : C // 2])
    fits = jnp.int32(KEYS - C), jnp.int32(C)  # a chunk that ends ON the table's end clamps nothing
    np.testing.assert_array_equal(np.asarray(clamped(cache, *fits)[0]), np.asarray(reference(cache, *fits)[0]))


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("flash", [True, False], ids=["kernel", "materialised"])
def test_one_program_holds_every_rung(flash):
    """What the NaNs cannot show of the kernel's path (it never reads a dead
    tile, gathered or not): the program is ONE ``lax.switch`` over the table's
    eight rungs, and a rung gathers its own tiles' blocks and the window's
    room, and no other; the window's own blocks are gathered once, outside."""
    *_, cache, _ = _inputs("xing4")
    jaxpr = jax.make_jaxpr(_programs("xing4", flash)[0])(cache, jnp.int32(0), jnp.int32(C)).jaxpr
    # (``pl.when`` inside the interpreted kernel is a ``cond`` of two)
    (switch,) = [e for e in _eqns(jaxpr) if e.primitive.name == "cond" and len(e.params["branches"]) == len(RUNGS)]
    nblk = (C + BS - 2) // BS + 1
    stored = BS * _inputs("xing4")[0].latent_width  # a block of the cache, as gathered
    gathered = [
        [
            e.outvars[0].aval.shape[0] for e in _eqns(branch.jaxpr)
            if e.primitive.name == "gather" and e.outvars[0].aval.shape[1:] == (stored,)
        ]
        for branch in switch.params["branches"]
    ]
    assert gathered == [[width // BS + nblk] for width in RUNGS]
    (program,) = jaxpr.eqns  # the jitted call
    outside = [e.outvars[0].aval.shape for e in program.params["jaxpr"].eqns if e.primitive.name == "gather"]
    assert [shape for shape in outside if shape[-1] == stored] == [(1, nblk, stored)]


def test_the_rungs_are_read_off_shapes(monkeypatch):
    """Every whole number of key tiles up to the table; the table whole where
    it is one tile (the toy tables of the other tests), no whole number of
    tiles, or a tile is no whole number of blocks. ``Model.gather_rungs`` is
    what the runner rounds ``expanded_tokens`` up to: the rungs at the
    published widths, none (the table whole) for a model without rungs."""
    assert latent.key_rungs(C, KEYS, BS) == RUNGS
    assert latent.key_rungs(C, 2 * TILE, BS) == (TILE, 2 * TILE)
    assert latent.key_rungs(C, TILE, BS) == (TILE,)
    assert latent.key_rungs(C, KEYS + BS, BS) == (KEYS + BS,)
    assert latent.key_rungs(C, 5 * 24, 24) == (120,)  # tiles of 16 are no whole blocks of 24
    monkeypatch.setattr(latent_flash, "_KEY_TILE", 1024)  # as published
    for module in (xing4, deepseek_v3, kimi_linear):
        config = type(CONFIGS[module.__name__.rsplit(".", 1)[1]]())
        big = config(dtype=jnp.bfloat16, max_seq_len=8192)
        stored = jax.eval_shape(lambda big=big, module=module: module.cache_layout(big, 16).init(8))
        for window in (256, 1024):
            assert module.MODEL.gather_rungs(big, window, stored) == latent.key_rungs(window, 8192, 16) == RUNGS_8192
        assert module.MODEL.gather_rungs(config(dtype=jnp.bfloat16, max_seq_len=1024), 1024, stored) == (1024,)
    tiny = llama.LlamaConfig.tiny()
    assert model_of(tiny).gather_rungs(tiny, 32, None) == ()
