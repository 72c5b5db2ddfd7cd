"""The two ways ``models/paged_kv.py::write_kv`` lays a window's K and V into
a paged cache: by rows (``scatter_kv``: an update a token, a token a head in a
cache stored flat) and by blocks (``write_blocks``: the span's blocks gathered,
overlaid, written back as ONE scatter of whole blocks). The shapes choose
(``write_way``); after either the cache is the same BIT FOR BIT everywhere but
the null block, in each of the three forms a block is stored in. Random caches
on the CPU: what is compared is data movement, so the comparison is exact."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import paged_kv

BS, M, N, LAYERS = 4, 8, 24, 2  # positions a block, blocks a table row, blocks in the pool, layers an array
N_KV, HD = 2, 8

#: the three stored forms: a block's shape, and the window's K as the caller hands it over
FORMS = {
    "heads_apart": ((BS, N_KV, HD), lambda k: k),
    "flat_rows": ((BS * N_KV, HD), lambda k: k),
    "heads_in_lanes": ((BS, N_KV * HD), lambda k: k.reshape(*k.shape[:2], 1, -1)),
}

#: name -> (chunk, start, true_len, table row, layer): what a chunk can meet
CASES = {
    "starts_on_a_block": (8, 8, 8, (3, 5, 7, 9, 11, 0, 0, 0), 0),
    # the one-token tail of a full prefix hit at len - 1: the block's earlier rows are the prefix
    "one_token_mid_block": (8, 14, 1, (3, 5, 7, 9, 0, 0, 0, 0), 0),
    "starts_mid_block": (8, 6, 8, (3, 5, 7, 9, 0, 0, 0, 0), 1),
    "rows_past_true_len_left": (8, 4, 3, (3, 5, 7, 9, 0, 0, 0, 0), 0),
    "rows_past_true_len_in_unallocated_blocks": (8, 4, 3, (3, 5, 0, 0, 0, 0, 0, 0), 1),
    "ends_at_the_tables_last_column": (8, 24, 8, (3, 5, 7, 9, 11, 13, 15, 17), 0),
    "ends_mid_block_at_the_tables_last_column": (8, 26, 6, (3, 5, 7, 9, 11, 13, 15, 17), 1),
    "null_columns_behind_the_chunk": (8, 16, 8, (0, 0, 0, 9, 11, 13, 0, 0), 0),  # a window group's table
    "second_layer_of_the_array": (4, 5, 4, (3, 5, 7, 0, 0, 0, 0, 0), 1),
    "nothing_valid": (8, 8, 0, (3, 5, 7, 9, 0, 0, 0, 0), 0),
}


def _cache(form, seed=0):
    rng = np.random.default_rng(seed)
    block = FORMS[form][0]
    return {name: jnp.asarray(rng.standard_normal((LAYERS, N, *block)), jnp.float32) for name in ("k", "v")}


def _window(form, batch, chunk, seed=1):
    rng = np.random.default_rng(seed)
    shape = FORMS[form][1]
    return tuple(shape(jnp.asarray(rng.standard_normal((batch, chunk, N_KV, HD)), jnp.float32)) for _ in "kv")


def _by_rows(cache, layer, tables, pos, valid, k, v):
    """The parent's write, whatever the shapes: the addresses made as its callers made them."""
    blk, off = jnp.where(valid, paged_kv.block_at(tables, pos, BS), 0), pos % BS
    return paged_kv.scatter_kv(cache, layer, blk, off, k, v)


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("form", list(FORMS))
def test_a_chunk_written_by_blocks_leaves_the_cache_the_rows_way_leaves(form, case):
    chunk, start, true_len, row, layer = CASES[case]
    cache, (k, v) = _cache(form), _window(form, 1, chunk)
    tables = jnp.asarray([row], jnp.int32)
    pos = (start + jnp.arange(chunk, dtype=jnp.int32))[None]
    valid = (jnp.arange(chunk) < true_len)[None]
    assert paged_kv.write_way(1, chunk, BS) == "blocks"
    want = _by_rows(cache, layer, tables, pos, valid, k, v)
    have = jax.jit(paged_kv.write_kv, static_argnums=1)(cache, layer, tables, pos, valid, k, v)
    for name in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(have[name])[:, 1:], np.asarray(want[name])[:, 1:])
        # ... and the rows the chunk names are the chunk's (the comparison is not of two untouched caches)
        flat = np.asarray(have[name][layer]).reshape(N, BS, -1)
        new = np.asarray(k if name == "k" else v).reshape(chunk, -1)
        for c in range(true_len):
            np.testing.assert_array_equal(flat[row[(start + c) // BS], (start + c) % BS], new[c])
    if true_len == 0:
        for name in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(have[name])[:, 1:], np.asarray(cache[name])[:, 1:])


@pytest.mark.parametrize("form", list(FORMS))
def test_a_mid_block_start_keeps_the_blocks_earlier_rows(form):
    """The copy-on-write block of a full prefix hit: positions 12 and 13 of block
    9 hold the prefix, the chunk's one valid row lands at 14, and 15 stays."""
    cache, (k, v) = _cache(form), _window(form, 1, 8)
    tables = jnp.asarray([(3, 5, 7, 9, 0, 0, 0, 0)], jnp.int32)
    pos = (14 + jnp.arange(8, dtype=jnp.int32))[None]
    have = paged_kv.write_kv(cache, 1, tables, pos, (jnp.arange(8) < 1)[None], k, v)
    before, after = (np.asarray(c["k"][1, 9]).reshape(BS, -1) for c in (cache, have))
    np.testing.assert_array_equal(after[[0, 1, 3]], before[[0, 1, 3]])
    np.testing.assert_array_equal(after[2], np.asarray(k).reshape(8, -1)[0])
    np.testing.assert_array_equal(np.asarray(have["k"][0]), np.asarray(cache["k"][0]))  # the other layer whole


#: the shapes that keep the rows way: (batch, chunk)
ROWS = {"several_slots": (2, 8), "decode": (1, 1), "a_batch_of_decodes": (3, 1), "no_whole_blocks": (1, 6)}


@pytest.mark.parametrize("shape", list(ROWS))
@pytest.mark.parametrize("form", list(FORMS))
def test_every_other_window_lowers_to_the_parents_scatter(form, shape):
    batch, chunk = ROWS[shape]
    assert paged_kv.write_way(batch, chunk, BS) == "rows"
    cache, (k, v) = jax.eval_shape(lambda: (_cache(form), _window(form, batch, chunk)))
    of = jax.ShapeDtypeStruct
    tables, pos, valid = of((batch, M), jnp.int32), of((batch, chunk), jnp.int32), of((batch, chunk), bool)

    def parent(cache, tables, pos, valid, k, v):
        return _by_rows(cache, 1, tables, pos, valid, k, v)

    def now(cache, tables, pos, valid, k, v):
        return paged_kv.write_kv(cache, 1, tables, pos, valid, k, v, at=paged_kv.rows_at(tables, pos, valid, BS))

    def unaddressed(cache, tables, pos, valid, k, v):
        return paged_kv.write_kv(cache, 1, tables, pos, valid, k, v)

    texts = [
        jax.jit(f).trace(cache, tables, pos, valid, k, v).lower(lowering_platforms=(platform,)).as_text()
        .replace(f.__name__, "f")
        for f in (parent, now, unaddressed) for platform in ("cpu", "tpu")
    ]
    assert texts[0:2] == texts[2:4] == texts[4:6]
    assert "scatter" in texts[0]


@pytest.mark.parametrize("form", list(FORMS))
def test_a_chunk_by_blocks_is_one_scatter_an_array(form):
    """The program of the blocks way holds ONE scatter an array, of ``C / bs +
    1`` whole blocks, and the rows way's one an array, of a row a token."""
    cache, (k, v) = jax.eval_shape(lambda: (_cache(form), _window(form, 1, 8)))
    of = jax.ShapeDtypeStruct
    args = (of((1, M), jnp.int32), of((1, 8), jnp.int32), of((1, 8), bool), k, v)
    text = jax.jit(lambda c, *a: paged_kv.write_kv(c, 0, *a)).trace(cache, *args).lower().as_text()
    assert text.count('"stablehlo.scatter"(') == 2
    block = "x".join(str(n) for n in FORMS[form][0])
    assert f"tensor<3x{block}xf32>" in text  # the update: 8 / 4 + 1 whole blocks


@pytest.mark.parametrize("form,per", [("heads_apart", 1), ("flat_rows", N_KV), ("heads_in_lanes", 1)])
def test_the_count_of_updates_is_the_ways(form, per):
    block = FORMS[form][0]
    assert paged_kv.write_updates(1, 8, block, BS) == 3
    assert paged_kv.write_updates(1, 1024, (16 * block[0] // BS, *block[1:]), 16) == 65
    assert paged_kv.write_updates(1, 6, block, BS) == 6 * per
    assert paged_kv.write_updates(2, 8, block, BS) == 16 * per
    assert paged_kv.write_updates(3, 1, block, BS) == 3 * per


def test_rows_at_is_none_where_blocks_are_written():
    tables, valid = jnp.zeros((1, M), jnp.int32), jnp.ones((1, 8), bool)
    assert paged_kv.rows_at(tables, jnp.arange(8, dtype=jnp.int32)[None], valid, BS) is None
    blk, off = paged_kv.rows_at(tables, jnp.arange(6, dtype=jnp.int32)[None], valid[:, :6], BS)
    assert blk.shape == off.shape == (1, 6)
