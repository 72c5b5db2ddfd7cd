"""``ops/sparse_index.py`` (the indexer's scores and the EXACT top ``k`` of
them: a radix select, no sort) against plain ``numpy``, and a ``CacheLayout``
of two row widths (a latent row and an indexer's key under one block table):
what it builds, what it says of itself, and the refusal of its payload. And
``ops/index_paged.py`` (the same scores with the keys read by a Pallas kernel
from each slot's live blocks) in Pallas' TPU interpreter against
``index_scores`` over each slot's gathered context."""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.inference import EngineConfig  # noqa: E402
from ray_tpu.inference.engine import InferenceEngine  # noqa: E402
from ray_tpu.models import glm_dsa, latent  # noqa: E402
from ray_tpu.models.interface import CacheLayout, copy_paged_blocks  # noqa: E402
from ray_tpu.ops import index_paged, sparse_index  # noqa: E402


def _scores_by_hand(q, w, keys):
    s = np.einsum("rhd,sd->rhs", q.astype(np.float64), keys.astype(np.float64))
    return (np.maximum(s, 0) * w.astype(np.float64)[:, :, None]).sum(1)


def _select_by_hand(scores, limit, k):
    """The ``k`` largest among positions ``<= limit`` a row, of equal scores
    the lower position: a stable sort, descending."""
    out = np.zeros(scores.shape, bool)
    for r, row in enumerate(scores):
        seen = np.arange(len(row)) <= limit[r]
        order = np.argsort(-(row + 0.0), kind="stable")
        order = [s for s in order if seen[s]][:k]
        out[r, order] = True
    return out


@pytest.mark.parametrize("tile_scores", [None, 4 * 3 * 128], ids=["one_tile", "tiled_keys"])
def test_index_scores_against_numpy(monkeypatch, tile_scores):
    """``sum_j w_j relu(q_j . k)`` in float32, with the keys in one piece and
    (a small ``_TILE_SCORES``) a tile at a time under ``lax.map``."""
    if tile_scores:
        monkeypatch.setattr(sparse_index, "_TILE_SCORES", tile_scores)
    rng = np.random.default_rng(3)
    q, w, keys = rng.standard_normal((4, 3, 16)), rng.standard_normal((4, 3)), rng.standard_normal((512, 16))
    q, w, keys = (a.astype(np.float32) for a in (q, w, keys))
    have = np.asarray(jax.jit(sparse_index.index_scores)(q, w, keys))
    want = _scores_by_hand(q, w, keys)
    assert have.shape == (4, 512) and have.dtype == np.float32
    np.testing.assert_allclose(have, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("case", ["random", "ties", "all_equal", "fewer_than_k", "negative_and_zero", "padded_chunk"])
def test_select_mask_is_the_exact_top_k_with_ties_to_the_lower_position(case):
    rng = np.random.default_rng(5)
    R, S, k = 12, 96, 16
    scores = rng.standard_normal((R, S)).astype(np.float32)
    limit = S - R + np.arange(R, dtype=np.int32)  # a chunk's queries: the last R positions
    if case == "ties":  # a few distinct values: the k-th is shared by many
        scores = np.round(scores * 2) / 2
    elif case == "all_equal":
        scores[:] = 0.25
    elif case == "fewer_than_k":  # t < k: everything seen is chosen
        limit = np.arange(R, dtype=np.int32)
    elif case == "negative_and_zero":  # relu leaves exact zeros; -0.0 is 0.0
        scores = np.where(rng.random((R, S)) < 0.6, 0.0, scores).astype(np.float32)
        scores[:, ::7] = -0.0
    elif case == "padded_chunk":  # the rows past the real ones are nobody's: finite, any limit
        scores[R // 2 :] = 1e30
    have = np.asarray(jax.jit(lambda s, l: sparse_index.select_mask(s, l, k))(scores, limit))
    want = _select_by_hand(scores, limit, k)
    np.testing.assert_array_equal(have, want)
    assert list(have.sum(1)) == [min(k, int(l) + 1) for l in limit]
    positions, real = jax.jit(lambda m: sparse_index.mask_positions(m, k))(have)
    for r in range(R):
        chosen = np.flatnonzero(want[r])
        assert list(np.asarray(positions)[r][np.asarray(real)[r]]) == list(chosen)
        assert int(np.asarray(real)[r].sum()) == len(chosen)


def test_two_slots_of_unlike_length_select_each_among_their_own():
    """A decode window: 2 slots, 2 queries each, contexts of 70 and 9 under
    one table of 96; ``vmap`` over the slots as ``latent._sparse_attention``."""
    rng = np.random.default_rng(9)
    B, C, S, k = 2, 2, 96, 16
    scores = rng.standard_normal((B, C, S)).astype(np.float32)
    at = np.asarray([70, 9], np.int32)
    limit = at[:, None] + np.arange(C, dtype=np.int32)
    have = np.asarray(jax.jit(jax.vmap(lambda s, l: sparse_index.select_mask(s, l, k)))(scores, limit))
    for b in range(B):
        np.testing.assert_array_equal(have[b], _select_by_hand(scores[b], limit[b], k))
    assert list(have.sum(-1).reshape(-1)) == [16, 16, 10, 11]


def test_kth_largest_walks_the_bits_of_an_ordered_key():
    keys = np.asarray([[5, 9, 9, 1, 0, 0, 7, 2**31 + 3]], np.uint32)
    for k, want in ((1, 2**31 + 3), (2, 9), (3, 9), (4, 7), (6, 1), (7, 0), (8, 0)):
        assert int(sparse_index.kth_largest(jnp.asarray(keys), k)[0]) == want
    x = np.asarray([[-3.5, -0.0, 0.0, 1e-30, 2.0, -np.inf, 7.25]], np.float32)
    ordered = np.asarray(sparse_index._ordered(jnp.asarray(x)))[0]
    assert list(np.argsort(ordered, kind="stable")) == [5, 0, 1, 2, 3, 4, 6] and ordered[1] == ordered[2] and ordered.min() > 0


# -- the scores of a short window through the paged kernel --------------------------------------

def _paged_case(Hi, di, contexts, dtype, seed=0, bs=16, M=6, layers=2):
    """``(q, w, clean keys, poisoned keys, tables, ctx)``: a shuffled pool,
    ``None`` in ``contexts`` a padding slot (its table on the null block).
    Every key past a slot's context, the dead rows of its last block among
    them, and every block no table refers to, the null block among them, is
    NaN in the poisoned array."""
    rng = np.random.default_rng(seed)
    B, C = len(contexts), 2
    N = 1 + B * M
    keys = rng.standard_normal((layers, N, bs, di)).astype(np.float32)
    tables, ctx, live = np.zeros((B, M), np.int32), np.zeros(B, np.int32), np.zeros((N, bs), bool)
    shuffled = rng.permutation(np.arange(1, N))
    for b, c in enumerate(contexts):
        if c is None:
            continue
        tables[b], ctx[b] = shuffled[b * M : (b + 1) * M], c
        for p in range(c):
            live[tables[b, p // bs], p % bs] = True
    poisoned = keys.copy()
    poisoned[:, ~live] = np.nan
    q = jnp.asarray(rng.standard_normal((B, C, Hi, di)), dtype)
    w = jnp.asarray(rng.standard_normal((B, C, Hi)) / np.sqrt(Hi * di), jnp.float32)
    return q, w, jnp.asarray(keys, dtype), jnp.asarray(poisoned, dtype), jnp.asarray(tables), jnp.asarray(ctx)


@pytest.mark.parametrize("wave_blocks", [1, 4, None], ids=["a_block_a_wave", "4_blocks", "the_default_wave"])
@pytest.mark.parametrize(
    "Hi, di, dtype", [(4, 128, jnp.float32), (32, 128, jnp.bfloat16)], ids=["toy", "the_published_widths"]
)
def test_the_paged_kernel_scores_a_slots_live_keys_and_reads_nothing_past_them(Hi, di, dtype, wave_blocks):
    """Slots of unlike length (one a single key, one that ends mid-block,
    one on a block's edge, one the table less the window), a padding slot
    between them and one at the end, and a real slot whose window starts its
    sequence: ``index_scores`` over the slot's gathered keys at every
    position under its context, the window's own scores at the two positions
    behind it, EXACTLY 0 past those and never a NaN. 4 does not divide the
    table's 6 blocks."""
    contexts = (37, None, 92, 1, 32, 0, None)
    bs, M, layer = 16, 6, 1
    q, w, clean, poisoned, tables, ctx = _paged_case(Hi, di, contexts, dtype)
    B = len(contexts)
    own = jnp.asarray(np.random.default_rng(8).standard_normal((B, 2, 2)), jnp.float32)
    have = np.asarray(
        index_paged.index_scores(q, w, own, poisoned, layer, tables, ctx, wave_blocks=wave_blocks, interpret=True)
    )
    assert have.shape == (B, 2, M * bs) and have.dtype == np.float32 and np.isfinite(have).all()
    for b, c in enumerate(contexts):
        c = c or 0
        if c:
            gathered = clean[layer][tables[b]].reshape(M * bs, di)
            want = np.asarray(sparse_index.index_scores(q[b], w[b], gathered))
            np.testing.assert_allclose(have[b, :, :c], want[:, :c], rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(have[b, :, c : c + 2], np.asarray(own[b]))
        assert (have[b, :, c + 2 :] == 0).all()


def test_the_choice_over_the_kernels_scores_walks_ties_zeros_and_negative_scores():
    """Scores with many equals at the threshold, exact zeros (relu leaves
    them) and negative weights' negative sums. Keys and queries of small whole
    numbers, so that the kernel's scores are ``index_scores``' to the last bit
    and equal scores are equal; ``select_mask`` over them chooses the lower
    position of a tie, a window's own positions among the candidates, nothing
    past a query's limit (the kernel's zeros there would tie with real ones)."""
    Hi, di, bs, M, k = 4, 128, 16, 6, 8
    rng = np.random.default_rng(12)
    contexts = (90, 40)
    B, N = len(contexts), 1 + 2 * M
    keys = np.zeros((1, N, bs, di), np.float32)
    keys[..., 0] = rng.integers(-1, 3, size=(1, N, bs))  # one live lane: a score is w . relu(q0 * key0)
    tables = np.arange(1, N, dtype=np.int32).reshape(B, M)
    q = np.zeros((B, 2, Hi, di), np.float32)
    q[..., 0] = rng.integers(-1, 2, size=(B, 2, Hi))
    w = rng.integers(-1, 3, size=(B, 2, Hi)).astype(np.float32)
    ctx = jnp.asarray(contexts, jnp.int32)
    limit = np.asarray(ctx)[:, None] + np.arange(2, dtype=np.int32)[None]
    own = jnp.asarray(rng.integers(-1, 3, size=(B, 2, 2)), jnp.float32)
    have = np.asarray(index_paged.index_scores(
        jnp.asarray(q), jnp.asarray(w), own, jnp.asarray(keys), 0, jnp.asarray(tables), ctx,
        wave_blocks=2, interpret=True,
    ))
    assert len(np.unique(have)) <= 12  # ties everywhere
    for b, c in enumerate(contexts):
        want = np.asarray(sparse_index.index_scores(jnp.asarray(q[b]), jnp.asarray(w[b]), jnp.asarray(keys[0, tables[b]].reshape(M * bs, di))))
        np.testing.assert_array_equal(have[b, :, :c], want[:, :c])
    flat, lim = have.reshape(B * 2, M * bs), limit.reshape(-1)
    chosen = np.asarray(sparse_index.select_mask(jnp.asarray(flat), jnp.asarray(lim), k))
    np.testing.assert_array_equal(chosen, _select_by_hand(flat, lim, k))
    assert list(chosen.sum(1)) == [k] * (B * 2)


@pytest.mark.parametrize(
    "backend, window, heads, di, shape, dtype, serves",
    [
        ("tpu", 2, 32, 128, (7, 12289, 16, 128), jnp.bfloat16, True),  # GLM-5's verify window
        ("tpu", 1, 32, 128, (7, 12289, 16, 128), jnp.float32, True),
        ("cpu", 2, 32, 128, (7, 12289, 16, 128), jnp.bfloat16, False),  # off the chip
        ("tpu", 2, 3, 16, (4, 24, 8 * 16), jnp.float32, False),  # the tests' toy widths: a block one row
        ("tpu", 2, 32, 64, (7, 12289, 8, 128), jnp.bfloat16, False),  # two keys a stored row
        ("tpu", 1, 3, 128, (7, 12289, 16, 128), jnp.bfloat16, False),  # query rows that are no whole sublanes
        ("tpu", 2, 32, 128, (7, 12289, 16, 128), jnp.float16, False),  # a dtype the MXU does not multiply
    ],
)
def test_the_paged_index_kernel_serves_whole_tiles_on_a_tpu(backend, window, heads, di, shape, dtype, serves):
    assert index_paged.kernel_serves(window, heads, di, jax.ShapeDtypeStruct(shape, dtype), backend=backend) is serves


# -- a cache of two row widths ------------------------------------------------------------------

def test_a_cache_layout_of_two_row_widths():
    cfg = glm_dsa.GlmDsaConfig()
    layout = latent.cache_layout(cfg, 16, jnp.bfloat16, n_layers=7)
    assert layout.arrays == (("latent", (576,)), ("index", (128,))) and not layout.one_payload
    assert layout.row_width == 704 and layout.bytes_per_token == 7 * 704 * 2 == 9856
    assert layout.block_shape((576,)) == (8, 1152) and layout.block_shape((128,)) == (16, 128)
    said = layout.describe()
    assert said["arrays"] == {"latent": {"row_width": 576, "bytes_per_token": 8064},
                              "index": {"row_width": 128, "bytes_per_token": 1792}}
    assert said["bytes_per_token"] == 9856 and said["kind"] == "latent"
    with pytest.raises(ValueError, match="different rows cannot share a payload"):
        layout.payload_shape(4)
    tiny = glm_dsa.GlmDsaConfig.tiny()
    small = glm_dsa.MODEL.cache_layout(tiny, 8)
    cache = small.init(12)
    assert {k: v.shape for k, v in cache.items()} == {"latent": (4, 12, 8 * 24), "index": (4, 12, 8 * 16)}
    # a model that does not select keeps its one array and its one payload
    one = latent.cache_layout(glm_dsa.DeepseekV3Config(), 16, jnp.bfloat16)
    assert one.one_payload and "arrays" not in one.describe() and [n for n, _ in one.arrays] == ["latent"]
    # the COW copy moves both arrays of a block
    cache = {k: v.at[:, 3].set(1.0) for k, v in cache.items()}
    copied = copy_paged_blocks(cache, jnp.asarray([3]), jnp.asarray([5]))
    assert all(float(a[:, 5].min()) == 1.0 and float(a[:, 4].max()) == 0.0 for a in copied.values())


@pytest.mark.parametrize("field", ["kv_transfer_enabled", "kv_tier_enabled"])
def test_the_payload_of_two_row_widths_is_refused_where_the_engine_is_made(field):
    cfg = glm_dsa.GlmDsaConfig.tiny()
    params = glm_dsa.MODEL.init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(num_blocks=32, block_size=8, prefill_buckets=(16,), decode_buckets=(2,), max_decode_batch=2,
              warmup=False, prefix_cache_enabled=False)
    with pytest.raises(ValueError, match=f"{field} cannot run here.*rows of different widths.*latent 24, index 16"):
        InferenceEngine(cfg, params, EngineConfig(**kw, **{field: True}))
