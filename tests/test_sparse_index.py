"""``ops/sparse_index.py`` (the indexer's scores and the EXACT top ``k`` of
them: a radix select, no sort) against plain ``numpy``, and a ``CacheLayout``
of two row widths (a latent row and an indexer's key under one block table):
what it builds, what it says of itself, and the refusal of its payload."""

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
from ray_tpu.ops import sparse_index  # noqa: E402


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
