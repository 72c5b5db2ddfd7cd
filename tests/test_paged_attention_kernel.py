"""The paged-attention kernel (``ops/paged_attention.py``) against the gather
path of ``models/llama.py::_paged_attention``, on the CPU in Pallas' TPU
interpreter at tiny widths with ``head_dim`` 128.

Every case runs the KERNEL over a poisoned cache and the GATHER over the
clean one: K past every slot's last position, V likewise, and every block no
table refers to, the null block among them, are NaN / inf. The interpreter
hands out NaN for memory nobody wrote, so a wave's unfetched blocks are
poison too. One read past the mask and the output is not finite. Block
tables are a shuffle of the pool; the last two slots of every batch are
padding on the null block: the kernel reads nothing for them and returns
zeros (the gather attends to the null block's trash there; nobody reads
either).

NARROW heads (``head_dim`` 64) run the same cases with a token's heads side by
side in one row of the cache the kernel is handed, ``[layers, blocks, bs, n_kv x
64]`` (the same bytes as the gather's ``[layers, blocks, bs, n_kv, 64]``)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama as L
from ray_tpu.ops import paged_attention as PA

BS, M, HD, N_KV, LAYERS, LAYER = 4, 8, 128, 2, 2, 1
FULL = M * BS

#: what the FIRST row of a slot's window sees (its context with this step's
#: token), a slot each; 0 = a padding slot
CONTEXTS = {
    "one": (1,),
    "a_block_less_one": (BS - 1,),
    "a_block": (BS,),
    "a_block_and_one": (BS + 1,),
    "the_full_table": (FULL,),
    "ragged": (1, FULL, BS + 1, 3 * BS, 2 * BS - 1, 17),
}


def _case(rep, window, contexts, seed=0, dtype=jnp.float32, step=1, hd=HD):
    """``(cfg, q, clean cache, poisoned cache, tables, pos)``: the window's
    rows sit at ``context - 1 + step * c`` (clipped to the table), so the
    last row sees the most."""
    rng = np.random.default_rng(seed)
    H = N_KV * rep
    contexts = (*contexts, 0, 0)
    B = len(contexts)
    N = 1 + B * M
    k, v = rng.standard_normal((2, LAYERS, N, BS, N_KV, hd)).astype(np.float32)
    shuffled = rng.permutation(np.arange(1, N))
    tables = np.zeros((B, M), np.int32)
    pos = np.zeros((B, window), np.int32)
    for b, ctx in enumerate(contexts):
        if ctx:
            tables[b] = shuffled[b * M:(b + 1) * M]
            pos[b] = np.minimum(ctx - 1 + step * np.arange(window), FULL - 1)
    live = np.zeros((N, BS), bool)
    for b in range(B - 2):
        for p in range(pos[b].max() + 1):
            live[tables[b, p // BS], p % BS] = True
    kp, vp = k.copy(), v.copy()
    kp[:, ~live], vp[:, ~live] = np.nan, np.inf
    q = rng.standard_normal((B, window, H, hd)).astype(np.float32)
    cfg = dataclasses.replace(L.LlamaConfig.tiny(), n_heads=H, n_kv_heads=N_KV, dim=H * hd)
    as_cache = lambda k_, v_: {"k": jnp.asarray(k_, dtype), "v": jnp.asarray(v_, dtype)}  # noqa: E731
    return cfg, jnp.asarray(q, dtype), as_cache(k, v), as_cache(kp, vp), jnp.asarray(tables), jnp.asarray(pos)


def _both(case, **kw):
    cfg, q, clean, poisoned, tables, pos = case
    want = L._paged_attention(cfg, q, clean, LAYER, tables, pos)  # the CPU: the gather
    if q.shape[-1] < 128:  # narrow heads: a token's heads in one row, said beside it
        poisoned = {name: a.reshape(*a.shape[:3], -1) for name, a in poisoned.items()}
        kw["n_kv"] = N_KV
    have = PA.paged_attention(
        q, poisoned["k"], poisoned["v"], LAYER, tables, pos, interpret=True, **kw
    )
    assert have.shape == want.shape and have.dtype == want.dtype
    have, want = np.asarray(have, np.float32), np.asarray(want, np.float32)
    assert (have[-2:] == 0).all()  # the padding slots
    return have[:-2], want[:-2]


@pytest.mark.parametrize("contexts", list(CONTEXTS))
@pytest.mark.parametrize("window", [1, 4], ids=["decode", "verify_window_of_4"])
@pytest.mark.parametrize("rep", [4, 1], ids=["rep4", "rep1"])
@pytest.mark.parametrize("hd", [HD, 64], ids=["heads_of_128", "heads_of_64_in_lanes"])
def test_kernel_is_the_gather_and_reads_nothing_past_the_mask(hd, rep, window, contexts):
    """Waves of 2 blocks, so a full table is 4 waves and a ragged batch ends
    each slot's loop somewhere else."""
    have, want = _both(_case(rep, window, CONTEXTS[contexts], hd=hd), wave_blocks=2)
    assert np.isfinite(have).all()
    np.testing.assert_allclose(have, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("contexts", ["ragged", "the_full_table"])
@pytest.mark.parametrize("window", [1, 3], ids=["decode", "window_of_3"])
def test_one_kv_head_under_twenty_query_heads_stored_flat(window, contexts):
    """Multi-query attention as AI21-Jamba2-3B has it: 20 query heads (no
    whole tile of 8 or 16 rows a slot) over ONE KV head, the cache stored flat
    ``[layers, blocks, bs x 1, hd]`` with ``n_kv`` said beside it."""
    rng = np.random.default_rng(20)
    ctxs = (*CONTEXTS[contexts], 0, 0)
    B, H, N = len(ctxs), 20, 1 + len(ctxs) * M
    k, v = (jnp.asarray(a) for a in rng.standard_normal((2, LAYERS, N, BS, 1, HD)).astype(np.float32))
    tables, pos = np.zeros((B, M), np.int32), np.zeros((B, window), np.int32)
    shuffled = rng.permutation(np.arange(1, N))
    for b, ctx in enumerate(ctxs):
        if ctx:
            tables[b] = shuffled[b * M:(b + 1) * M]
            pos[b] = np.minimum(ctx - 1 + np.arange(window), FULL - 1)
    q = jnp.asarray(rng.standard_normal((B, window, H, HD)).astype(np.float32))
    want = L._attend_gathered(q, k, v, LAYER, jnp.asarray(tables), jnp.asarray(pos), 1, FULL)
    flat = lambda a: a.reshape(LAYERS, N, BS, HD)  # noqa: E731
    assert PA.kernel_serves(window, H, jax.ShapeDtypeStruct((LAYERS, N, 16, HD), jnp.bfloat16), backend="tpu", n_kv=1)
    have = PA.paged_attention(q, flat(k), flat(v), LAYER, jnp.asarray(tables), jnp.asarray(pos), interpret=True,
                              n_kv=1, wave_blocks=2)
    assert (np.asarray(have)[-2:] == 0).all()  # the padding slots
    np.testing.assert_allclose(np.asarray(have)[:-2], np.asarray(want)[:-2], rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("wave_blocks", [1, 3, None], ids=["a_block_a_wave", "3_blocks", "the_default_wave"])
@pytest.mark.parametrize("hd", [HD, 64], ids=["heads_of_128", "heads_of_64_in_lanes"])
def test_any_wave_size_gives_the_same_numbers(hd, wave_blocks):
    """3 does not divide the table's 8 blocks; the default wave (2048 rows of
    K, cut to the table) is the whole table."""
    have, want = _both(_case(4, 1, CONTEXTS["ragged"], seed=1, hd=hd), wave_blocks=wave_blocks)
    assert np.isfinite(have).all()
    np.testing.assert_allclose(have, want, rtol=2e-5, atol=2e-5)


def test_each_row_of_a_window_masks_on_its_own_position():
    """Rows three positions apart: a row must not see what only a later row
    may (the keys between them are real and would move it)."""
    case = _case(4, 4, (2, BS + 2, FULL - 9), seed=2, step=3)
    have, want = _both(case, wave_blocks=2)
    np.testing.assert_allclose(have, want, rtol=2e-5, atol=2e-5)
    cfg, q, clean, _, tables, pos = case
    blind = L._paged_attention(cfg, q, clean, LAYER, tables, jnp.broadcast_to(pos[:, -1:], pos.shape))
    assert np.abs(np.asarray(blind)[:3] - want)[:, 0].max() > 1e-2


@pytest.mark.parametrize("hd", [HD, 64], ids=["heads_of_128", "heads_of_64_in_lanes"])
def test_bfloat16_cache_accumulates_in_float32(hd):
    """The serving dtype. The gather rounds its scores to bfloat16 out of the
    first matmul, the kernel keeps them float32: they agree to bfloat16's
    step, and the kernel is the nearer of the two to the float32 answer."""
    case16 = _case(4, 1, CONTEXTS["ragged"], seed=3, dtype=jnp.bfloat16, hd=hd)
    have, want = _both(case16, wave_blocks=2)
    assert np.isfinite(have).all()
    np.testing.assert_allclose(have, want, rtol=0, atol=3e-2)
    cfg, q, clean, _, tables, pos = case16
    exact = np.asarray(L._paged_attention(
        cfg, q.astype(jnp.float32), jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), clean),
        LAYER, tables, pos,
    ))[:-2]
    assert np.abs(have - exact).max() <= np.abs(want - exact).max() + 2e-3


@pytest.mark.parametrize(
    "backend, window, n_heads, cache_shape, dtype, serves",
    [
        # narrow heads, a token's 8 heads of 64 in one row, said beside the cache: LFM2 decode
        ("tpu", 1, 32, ((6, 27000, 16, 512), {"n_kv": 8, "head_dim": 64}), jnp.bfloat16, True),
        ("tpu", 1024, 32, ((6, 27000, 16, 512), {"n_kv": 8, "head_dim": 64}), jnp.bfloat16, False),  # its chunk
        ("cpu", 1, 32, ((6, 27000, 16, 512), {"n_kv": 8, "head_dim": 64}), jnp.bfloat16, False),
        ("tpu", 1, 4, ((2, 24, 8, 32), {"n_kv": 2, "head_dim": 16}), jnp.bfloat16, False),  # a row that is no whole lanes
        # the same array WITHOUT the head's width is heads of 512 joined to the tokens, as ever
        ("tpu", 1, 32, ((6, 27000, 16, 512), {"n_kv": 8}), jnp.bfloat16, True),
        # ONE KV head under 20 query heads, a block stored flat [16 x 1, 128]: Jamba2-3B decode at 256 slots (PR 52: run)
        ("tpu", 1, 20, ((2, 131073, 16, 128), {"n_kv": 1}), jnp.bfloat16, True),
        ("tpu", 1024, 20, ((2, 131073, 16, 128), {"n_kv": 1}), jnp.bfloat16, False),  # its chunk: the flash kernel's
        ("cpu", 1, 20, ((2, 131073, 16, 128), {"n_kv": 1}), jnp.bfloat16, False),
        ("tpu", 1, 32, (16, 6144, 16, 8, 128), jnp.bfloat16, True),  # Mistral decode
        ("tpu", 8, 32, (16, 6144, 16, 8, 128), jnp.bfloat16, True),  # a verify window
        ("tpu", 1, 16, (12, 2240, 16, 16, 128), jnp.bfloat16, True),  # OLMoE decode
        ("tpu", 1, 32, (16, 6144, 16, 8, 128), jnp.float32, True),
        ("tpu", 256, 32, (16, 6144, 16, 8, 128), jnp.bfloat16, False),  # a prefill chunk
        ("tpu", 1024, 16, (12, 2240, 16, 16, 128), jnp.bfloat16, False),
        ("cpu", 1, 32, (16, 6144, 16, 8, 128), jnp.bfloat16, False),  # off the chip
        ("tpu", 1, 4, (2, 24, 4, 2, 16), jnp.float32, False),  # a head that is no whole lane
        ("tpu", 1, 12, (2, 24, 16, 6, 128), jnp.bfloat16, False),  # KV heads that are no whole tile (Mosaic refuses)
        ("tpu", 1, 8, (2, 24, 16, 1, 128), jnp.bfloat16, False),
        ("tpu", 1, 32, (2, 24, 16, 8, 128), jnp.float16, False),  # a dtype the MXU does not multiply
    ],
)
def test_the_kernel_serves_short_windows_of_whole_tiles_on_a_tpu(backend, window, n_heads, cache_shape, dtype, serves):
    cache_shape, said = cache_shape if isinstance(cache_shape[1], dict) else (cache_shape, {})
    cache_like = jax.ShapeDtypeStruct(cache_shape, dtype)
    assert PA.kernel_serves(window, n_heads, cache_like, backend=backend, **said) is serves
