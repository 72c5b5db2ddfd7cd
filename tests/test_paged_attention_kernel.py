"""The paged-attention kernel (``ops/paged_attention.py``) against the gather
path of ``models/paged_kv.py::attention``, on the CPU in Pallas' TPU
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
from ray_tpu.models import paged_kv
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


def _attention(cfg, q, cache, layer, tables, pos):
    return paged_kv.attention(
        q, cache["k"], cache["v"], layer, tables, pos, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim)


def _both(case, **kw):
    cfg, q, clean, poisoned, tables, pos = case
    want = _attention(cfg, q, clean, LAYER, tables, pos)  # the CPU: the gather
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
    want = paged_kv.attend_gathered(q, k, v, LAYER, jnp.asarray(tables), jnp.asarray(pos), 1, FULL)
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
    blind = _attention(cfg, q, clean, LAYER, tables, jnp.broadcast_to(pos[:, -1:], pos.shape))
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
    exact = np.asarray(_attention(
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


# -- waves: ONE wait a full wave, a window's span cut into waves it fills (ISSUE 57) -----------------

def _window_case(contexts, keeps, block, n_kv, table_blocks, seed=0, window=1):
    """Slots at ``contexts`` (0: a padding slot) under a layer that keeps
    ``keeps``, blocks of ``block`` (``[bs, n_kv, hd]`` or flat ``[bs * n_kv,
    hd]``) in a shuffled pool. Returns ``(q, clean K/V, poisoned K/V, tables,
    pos, spans)``: the poisoned cache is NaN / inf past every slot's last
    position, in every block no table holds and in every block wholly behind
    the first query's window (whose table entries are the null block, as the
    block manager leaves them); ``spans`` the live blocks a slot."""
    rng = np.random.default_rng(seed)
    bs = int(np.prod(block[:-1])) // n_kv
    hd, B, Mt = block[-1], len(contexts), table_blocks
    N = 1 + sum(-(-c // bs) for c in contexts)
    k, v = rng.standard_normal((2, LAYERS, N, bs, n_kv, hd)).astype(np.float32)
    pool = iter(rng.permutation(np.arange(1, N)))
    tables, pos = np.zeros((B, Mt), np.int32), np.zeros((B, window), np.int32)
    live, spans = np.zeros((N, bs), bool), []
    for b, ctx in enumerate(contexts):
        if not ctx:
            spans.append(0)
            continue
        pos[b] = np.minimum(ctx - 1 + np.arange(window), Mt * bs - 1)
        first = max(0, pos[b].min() - keeps + 1) // bs if keeps else 0
        last = pos[b].max() // bs
        tables[b, first:last + 1] = [next(pool) for _ in range(last + 1 - first)]
        for p in range(first * bs, pos[b].max() + 1):
            live[tables[b, p // bs], p % bs] = True
        spans.append(last + 1 - first)
    kp, vp = k.copy(), v.copy()
    kp[:, ~live], vp[:, ~live] = np.nan, np.inf
    q = rng.standard_normal((B, window, n_kv * 2, hd)).astype(np.float32)
    to = lambda a: jnp.asarray(a.reshape(LAYERS, N, *block))  # noqa: E731
    return jnp.asarray(q), (jnp.asarray(k), jnp.asarray(v)), (to(kp), to(vp)), jnp.asarray(tables), jnp.asarray(pos), spans


def _kernel_and_gather(case, keeps, n_kv, capfd, **kw):
    """The kernel over the poisoned cache, the gather over the clean one, for
    the real slots; a padding slot is zeros; and the interpreter found every
    DMA semaphore back at zero when the kernel ended (it prints one that is not)."""
    q, clean, poisoned, tables, pos, spans = case
    keys = tables.shape[1] * clean[0].shape[2]
    want = np.asarray(paged_kv.attend_gathered(q, *clean, LAYER, tables, pos, n_kv, keys, keeps))
    said = {} if poisoned[0].ndim == 5 else {"n_kv": n_kv}
    capfd.readouterr()
    have = np.asarray(PA.paged_attention(q, *poisoned, LAYER, tables, pos, interpret=True, keeps=keeps, **said, **kw))
    assert "non-zero count" not in capfd.readouterr().out
    real = np.asarray(spans) > 0
    assert np.isfinite(have).all() and (have[~real] == 0).all()
    return have[real], want[real]


WAVE = 3


@pytest.mark.parametrize("span", [1, WAVE, WAVE + 1, 2 * WAVE + 1], ids=["one_block", "a_wave", "a_wave_and_one", "two_waves_and_one"])
@pytest.mark.parametrize("form", ["five_d", "flat"])
def test_a_windows_span_in_waves_of_three_is_the_gather(form, span, capfd):
    """A window whose live span is 1, P, P + 1 and 2P + 1 blocks (P = 3): full
    waves wait once, the last by the bits of its count, and what a partial wave
    did not fetch (NaN in the interpreter's VMEM, as in the pool) never reaches
    the output. A slot short of its window, a padding slot and a slot of one
    token share the batch."""
    keeps = (span - 1) * BS + 1  # a query at a block's first row sees span - 1 blocks, elsewhere span
    contexts = (12 * BS - 1, 12 * BS + 1, 0, 9 * BS, 1, 2, 11 * BS + 2, 0)
    block = (BS, N_KV, HD) if form == "five_d" else (BS * N_KV, HD)
    case = _window_case(contexts, keeps, block, N_KV, 16, seed=span)
    assert span in case[-1] and max(case[-1]) == span
    have, want = _kernel_and_gather(case, keeps, N_KV, capfd, wave_blocks=WAVE)
    np.testing.assert_allclose(have, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "block, n_kv, keeps, wave",
    [((16, 8, 128), 8, 512, 17), ((64, 128), 4, 1024, 34)],
    ids=["laguna_33_blocks_in_waves_of_17", "mellum2_65_blocks_in_waves_of_34"],
)
def test_the_default_wave_of_both_shipped_windows_is_the_gather(block, n_kv, keeps, wave, capfd):
    """The shipped window shapes at their own block sizes and the default
    wave: a slot far past its window holds ``keeps // 16 + 1`` blocks, two waves
    (the second one block short); one whose last row ends a block a block fewer; one
    short of its window; a verify-sized tail is the loop's business, not the
    default's."""
    span = keeps // 16 + 1
    assert PA.blocks_a_wave(block, 16, span + 8, keeps) == wave
    contexts = (keeps + 100, keeps + 16 * 3, 0, keeps - 40, 16 * wave)
    case = _window_case(contexts, keeps, block, n_kv, span + 8, seed=keeps)
    assert case[-1][:2] == [span, span - 1]
    have, want = _kernel_and_gather(case, keeps, n_kv, capfd)
    np.testing.assert_allclose(have, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("keeps", [0, 5 * BS], ids=["keeps_all", "a_window"])
def test_a_full_waves_single_wait_leaves_the_next_waves_bytes_alone(keeps, capfd):
    """Full waves (waited for ONCE a buffer) followed by a padding slot, by a
    slot of one block and by another full wave in the other buffer: each wait
    takes its own wave's bytes and no more, so both buffers' semaphores are
    back at zero when the kernel ends and every slot reads its own blocks."""
    contexts = (WAVE * BS, 0, 1, 2 * WAVE * BS, 0, BS, WAVE * BS, WAVE * BS + 1, 0)
    case = _window_case(contexts, keeps, (BS, N_KV, HD), N_KV, 8, seed=7)
    have, want = _kernel_and_gather(case, keeps, N_KV, capfd, wave_blocks=WAVE)
    np.testing.assert_allclose(have, want, rtol=2e-5, atol=2e-5)


#: the six shipped shapes: (a block of the cache, table blocks) -> the wave of a
#: layer that keeps everything, and of one that keeps a window
SHIPPED = {
    "mistral": ((16, 8, 128), 256, 16, None),
    "olmoe": ((16, 16, 128), 256, 8, None),
    "mellum2": ((64, 128), 1024, 32, (1024, 34)),
    "lfm2": ((16, 512), 512, 32, None),
    "jamba2": ((16, 128), 512, 128, None),
    "laguna": ((16, 8, 128), 512, 16, (512, 17)),
}


@pytest.mark.parametrize("name", list(SHIPPED))
def test_blocks_a_wave_on_the_shipped_shapes(name):
    """A layer that keeps everything takes the wave it always took (2048 rows
    of 128 lanes); a window's span is cut into waves it fills: no wave of one
    block for a full window, and the scores' columns are whole lane tiles."""
    block, table_blocks, full, window = SHIPPED[name]
    rows = int(np.prod(block[:-1]))
    assert PA.blocks_a_wave(block, 16, table_blocks) == full
    assert full == min(table_blocks, max(1, (2048 * 128 // block[-1] if block[-1] != 128 else 2048) // rows))
    assert (full * rows) % 128 == 0
    assert PA.blocks_a_wave(block, 16, 4) == 4  # never wider than the table
    if window:
        keeps, wave = window
        span = keeps // 16 + 1
        assert PA.blocks_a_wave(block, 16, table_blocks, keeps) == wave
        assert -(-span // wave) == 2 < -(-span // full) and span % wave != 1 and (wave * rows) % 128 == 0
