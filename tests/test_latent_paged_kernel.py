"""The kernel over latent rows (``ops/latent_paged.py``) against the gather
path's ``models/latent.py::attend_rows``, on the CPU in Pallas' TPU
interpreter at small widths of whole lanes.

Every case runs the KERNEL over a poisoned cache and ``attend_rows`` over the
clean one's gathered context: every row past a slot's cached context, the
dead rows of its last block among them, and every block no table refers to,
the null block among them, are NaN. The interpreter hands out NaN for memory
nobody wrote, so a wave's unfetched blocks are poison too. One read past the
mask and the output is not finite. Block tables are a shuffle of the pool;
a padding slot (its table on the null block) sits BETWEEN the real ones and
two more end the batch: the kernel reads nothing for them and the door
returns zeros.

Under a SELECTION (a model that chooses ``index_topk`` positions a query) the
same kernel takes the chosen positions as a mask: held to the materialised
softmax under that mask (``latent.attend_masked``, the window's own rows laid
in), to ``attend_rows`` where the window's own are all chosen, and, the whole
window of ``latent._sparse_attention`` through both paged kernels, to the
by-token path (``mask_positions`` + ``_token_rows``) it stands in for."""

import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import glm_dsa, kimi_linear, latent, xing4
from ray_tpu.models.interface import CacheLayout
from ray_tpu.ops import latent_paged as LP

BS, M, LAYERS, LAYER = 16, 6, 2, 1
FULL = M * BS

#: the widths of a case: (kr, dr, heads); ``two_a_row`` is both models' form
#: at a fifth of the width (two rows of 192 fill three lanes' worth: T = 2,
#: the value a slice of the key), ``one_a_row`` a row that is whole lanes
WIDTHS = {"two_a_row": (128, 64, 8), "one_a_row": (128, 128, 8)}

#: cached positions a slot's window sees before its own rows; 0 on a real
#: slot is a window that starts its sequence
CONTEXTS = {
    "one": (1,),
    "mid_block": (BS + 5,),
    "a_block": (BS,),
    "a_block_and_one": (BS + 1,),
    "the_table_less_the_window": (FULL - 4,),
    "ragged": (1, FULL - 4, BS + 1, 3 * BS, 2 * BS - 1, 37, 0),
}


def _cfg(kr, dr, heads, dtype):
    return types.SimpleNamespace(
        kv_lora_rank=kr, qk_rope_head_dim=dr, latent_width=kr + dr, n_heads=heads,
        attn_scale=0.11, dtype=dtype,
    )


def _case(widths, window, contexts, seed=0, dtype=jnp.float32):
    """``(cfg, q_row, own, clean cache, poisoned cache, tables, first, real)``:
    a padding slot after the first real one and two at the end."""
    kr, dr, H = WIDTHS[widths]
    W = kr + dr
    rng = np.random.default_rng(seed)
    ctxs = [contexts[0], None, *contexts[1:], None, None]
    B = len(ctxs)
    N = 1 + B * M
    layout = CacheLayout("latent", LAYERS, BS, (("latent", (W,)),), dtype, flat_blocks=True)
    block = layout.block_shape((W,))
    assert len(block) == 2 and block[0] % 8 == 0 and block[1] % 128 == 0
    rows = rng.standard_normal((LAYERS, N, BS, W)).astype(np.float32)
    shuffled = rng.permutation(np.arange(1, N))
    tables = np.zeros((B, M), np.int32)
    first = np.zeros((B,), np.int32)
    live = np.zeros((N, BS), bool)
    for b, ctx in enumerate(ctxs):
        if ctx is None:
            continue
        tables[b] = shuffled[b * M:(b + 1) * M]
        first[b] = ctx
        for p in range(ctx):
            live[tables[b, p // BS], p % BS] = True
    poisoned = rows.copy()
    poisoned[:, ~live] = np.nan
    q_row = rng.standard_normal((B, window, H, W)).astype(np.float32)
    own = rng.standard_normal((B, window, W)).astype(np.float32)
    as_cache = lambda r: {"latent": jnp.asarray(r.reshape(LAYERS, N, *block), dtype)}  # noqa: E731
    real = np.asarray([c is not None for c in ctxs])
    return (
        _cfg(kr, dr, H, dtype), jnp.asarray(q_row, dtype), jnp.asarray(own, dtype), as_cache(rows),
        as_cache(poisoned), jnp.asarray(tables), jnp.asarray(first), real,
    )


def _want(cfg, q_row, own, cache, tables, first, layer=LAYER):
    """``attend_rows`` over the whole table gathered from the clean cache."""
    L, N, *block = cache["latent"].shape
    W = cfg.latent_width
    rows = cache["latent"].reshape(L * N, *block)[layer * N + tables].reshape(tables.shape[0], -1, W)
    mask = jnp.arange(rows.shape[1])[None, None, :] < first[:, None, None]
    mask = jnp.broadcast_to(mask, (tables.shape[0], q_row.shape[1], rows.shape[1]))
    return latent.attend_rows(cfg, q_row, rows, mask, own)


def _both(case, **kw):
    cfg, q_row, own, clean, poisoned, tables, first, real = case
    want = _want(cfg, q_row, own, clean, tables, first)
    acc, m, l = LP.attend_paged(
        q_row, poisoned["latent"], LAYER, tables, first, kv_lora_rank=cfg.kv_lora_rank,
        scale=cfg.attn_scale, interpret=True, **kw,
    )
    # a padding slot read nothing: the softmax's state is as it started
    assert (np.asarray(acc)[~real] == 0).all() and (np.asarray(l)[~real] == 0).all()
    assert (np.asarray(m)[~real] == -1e30).all()
    have = latent.attend_paged(cfg, q_row, poisoned, LAYER, tables, first, own, interpret=True)
    assert have.shape == want.shape and have.dtype == want.dtype
    have, want = np.asarray(have, np.float32), np.asarray(want, np.float32)
    assert (have[~real] == 0).all()
    return have[real], want[real]


@pytest.mark.parametrize("contexts", list(CONTEXTS))
@pytest.mark.parametrize("window", [1, 4], ids=["decode", "verify_window_of_4"])
@pytest.mark.parametrize("widths", list(WIDTHS))
def test_kernel_is_attend_rows_and_reads_nothing_past_the_context(widths, window, contexts):
    """Waves of 2 blocks, so the longest context is 3 waves and a ragged
    batch ends each slot's loop somewhere else."""
    have, want = _both(_case(widths, window, CONTEXTS[contexts]), wave_blocks=2)
    assert np.isfinite(have).all()
    np.testing.assert_allclose(have, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("wave_blocks", [1, 4, None], ids=["a_block_a_wave", "4_blocks", "the_default_wave"])
def test_any_wave_size_gives_the_same_numbers(wave_blocks):
    """4 does not divide the table's 6 blocks; the default wave (cut to the
    table) is the whole table."""
    have, want = _both(_case("two_a_row", 1, CONTEXTS["ragged"], seed=1), wave_blocks=wave_blocks)
    assert np.isfinite(have).all()
    np.testing.assert_allclose(have, want, rtol=2e-5, atol=2e-5)


def test_the_windows_own_rows_are_under_the_same_softmax():
    """The kernel's state alone, normalised without the window's own rows,
    is NOT the answer: the fold is not a formality."""
    cfg, q_row, own, clean, _, tables, first, real = _case("two_a_row", 4, CONTEXTS["ragged"], seed=2)
    want = np.asarray(_want(cfg, q_row, own, clean, tables, first))[real]
    acc, _, l = LP.attend_paged(
        q_row, clean["latent"], LAYER, tables, first, kv_lora_rank=cfg.kv_lora_rank,
        scale=cfg.attn_scale, interpret=True,
    )
    # a window at the start of its sequence has only its own rows: leave it out
    some = real & (np.asarray(first) > 0)
    without = np.asarray(acc / l[..., None])[some]
    assert np.abs(without - want[np.asarray(first)[real] > 0]).max() > 1e-2


# -- under a selection: the chosen positions as a mask --------------------------------------------

def _chosen(first, window, seed, share=0.3):
    """A selection as ``select_mask`` leaves one: causal (``j <= first + c``),
    a random ``share`` of the rest; ``[B, window, FULL]`` bool numpy."""
    rng = np.random.default_rng(seed)
    pos = np.asarray(first)[:, None] + np.arange(window)[None]
    return (rng.random((len(pos), window, FULL)) < share) & (np.arange(FULL)[None, None] <= pos[..., None])


def _want_masked(cfg, q_row, own, cache, tables, first, chosen, layer=LAYER):
    """The materialised softmax under the mask, a slot at a time: the slot's
    gathered context with the window's own rows laid in where they will be
    written (``latent.attend_masked``: what a selecting chunk runs on the CPU)."""
    L, N, *block = cache["latent"].shape
    W, C = cfg.latent_width, q_row.shape[1]
    out = []
    for b in range(tables.shape[0]):
        rows = cache["latent"].reshape(L * N, *block)[layer * N + tables[b]].reshape(-1, W)
        at = min(int(first[b]), FULL - C)  # a padding slot: anywhere
        rows = jax.lax.dynamic_update_slice(rows, own[b], (at, 0))
        out.append(latent.attend_masked(cfg, q_row[b], rows, jnp.asarray(chosen[b])))
    return jnp.stack(out)


@pytest.mark.parametrize("wave_blocks", [1, 2, 4, None], ids=["a_block_a_wave", "2_blocks", "4_blocks", "the_default_wave"])
@pytest.mark.parametrize("window", [1, 4], ids=["decode", "verify_window_of_4"])
@pytest.mark.parametrize("widths", list(WIDTHS))
def test_the_kernel_under_a_selection_is_the_masked_softmax(widths, window, wave_blocks):
    """A third of the causal positions chosen, the window's own among them
    or not as the draw has it; one query's first 40 positions left out
    whole, so that its first wave (of 1 or 2 blocks) holds nothing it chose
    and the softmax's state must wait for a wave that does. A query that chose
    nothing at all (the draw leaves a few at short contexts) is nobody's."""
    cfg, q_row, own, clean, poisoned, tables, first, real = _case(widths, window, CONTEXTS["ragged"], seed=3)
    chosen = _chosen(first, window, seed=1)
    chosen[2, 0, :40] = False  # slot 2 holds FULL - 4 positions
    want = np.asarray(_want_masked(cfg, q_row, own, clean, tables, first, chosen))
    have = np.asarray(latent.attend_paged(
        cfg, q_row, poisoned, LAYER, tables, first, own, chosen=jnp.asarray(chosen), interpret=True,
    ) if wave_blocks is None else _folded(cfg, q_row, poisoned, tables, first, own, chosen, wave_blocks))
    some = real[:, None] & chosen.any(-1)
    assert np.isfinite(have).all() and (have[~real] == 0).all() and some[2, 0]
    np.testing.assert_allclose(have[some], want[some], rtol=2e-5, atol=2e-5)


def _folded(cfg, q_row, cache, tables, first, own, chosen, wave_blocks):
    """``latent.attend_paged`` at a wave size of the test's choosing."""
    real_call = LP.attend_paged
    try:
        LP.attend_paged = lambda *a, **kw: real_call(*a, **{**kw, "wave_blocks": wave_blocks})
        return latent.attend_paged(cfg, q_row, cache, LAYER, tables, first, own, chosen=jnp.asarray(chosen), interpret=True)
    finally:
        LP.attend_paged = real_call


@pytest.mark.parametrize("own_chosen", [True, False], ids=["own_chosen", "own_not_chosen"])
def test_an_own_position_is_under_the_same_softmax_only_if_chosen(own_chosen):
    """The window's own positions all chosen: ``attend_rows`` under the
    cached part of the mask (its ``within`` is the window's causal triangle).
    None of them chosen: the kernel's state alone, normalised. The two differ."""
    cfg, q_row, own, clean, poisoned, tables, first, real = _case("two_a_row", 4, CONTEXTS["ragged"], seed=5)
    some = real & (np.asarray(first) > 0)  # a window at the start of its sequence has only its own rows
    chosen = _chosen(first, 4, seed=2, share=0.5)
    chosen[:, :, 0] = np.asarray(first)[:, None] > 0  # every query of a slot with a context chose something cached
    for c in range(4):
        chosen[np.arange(len(real)), :, np.minimum(np.asarray(first) + c, FULL - 1)] = own_chosen
    chosen &= np.arange(FULL)[None, None] <= (np.asarray(first)[:, None] + np.arange(4)[None])[..., None]
    cached = jnp.asarray(chosen) & (jnp.arange(FULL)[None, None, :] < first[:, None, None])
    have = np.asarray(latent.attend_paged(cfg, q_row, poisoned, LAYER, tables, first, own, chosen=jnp.asarray(chosen), interpret=True))
    L, N, *block = clean["latent"].shape
    rows = clean["latent"].reshape(L * N, *block)[LAYER * N + tables].reshape(tables.shape[0], -1, cfg.latent_width)
    with_own = np.asarray(latent.attend_rows(cfg, q_row, rows, cached, own))
    acc, _, l = LP.attend_paged(
        q_row, clean["latent"], LAYER, tables, first, kv_lora_rank=cfg.kv_lora_rank, scale=cfg.attn_scale,
        chosen=cached, interpret=True,
    )
    without = np.asarray(acc / l[..., None])
    want, other = (with_own, without) if own_chosen else (without, with_own)
    assert np.isfinite(have).all()
    np.testing.assert_allclose(have[some], want[some], rtol=2e-5, atol=2e-5)
    assert np.abs(have[some] - other[some]).max() > 1e-2


def test_a_selection_of_everything_is_the_kernel_without_a_mask():
    """A context under ``index_topk``: every causal position is chosen, and
    the answer is ``attend_paged``'s without the operand, to the last bit."""
    cfg, q_row, own, _, poisoned, tables, first, real = _case("two_a_row", 4, CONTEXTS["ragged"], seed=6)
    everything = jnp.asarray(_chosen(first, 4, seed=0, share=2.0))
    have = latent.attend_paged(cfg, q_row, poisoned, LAYER, tables, first, own, chosen=everything, interpret=True)
    want = latent.attend_paged(cfg, q_row, poisoned, LAYER, tables, first, own, interpret=True)
    np.testing.assert_array_equal(np.asarray(have), np.asarray(want))


def _tiled_selecting_case(seed, contexts, dtype=jnp.float32):
    """GLM-5's block at toy widths of whole tiles (a latent row of 128 + 64,
    two a stored row; an index key of 128; 4 heads, 4 indexer heads, the top
    24 of a table of 96) with a decode window of two over ``contexts`` (None:
    a padding slot): everything ``latent._sparse_attention`` takes, a clean
    cache and one poisoned past every slot's context."""
    cfg = glm_dsa.GlmDsaConfig.tiny(
        dtype=dtype, max_seq_len=FULL, kv_lora_rank=128, qk_rope_head_dim=64, index_head_dim=128,
        index_n_heads=4, index_topk=24,
    )
    rng = np.random.default_rng(seed)
    B, C, N = len(contexts), 2, 1 + len(contexts) * M
    layout = latent.cache_layout(cfg, BS, dtype, n_layers=LAYERS)
    clean = {name: rng.standard_normal(a.shape).astype(np.float32) for name, a in layout.init(N).items()}
    assert clean["latent"].shape[2:] == (8, 384) and clean["index"].shape[2:] == (16, 128)
    tables, first, live = np.zeros((B, M), np.int32), np.zeros(B, np.int32), np.zeros((N, BS), bool)
    shuffled = rng.permutation(np.arange(1, N))
    for b, ctx in enumerate(contexts):
        if ctx is None:
            continue
        tables[b], first[b] = shuffled[b * M : (b + 1) * M], ctx
        for p in range(ctx):
            live[tables[b, p // BS], p % BS] = True
    poisoned = {}
    for name, a in clean.items():
        rows = a.reshape(LAYERS, N, BS, -1).copy()
        rows[:, ~live] = np.nan
        poisoned[name] = jnp.asarray(rows.reshape(a.shape), dtype)
    clean = {name: jnp.asarray(a, dtype) for name, a in clean.items()}
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), dtype)  # noqa: E731
    H, Hi, di, W = cfg.n_heads, cfg.index_n_heads, cfg.index_head_dim, cfg.latent_width
    p = {"w_kvb": f(cfg.kv_lora_rank, H, cfg.qk_nope_head_dim + cfg.v_head_dim) / 11.0}
    window = dict(
        q_nope=f(B, C, H, cfg.qk_nope_head_dim), q_rope=f(B, C, H, cfg.qk_rope_head_dim), row=f(B, C, W),
        index=(f(B, C, Hi, di), f(B, C, di), jnp.asarray(rng.standard_normal((B, C, Hi)), jnp.float32) / 23.0),
    )
    pos = jnp.asarray(first)[:, None] + jnp.arange(C)[None]
    real = np.asarray([c is not None for c in contexts])
    return cfg, p, window, clean, poisoned, jnp.asarray(tables), pos, real


@pytest.mark.parametrize(
    "contexts",
    [(37, None, 90, 16, 5, 0, None), (FULL - 2, 70)],
    ids=["ragged_on_both_sides_of_the_topk", "the_table_less_the_window"],
)
def test_a_selecting_window_through_both_paged_kernels_is_the_by_token_path(monkeypatch, contexts):
    """``latent._sparse_attention``'s decode / verify branch two ways: as the
    CPU runs it (the index keys at the table's width, ``mask_positions``,
    ``_token_rows``: over the CLEAN cache, it reads the table) and as a TPU
    does (``sparse_paged_serves`` answered as there; both kernels in Pallas'
    interpreter over the POISONED cache). The same output and the same blocks
    for the write; contexts under the top 24 (everything chosen) and past it."""
    cfg, p, w, clean, poisoned, tables, pos, real = _tiled_selecting_case(7, contexts)
    true_lens = jnp.full((len(contexts),), 2, jnp.int32)

    def run(cache):
        return latent._sparse_attention(
            cfg, p, w["q_nope"], w["q_rope"], w["row"], w["index"], cache, jnp.int32(LAYER), tables, pos, true_lens
        )

    want, want_blocks = run(clean)
    calls = []
    monkeypatch.setattr(latent, "sparse_paged_serves", lambda cfg, window, cache, backend=None: True)
    real_scores, real_attend = latent.index_paged.index_scores, latent.latent_paged.attend_paged
    monkeypatch.setattr(latent.index_paged, "index_scores", lambda *a, **kw: calls.append("index") or real_scores(*a, **kw))
    monkeypatch.setattr(
        latent.latent_paged, "attend_paged",
        lambda *a, **kw: calls.append("rows" if kw.get("chosen") is not None else "unmasked") or real_attend(*a, **kw),
    )
    have, have_blocks = run(poisoned)
    assert calls == ["index", "rows"]
    have, want = np.asarray(have), np.asarray(want)
    assert np.isfinite(have).all() and (have[~real] == 0).all()
    np.testing.assert_allclose(have[real], want[real], rtol=3e-5, atol=3e-5)
    # the window's blocks as the cache must hold them after the step: the old
    # rows under the context, the window's own two; what lies past is nobody's
    have_blocks, want_blocks = np.asarray(have_blocks), np.asarray(want_blocks)
    assert (have_blocks[~real] == 0).all()
    for b in np.flatnonzero(real):
        n = int(pos[b, 0]) % BS + 2
        np.testing.assert_array_equal(have_blocks[b, :n], want_blocks[b, :n])


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
@pytest.mark.parametrize("model", ["xing4", "kimi_linear"])
def test_both_models_dimensions_in_the_serving_dtypes(model, dtype):
    """The published widths of both models (32 heads, 512 + 64: two tokens a
    stored row of 1152) through their OWN config objects and cache layouts.
    In bfloat16 the gather rounds its normalised probabilities, the kernel
    the unnormalised: they agree to bfloat16's step."""
    if model == "xing4":
        cfg = xing4.Xing4Config(dtype=dtype)
        layout = xing4.MODEL.cache_layout(cfg, BS, dtype)
    else:
        cfg = kimi_linear.KimiLinearConfig(dtype=dtype)
        layout = kimi_linear.MODEL.cache_layout(cfg, BS, dtype)
    W, H = cfg.latent_width, cfg.n_heads
    assert layout.block_shape((W,)) == (8, 1152)
    rng = np.random.default_rng(4)
    ctxs = (37, 0, FULL - 1, BS)
    B, N = len(ctxs), 1 + len(ctxs) * M
    rows = rng.standard_normal((1, N, BS, W)).astype(np.float32)
    tables = np.zeros((B, M), np.int32)
    live = np.zeros((N, BS), bool)
    for b, ctx in enumerate(ctxs):
        if ctx:
            tables[b] = 1 + b * M + np.arange(M)
            for p in range(ctx):
                live[tables[b, p // BS], p % BS] = True
    poisoned = rows.copy()
    poisoned[:, ~live] = np.nan
    as_cache = lambda r: {"latent": jnp.asarray(r.reshape(1, N, 8, 1152), dtype)}  # noqa: E731
    q_row = jnp.asarray(rng.standard_normal((B, 1, H, W)) * 0.3, dtype)
    own = jnp.asarray(rng.standard_normal((B, 1, W)), dtype)
    tables, first = jnp.asarray(tables), jnp.asarray(ctxs, jnp.int32)
    want = np.asarray(_want(cfg, q_row, own, as_cache(rows), tables, first, layer=0), np.float32)
    have = np.asarray(latent.attend_paged(cfg, q_row, as_cache(poisoned), 0, tables, first, own, interpret=True), np.float32)
    real = np.asarray(ctxs) > 0
    assert np.isfinite(have).all() and (have[~real] == 0).all()
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(have[real], want[real], rtol=0, atol=tol)


@pytest.mark.parametrize(
    "backend, window, n_heads, widths, cache_shape, dtype, serves",
    [
        ("tpu", 1, 32, (576, 512), (7, 12664, 8, 1152), jnp.bfloat16, True),  # Kimi-Linear decode
        ("tpu", 1, 32, (576, 512), (40, 2824, 8, 1152), jnp.bfloat16, True),  # Xing4 decode
        ("tpu", 8, 32, (576, 512), (40, 2824, 8, 1152), jnp.bfloat16, True),  # a verify window
        ("tpu", 1, 32, (576, 512), (40, 2824, 8, 1152), jnp.float32, True),
        ("tpu", 1, 8, (256, 128), (2, 24, 16, 256), jnp.bfloat16, True),  # a row of whole lanes: one a stored row
        ("tpu", 16, 32, (576, 512), (40, 2824, 8, 1152), jnp.bfloat16, False),  # a longer verify window
        ("cpu", 1, 32, (576, 512), (40, 2824, 8, 1152), jnp.bfloat16, False),  # off the chip
        ("tpu", 1, 32, (576, 512), (40, 2824, 9216), jnp.bfloat16, False),  # a block stored as one row
        ("tpu", 1, 4, (24, 16), (4, 24, 192), jnp.float32, False),  # the tests' toy widths
        ("tpu", 1, 32, (576, 512), (40, 2824, 4, 2304), jnp.bfloat16, False),  # more tokens a row than fill the lanes
        ("tpu", 1, 32, (576, 512), (40, 2824, 4, 1152), jnp.bfloat16, False),  # a block of 8: no whole tile
        ("tpu", 1, 32, (640, 576), (40, 2824, 16, 640), jnp.bfloat16, False),  # a value that is no whole lanes
        ("tpu", 1, 3, (576, 512), (40, 2824, 8, 1152), jnp.bfloat16, False),  # query rows that are no whole sublanes
        ("tpu", 1, 32, (576, 512), (40, 2824, 8, 1152), jnp.float16, False),  # a dtype the MXU does not multiply
    ],
)
def test_the_kernel_serves_short_windows_over_whole_tiles_on_a_tpu(backend, window, n_heads, widths, cache_shape, dtype, serves):
    cache_like = jax.ShapeDtypeStruct(cache_shape, dtype)
    assert LP.kernel_serves(window, n_heads, *widths, cache_like, backend=backend) is serves


@pytest.mark.parametrize(
    "block_size, width, shape",
    [
        (16, 576, (8, 1152)),  # both models: two tokens a stored row
        (32, 576, (16, 1152)),
        (16, 128, (16, 128)),  # a row of whole lanes stays a row
        (16, 192, (8, 384)),
        (8, 576, (8 * 576,)),  # four stored rows are no whole tile: one row a block
        (8, 24, (8 * 24,)),  # the tests' toy widths
    ],
)
def test_a_flat_block_is_whole_tiles_or_one_row(block_size, width, shape):
    layout = CacheLayout("latent", 3, block_size, (("latent", (width,)),), jnp.bfloat16, flat_blocks=True)
    assert layout.block_shape((width,)) == shape
    assert layout.payload_shape(5) == (1, 3, 5, *shape)
    assert layout.init(4)["latent"].shape == (3, 4, *shape)
    assert layout.bytes_per_token == 3 * width * 2  # no padding either way
