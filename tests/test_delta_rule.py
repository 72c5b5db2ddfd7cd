"""``ops/delta_rule.py``, the ONE place of the gated delta rule: the chunked
(WY) form for a gate a HEAD (``gdn_chunked``: Gated DeltaNet's) against the
recurrence a position (``kda_update``) and against ``kda_chunked`` at a gate
broadcast over ``dk``, at ``beta`` up to 2 and ``dk != dv``; the functions that
moved from ``models/kimi_linear.py`` are the ones Kimi-Linear runs; and
``ops/kda.py``'s kernel for a state whose heads are joined along the lanes, in
Pallas' TPU interpreter at a toy ``dk != dv`` that fills no whole lane, against
``kda_update``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import kimi_linear as kl
from ray_tpu.ops import delta_rule, kda

F32 = jnp.float32


def _rel(have, want):
    return float(np.abs(np.asarray(have) - np.asarray(want)).max() / np.abs(np.asarray(want)).max())


def _unit(a):
    return a / np.linalg.norm(a, axis=-1, keepdims=True)


def _window(seed, B, T, H, dk, dv, beta_max=2.0, fastest=2.0, rho=0.0):
    """``(q, k, v, g, beta)`` of ``T`` positions as a Gated DeltaNet mixer hands
    them over: q and k normalised a head, ONE gate a head ``g <= 0``, ``beta``
    in (0, ``beta_max``). ``rho``: the common part of a head's keys."""
    rng = np.random.default_rng(seed)
    common = _unit(rng.standard_normal((1, 1, H, dk)))
    k = _unit(rho * common + np.sqrt(1 - rho ** 2) * _unit(rng.standard_normal((B, T, H, dk))))
    q = _unit(rng.standard_normal((B, T, H, dk))) * dk ** -0.5
    v = rng.standard_normal((B, T, H, dv))
    g = -rng.uniform(1e-3, fastest, (B, T, H))
    beta = rng.uniform(0.0, beta_max, (B, T, H))
    return tuple(jnp.asarray(a, F32) for a in (q, k, v, g, beta))


def _a_position_at_a_time(S, q, k, v, g, beta):
    outs = []
    for t in range(q.shape[1]):
        S, o = delta_rule.kda_update(S, q[:, t], k[:, t], v[:, t], g[:, t, :, None], beta[:, t])
        outs.append(o)
    return S, jnp.stack(outs, axis=1)


# -- the chunked form for a gate a head ----------------------------------------------------------

@pytest.mark.parametrize("chunk", [8, 16, 48])
@pytest.mark.parametrize("carried", [False, True], ids=["fresh", "carried"])
@pytest.mark.parametrize("fastest", [2.0, 30.0], ids=["slow", "fast"])
def test_the_scalar_gate_chunked_form_is_the_recurrence(chunk, carried, fastest):
    """Sub-chunks of 8, 16 and the whole window of 48, from zeros (a fresh slot)
    and from a state that is not (a carried one), ``beta`` up to 2, a state of 8
    x 16 a head (``dv = 2 dk``); ``fast``: decays of e^-30 a position, under
    which a form that divides by the running decay overflows."""
    B, T, H, dk, dv = 2, 48, 3, 8, 16
    window = _window(chunk + carried, B, T, H, dk, dv, fastest=fastest)
    S0 = jnp.asarray(np.random.default_rng(5).standard_normal((B, H, dk, dv)), F32) * carried
    S, o = _a_position_at_a_time(S0, *window)
    S_c, o_c = delta_rule.gdn_chunked(S0, *window, chunk)
    assert bool(jnp.all(jnp.isfinite(o_c)))
    assert _rel(o_c, o) < 1e-5 and _rel(S_c, S) < 1e-5


@pytest.mark.parametrize("chunk", [8, 16])
def test_the_scalar_gate_form_is_kda_chunked_at_a_gate_broadcast_over_the_channels(chunk):
    """One number a head is the same number in every channel: the two chunked
    forms agree, where the gate a channel takes ``2 c x c x dk`` exponentials
    and a reduction on the vector unit and the gate a head ``c x c`` and two
    matmuls."""
    B, T, H, dk, dv = 2, 32, 2, 8, 16
    q, k, v, g, beta = _window(3, B, T, H, dk, dv)
    S0 = jnp.asarray(np.random.default_rng(6).standard_normal((B, H, dk, dv)), F32)
    S_h, o_h = delta_rule.gdn_chunked(S0, q, k, v, g, beta, chunk)
    S_c, o_c = delta_rule.kda_chunked(S0, q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta, chunk)
    assert _rel(o_h, o_c) < 1e-5 and _rel(S_h, S_c) < 1e-5


@pytest.mark.parametrize("rho, betas, tol", [
    (0.0, (1.8, 2.0), 1e-5), (0.8, (0.0, 2.0), 1e-4), (0.95, (0.0, 2.0), 1e-3), (0.8, (1.8, 2.0), 1e-2),
], ids=["apart.near-two", "alike.any", "very-alike.any", "alike.near-two"])
def test_how_far_the_scalar_gate_form_holds_with_keys_alike_and_beta_up_to_two(rho, betas, tol):
    """``beta`` up to 2 makes ``(I + beta * A^kk)^-1`` larger than any
    Kimi-Linear sees (KDA's ``beta`` is a sigmoid): at 2 the transition ``I - 2 k
    k^T`` is a reflection and contracts nothing. At the published widths 96 x
    192, a sub-chunk of 64, a slow decay (e^-0.001 a position), against the
    float32 recurrence a position (itself 1e-6 from float64): keys apart hold to
    1e-6 at any ``beta``; keys with a common part (pairwise cosine ``rho^2`` =
    0.64, 0.9) and ``beta`` anywhere in (0, 2), what ``2 sigmoid`` gives, hold to
    1.6e-5 and 1.3e-4. EVERY position's ``beta`` in (1.8, 2) with keys alike is
    where the float32 WY form ends: 1.3e-3 at cosine 0.64 (3e-2 at 0.9), the
    same with sub-chunks of 16, whose inverse is the exact product formula: it
    is the conditioning of ``I + beta * A^kk`` itself, which every chunked
    implementation of the published algorithm shares, not the inverse by blocks."""
    B, T, H, dk, dv = 1, 128, 2, 96, 192
    q, k, v, _, _ = _window(0, B, T, H, dk, dv, rho=rho)
    g = jnp.full((B, T, H), -1e-3, F32)
    beta = jnp.asarray(np.random.default_rng(1).uniform(*betas, (B, T, H)), F32)
    S0 = jnp.zeros((B, H, dk, dv), F32)
    S, o = _a_position_at_a_time(S0, q, k, v, g, beta)
    S_c, o_c = delta_rule.gdn_chunked(S0, q, k, v, g, beta, 64)
    assert _rel(S_c, S) < tol and _rel(o_c, o) < tol


def test_positions_with_beta_and_gate_zero_leave_the_state_where_it_was():
    """What a window's padding is handed: nothing of the state moves, so a
    chunk size that does not divide the window is padded with such positions."""
    B, T, H, dk, dv = 1, 20, 2, 8, 16
    q, k, v, g, beta = _window(9, B, T, H, dk, dv)
    S0 = jnp.asarray(np.random.default_rng(2).standard_normal((B, H, dk, dv)), F32)
    S, o = _a_position_at_a_time(S0, q, k, v, g, beta)
    pad = lambda a: jnp.pad(a, ((0, 0), (0, 4)) + ((0, 0),) * (a.ndim - 2))  # noqa: E731
    S_c, o_c = delta_rule.gdn_chunked(S0, *(pad(a) for a in (q, k, v, g, beta)), 8)
    assert _rel(S_c, S) < 1e-5 and _rel(o_c[:, :T], o) < 1e-5


# -- the move ------------------------------------------------------------------------------------

def test_kimi_linear_runs_the_recurrence_of_this_module():
    """MOVED, not copied: the names ``models/kimi_linear.py`` keeps are these objects."""
    for name in ("kda_update", "kda_chunked", "_unit_lower_inverse", "_l2_norm"):
        assert getattr(kl, name) is getattr(delta_rule, name)
    import inspect

    source = inspect.getsource(kl)
    assert "def kda_chunked" not in source and "def _unit_lower_inverse" not in source


def test_a_decode_batch_seen_from_the_pool():
    slots, real = jnp.asarray([3, 0, 1, 0]), jnp.asarray([True, False, True, False])
    row_of, held = delta_rule.rows_of_slots(slots, real, 5)
    assert held.tolist() == [False, True, False, True, False] and row_of[1] == 2 and row_of[3] == 0


# -- ops/kda.py over a state whose heads are joined along the lanes ------------------------------

LAYERS, LAYER, SLOTS = 3, 1, 5
IDLE, FRESH = (0, 2), 3


def _joined(S):
    S = jnp.swapaxes(S, -3, -2)
    return S.reshape(*S.shape[:-2], -1)


def _apart(S, H):
    return jnp.swapaxes(S.reshape(*S.shape[:-1], H, -1), -3, -2)


def _steps(seed, steps, H, dk, dv):
    q, k, v, g, beta = (np.array(a) for a in _window(seed, steps, SLOTS, H, dk, dv))
    g[:, IDLE], beta[:, IDLE] = 0.0, 0.0
    return tuple(jnp.asarray(a) for a in (q, k, v, g, beta))


@pytest.mark.parametrize("H, dk, dv, hb", [(4, 8, 192, None), (4, 8, 192, 2), (6, 24, 192, None), (4, 16, 64, None)],
                         ids=["4x8x192", "4x8x192.pairs", "6x24x192", "4x16x64"])
def test_the_joined_kernel_is_kda_update_on_the_layers_slab_and_touches_nothing_else(H, dk, dv, hb):
    """A state of ``dk != dv`` whose ``dv`` is one and a half lane tiles (192:
    a PAIR of heads is 3 whole tiles) or half a tile (64: a pair is one), heads
    joined along the lanes as the pool of ``models/olmo_hybrid.py`` keeps them,
    24 steps in a row on the middle layer's slab: slot 0 is the null slot, slot
    2 is held by nobody, slot 3's sequence starts here (NaN lies there)."""
    steps = 24
    rng = np.random.default_rng(H + dk)
    pool = rng.standard_normal((LAYERS, SLOTS, H, dk, dv)).astype(np.float32)
    pool[:, FRESH] = np.nan
    pool = _joined(jnp.asarray(pool))
    was = np.asarray(pool).view(np.uint32)
    window = _steps(1, steps, H, dk, dv)
    S = _apart(pool[LAYER], H)
    for t in range(steps):
        q, k, v, g, beta = (a[t] for a in window)
        fresh = jnp.zeros((SLOTS,), bool).at[FRESH].set(t == 0)
        pool, o = kda.update(pool, LAYER, q, k, v, g, beta, fresh, head_block=hb)
        S, o_want = delta_rule.kda_update(jnp.where(fresh[:, None, None, None], 0.0, S), q, k, v, g[..., None], beta)
        assert np.isfinite(np.asarray(o)).all() and _rel(o, o_want) < 4e-6, t
    assert _rel(pool[LAYER], _joined(S)) < 4e-6
    have = np.asarray(pool).view(np.uint32)
    for slot in IDLE:  # a slot nobody holds and the null slot come back bit for bit
        assert (have[LAYER, slot] == was[LAYER, slot]).all()
    for layer in (0, 2):  # and so do the other layers' slabs
        assert (have[layer] == was[layer]).all()


def test_which_states_the_kernel_serves():
    """Two forms: whole lanes a head (KDA's), and heads joined along the lanes
    with ``heads`` said (Gated DeltaNet's 96 x 192: 30 heads are 45 whole
    tiles); a 5-d pool of 96 x 192 (192 lanes stored as 256) keeps ``kda_update``."""
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, F32)  # noqa: E731
    assert kda.kernel_serves(f32(12, 65, 96, 5760), "tpu", heads=30)
    assert kda.kernel_serves(f32(20, 65, 32, 128, 128), "tpu")
    assert not kda.kernel_serves(f32(12, 65, 96, 5760), "cpu", heads=30)
    assert not kda.kernel_serves(f32(12, 65, 96, 5760), "tpu")  # nobody said the heads
    assert not kda.kernel_serves(f32(12, 65, 30, 96, 192), "tpu")
    assert not kda.kernel_serves(f32(5, 5, 8, 48), "tpu", heads=3)  # the toy: three heads of 16 are no whole lane
    assert not kda.kernel_serves(jax.ShapeDtypeStruct((12, 65, 96, 5760), jnp.bfloat16), "tpu", heads=30)
    assert [kda._joined_block(*a) for a in ((30, 96, 192), (4, 8, 192), (3, 8, 16), (32, 128, 128), (7, 96, 192))] \
        == [10, 4, 0, 16, 0]
    with pytest.raises(ValueError, match="does not serve"):
        kda.update(jnp.zeros((1, 2, 8, 4 * 192)), 0, *(jnp.zeros(s) for s in ((2, 4, 8), (2, 4, 8), (2, 4, 192), (2, 4), (2, 4))),
                   jnp.zeros((2,), bool), head_block=3)


# -- the move and the second form change no program that was there -------------------------------

#: sha256 (first 16) of the lowered texts AT THE PARENT of PR 64 (``tests/tools/lowered_text.py --ref
#: <parent>`` read them the same in both trees): Kimi-Linear's warmed programs at its toy sizes, for a
#: TPU and for the CPU
KIMI_TOY = {
    "tpu paged_prefill_step[16]": "46ee33b5f11f405c", "tpu paged_prefill_step[32]": "5efeda9bf1dc8411",
    "tpu paged_decode_step[4x128]": "7bd8db8127379a79", "cpu paged_prefill_step[16]": "4c82b3399ed62273",
    "cpu paged_prefill_step[32]": "d086cb2a611a0043", "cpu paged_decode_step[4x128]": "f257f00847819e99",
}
#: and at the configuration's own sizes for a TPU: what ``kda-reason-offline`` warms on the chip
KIMI_V5E = {
    "tpu paged_prefill_step[256]": "6b8d7eb50777d13a", "tpu paged_prefill_step[1024]": "e6f9b35c0c04cee0",
    "tpu paged_decode_step[64x8192]": "b31c91bb31c538f3",
}
#: Olmo-Hybrid's warmed programs since PR 65: the DECODE rows are the parent's (PR 64's: the tool read them
#: the same in both trees); the prefill rows are new with it (the chunk's recurrence through
#: ``ops/gdn_chunk.py`` on a TPU at the published widths, a slot's rows by one dynamic slice everywhere, the
#: chunk's large values behind barriers on a TPU), and since PR 66 a chunk's K and V reach the cache as whole
#: blocks (``paged_kv.write_blocks``), which changed the six prefill rows and no decode row
OLMO_TOY = {
    "tpu paged_prefill_step[16]": "45fc650983d300e0", "tpu paged_prefill_step[32]": "6e823f9ca62c040e",
    "tpu paged_decode_step[4x128]": "cd13d270acb8deef", "cpu paged_prefill_step[16]": "c498c5e47d3ffb50",
    "cpu paged_prefill_step[32]": "ef0bfcacf0de8c6a", "cpu paged_decode_step[4x128]": "24238c9a3d92dd0f",
}
OLMO_V5E = {
    "tpu paged_prefill_step[256]": "fc57a92a459ca8bd", "tpu paged_prefill_step[1024]": "9a5e5c69f66477fa",
    "tpu paged_decode_step[64x4096]": "9efe54251bd40bc3",
}
#: ``ops/kda.py``'s call over Kimi-Linear's pool, 65 slots x 32 heads of 128 x 128, lowered for a TPU
KDA_128 = "ce43cf9ae5c80c1f269c82f3463215a1cf4c2da2e8f6340bd8b8e6a385cb12fc"


def _tool():
    import importlib.util
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location("lowered_text", os.path.join(repo, "tests", "tools", "lowered_text.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return repo, tool


@pytest.mark.parametrize("config, toy, want", [
    ("kimi-linear-48b-a3b-ep16", True, KIMI_TOY), ("kimi-linear-48b-a3b-ep16", False, KIMI_V5E),
    ("olmo-hybrid-7b-16l", True, OLMO_TOY), ("olmo-hybrid-7b-16l", False, OLMO_V5E),
], ids=["toy", "published_for_a_tpu", "olmo_hybrid.toy", "olmo_hybrid.published_for_a_tpu"])
def test_the_delta_rule_models_warmed_programs_keep_the_lowered_text_they_had(config, toy, want):
    """The recurrence MOVED and the chunked form's tail became a function two
    forms share: the programs Kimi-Linear warms are, text for text, what they
    were (the operations are traced in the order they were). Beside a kernel
    for Gated DeltaNet's chunk (PR 65) they still are, and so is Olmo-Hybrid's
    decode program; its prefill programs are pinned as PR 66 left them (the
    chunk's K/V written by blocks)."""
    repo, tool = _tool()
    rows = tool.config_hashes(repo, config, ("tpu", "cpu") if toy else ("tpu",), toy=toy)
    have = {key[len(config) + 1:]: digest[:16] for key, digest in rows.items()}
    assert {label: have[label] for label in want} == want


def test_the_kernel_at_128_x_128_keeps_the_lowered_text_it_had():
    """A second form of the state beside it (heads joined along the lanes)
    changed nothing of the call over a pool of whole-lane heads."""
    import hashlib

    _, tool = _tool()
    f32 = lambda *s: jax.ShapeDtypeStruct(s, F32)  # noqa: E731
    n, slots, H, d = 20, 65, 32, 128
    with tool.described("tpu"):
        text = jax.jit(
            lambda pool, layer, q, k, v, g, beta, fresh: kda.update(pool, layer, q, k, v, g, beta, fresh, interpret=False),
            donate_argnums=0,
        ).trace(
            f32(n, slots, H, d, d), jax.ShapeDtypeStruct((), jnp.int32), f32(slots, H, d), f32(slots, H, d),
            f32(slots, H, d), f32(slots, H, d), f32(slots, H), jax.ShapeDtypeStruct((slots,), jnp.bool_),
        ).lower(lowering_platforms=("tpu",)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == KDA_128
