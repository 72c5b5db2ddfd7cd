"""``models/olmo_hybrid.py`` (Gated DeltaNet layers with a per-sequence state
pool whose heads lie joined along the lanes, beside multi-head attention layers
without positions over a paged K/V cache of as many KV heads as query heads;
the norm on each sublayer's output; an untied head) against the plain reference
of its family, ``perfbench/families/olmo_hybrid/reference.py``, on the CPU at a
small size: float32 against float32, seeded weights. The decode step through
``ops/kda.py``'s kernel in Pallas' TPU interpreter; which way 30 KV heads
attend; and the state slots through the engine: the FOURTH kind of state in the
pool (``gdn``)."""

import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(HERE, "perfbench"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import olmo_hybrid_controls as controls  # noqa: E402
import rehearsal  # noqa: E402
from perfbench import families  # noqa: E402
from perfbench.families.olmo_hybrid import reference, server  # noqa: E402
from ray_tpu.inference import EngineConfig  # noqa: E402
from ray_tpu.inference.engine import InferenceEngine  # noqa: E402
from ray_tpu.models import olmo_hybrid as oh  # noqa: E402
from ray_tpu.models import paged_kv  # noqa: E402
from ray_tpu.models.interface import model_of  # noqa: E402
from ray_tpu.ops import kda  # noqa: E402
from ray_tpu.ops import paged_attention as PA  # noqa: E402

CONFIG = "olmo-hybrid-7b-16l"
TOL = 2e-4
BS = 8
F32 = jnp.float32


@pytest.fixture(scope="module")
def model():
    return rehearsal.tiny_config(CONFIG)


@pytest.fixture(scope="module")
def cfg(model):
    return families.of(model).model_config(model, max_seq_len=model["max_position_embeddings"])


@pytest.fixture(scope="module")
def params(cfg):
    return oh.init_params(cfg, jax.random.PRNGKey(5))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(11).integers(1, 256, size=(2, 60)).astype(np.int32)


def _rel(have, want):
    return float(np.max(np.abs(np.asarray(have) - np.asarray(want))) / np.max(np.abs(np.asarray(want))))


# -- the whole model through both pools ----------------------------------------------------------

def _steps(cfg):
    prefill = jax.jit(lambda p, c, s, *a: oh.paged_prefill_step(cfg, p, c, s, *a), donate_argnums=(1, 2))
    decode = jax.jit(lambda p, c, s, *a: oh.paged_decode_step(cfg, p, c, s, *a), donate_argnums=(1, 2))
    return prefill, decode


def _prefill(step, params, cache, state, row_tokens, table, chunks, slot, bucket=40):
    start = 0
    for c in chunks:
        chunk = np.full(bucket, 77, np.int32)  # the padding rows hold a real token: its inputs are not zero
        chunk[:c] = row_tokens[start : start + c]
        cache, state, logits = step(
            params, cache, state, chunk, table, np.int32(start), np.int32(c), np.int32(slot)
        )
        start += c
    return cache, state, np.asarray(logits)


def _kept_of(state, layer, slot):
    return np.asarray(state["gdn_state"][layer, slot]), np.asarray(state["gdn_conv"][layer, slot])


@pytest.mark.parametrize("chunks", [(37,), (36, 1), (35, 2), (34, 3), (16, 16, 5), (7, 1, 2, 3, 24), (1, 1, 1, 34)],
                         ids=lambda c: "+".join(map(str, c)))
def test_chunked_prefill_then_decode_match_the_reference(model, cfg, params, tokens, chunks):
    """Chunks of 1, 2 and 3 rows (shorter than the taps) with a padded tail,
    whose edges split a block of 8 and a sub-chunk of 8, then three decode
    steps, through the K/V cache AND the state slots (a slot that held another
    sequence's trash), against the reference's full forward pass: logits, not
    tokens; and the state and the tail the pool is left with, against the
    reference's ``S`` (heads joined along the lanes, as the pool keeps them)."""
    n = sum(chunks)
    table = np.arange(1, 9, dtype=np.int32)
    cache = oh.cache_layout(cfg, BS).init(16)
    assert cache["k"].shape == (2, 16, BS, 3, 16)  # TWO attending layers of seven, three KV heads
    state = jax.tree_util.tree_map(lambda a: a + 3.0, oh.state_layout(cfg).init(4))  # trash in every slot
    assert state["gdn_state"].shape == (5, 4, 8, 3 * 16) and state["gdn_conv"].shape == (5, 4, 3 * 96)
    prefill, decode = _steps(cfg)
    cache, state, got_prefill = _prefill(prefill, params, cache, state, tokens[0], table, chunks, slot=2)
    tables = np.zeros((4, 8), np.int32)
    tables[1] = table  # rows 0, 2 and 3 of the batch are padding
    slots = np.array([0, 2, 0, 0], np.int32)
    have = [got_prefill]
    for d in range(3):
        toks, pos = np.zeros(4, np.int32), np.zeros(4, np.int32)
        toks[1], pos[1] = tokens[0, n + d], n + d
        cache, state, got = decode(params, cache, state, toks, pos, tables, pos + 1, slots)
        have.append(np.asarray(got)[1])
    picks = [(0, n - 1 + i) for i in range(4)]
    want, kept = reference.logits_at(model, params, tokens[:1], picks, [(n + 3,)])
    for h, w in zip(have, want):
        assert _rel(h, w) < TOL
    for layer, ((S, tail),) in enumerate(kept[0]):
        have_S, have_tail = _kept_of(state, layer, 2)
        assert _rel(have_S, server.as_the_pool_lies(S)) < TOL and _rel(have_tail, tail.reshape(-1)) < TOL
    # nothing but slot 2 (and the null slot, padding's) was written
    for a in state.values():
        assert float(jnp.min(a[:, 1])) == 3.0 == float(jnp.max(a[:, 3]))


def test_two_sequences_swap_slots_and_a_fresh_slot_reads_zeros_whatever_it_held(model, cfg, params, tokens):
    """Sequence A prefills on slot 3 and B on slot 1; then each is served
    AGAIN from position 0 on the other's slot, which holds the other's state:
    a chunk at ``ctx_len`` 0 reads zeros, so the logits are those of the
    reference, and decode steps of the two together (padding between them)
    go on from their own states."""
    lens, tables = (37, 13), np.zeros((4, 8), np.int32)
    tables[0, :6], tables[2, :3] = np.arange(1, 7), np.arange(7, 10)
    cache, state = oh.cache_layout(cfg, BS).init(16), oh.state_layout(cfg).init(4)
    prefill, decode = _steps(cfg)
    for slot_of in ((3, 1), (1, 3)):  # the second round: swapped, each over the other's leavings
        for i, row in ((0, 0), (1, 2)):
            cache, state, _ = _prefill(prefill, params, cache, state, tokens[i], tables[row], (lens[i],),
                                       slot=slot_of[i])
    slots = np.array([1, 0, 3, 0], np.int32)
    have = []
    for d in range(4):
        toks, pos = np.zeros(4, np.int32), np.zeros(4, np.int32)
        toks[[0, 2]], pos[[0, 2]] = [tokens[0, 37 + d], tokens[1, 13 + d]], [37 + d, 13 + d]
        cache, state, got = decode(params, cache, state, toks, pos, tables, pos + 1, slots)
        have += [np.asarray(got)[0], np.asarray(got)[2]]
    picks = [(i, n + d) for d in range(4) for i, n in enumerate(lens)]
    for h, w in zip(have, reference.logits_at(model, params, tokens, picks)):
        assert _rel(h, w) < TOL


def test_forward_matches_the_reference_and_the_counts(model, cfg, params, tokens):
    logits = oh.forward(cfg, params, jnp.asarray(tokens))
    picks = [(i, t) for i in range(2) for t in (0, 1, 2, 31, 59)]
    want = reference.logits_at(model, params, tokens, picks)
    for (i, t), w in zip(picks, want):
        assert _rel(logits[i, t], w) < TOL
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == oh.param_count(cfg) == families.of(model).param_count(model)
    assert "lm_head" in params  # untied
    assert cfg.layer_types == ("linear_attention",) * 3 + ("full_attention",) + ("linear_attention",) * 2 + ("full_attention",)
    axes = oh.logical_axes(cfg)
    assert jax.tree_util.tree_structure(jax.tree_util.tree_map(lambda _: 0, params)) == jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda _: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))
    loss = reference.next_token_loss(model, params, tokens[:, :-1], tokens[:, 1:])
    assert abs(loss - np.log(256)) < 1.0
    # the published family's law: A in [1, 16] a head, the step size in [1e-3, 1e-1] where the projection adds nothing
    p = params["layers"][0]
    A, dt0 = np.exp(np.asarray(p["gdn_a_log"])), np.asarray(jax.nn.softplus(p["gdn_dt_bias"]))
    assert p["gdn_a_log"].shape == p["gdn_dt_bias"].shape == (cfg.gdn_heads,)  # ONE gate a head
    assert 1.0 <= A.min() and A.max() <= 16.0 and 1e-3 * 0.99 <= dt0.min() and dt0.max() <= 1e-1 * 1.01
    with pytest.raises(ValueError, match="layer_types"):
        oh.OlmoHybridConfig.tiny(n_layers=6)


def test_the_published_widths_count_what_the_catalog_says():
    """At the published keys (no array is made): a mixer of 88.75 M, a linear
    layer of 215.6 M, an attending one of 185.8 M, 7.43 B whole and 4.101 B at
    the served depth; 61,440 B a token and 27,371,520 B a sequence."""
    model = rehearsal._load("configs", f"{CONFIG}.json")
    fam = families.of(model)
    served = fam.model_config(model, max_seq_len=4096)
    assert oh.param_count(served) == fam.param_count(model) == 4_100_788_944
    assert fam.counts.gdn_params(model) == 88_750_332 and fam.counts.attn_params(model) == 58_990_080
    whole = dict(model, num_hidden_layers=32, layer_types=model["layer_types"] * 2)
    assert fam.param_count(whole) == oh.param_count(oh.OlmoHybridConfig()) == 7_430_870_688
    layout, state = oh.cache_layout(served, 16, jnp.bfloat16), oh.state_layout(served)
    assert layout.bytes_per_token == fam.kv_bytes_per_token(model) == 61_440 and layout.n_layers == 4
    assert dict(state.describe(), kind="gdn") == {"kind": "gdn", "layers": 12, "bytes_per_seq": 27_371_520}
    assert state.bytes_per_seq == fam.state_bytes_per_seq(model) == state.stored_bytes_per_seq
    assert [(n, s) for n, s, _ in state.arrays] == [("gdn_state", (96, 5760)), ("gdn_conv", (3 * 11520,))]
    assert fam.counts.gdn_update_bytes(model, 65) == 65 * 2 * 30 * 96 * 192 * 4
    assert fam.counts.paged_attn_bytes(model, 1000) == 1000 * 15_360
    with pytest.raises(SystemExit, match="served only"):
        fam.train_program()


@pytest.mark.parametrize("variant", controls.VARIANTS)
def test_every_control_reads_not_correct(model, cfg, params, tokens, variant):
    """Each wrong twin of the reference is told from the program by the
    logits of a full forward pass, float32 against float32: orders above the
    model's own reading. (``carry_dropped``: the prompt of 33 = 32 + 1 crosses a
    chunk edge, and the positions behind see the difference.)"""
    toy = dict(model, correctness={**model["correctness"], "prompt_lens": [33, 33]})
    logits = oh.forward(cfg, params, jnp.asarray(tokens))
    picks = [(i, t) for i in range(2) for t in (33, 34, 59)]
    right = reference.logits_at(model, params, tokens, picks)
    assert max(_rel(logits[i, t], w) for (i, t), w in zip(picks, right)) < TOL / 10
    want = controls.logits_at(toy, params, tokens, picks, variant)
    assert max(_rel(logits[i, t], w) for (i, t), w in zip(picks, want)) > TOL


def test_attention_has_no_position_term_and_norms_the_whole_of_q_and_k(model, cfg, params):
    """The same activations at other positions of a sequence give the same q,
    k and v: only the causal mask knows the order. A twin without the norm of
    k is told."""
    p = next(p for p in params["layers"] if "wq" in p)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((1, 12, cfg.dim)), F32)
    cache = {k: v[:1] for k, v in oh.cache_layout(cfg, BS).init(8).items()}
    table = jnp.arange(1, 7, dtype=jnp.int32)[None]
    valid = jnp.ones((1, 12), bool)
    _, at0 = oh._attention_mix(cfg, p, cache, 0, x, jnp.arange(12)[None], valid, table)
    want = reference.attention(reference.sizes(model), p, x[0])
    assert _rel(at0[0], want) < TOL < 0.05 < _rel(at0[0], controls.attention(model, p, x[0], "k_norm_left_out"))
    q, k, _ = oh._qkv(cfg, p, x)
    for a in (q, k):  # RMS 1 over the WHOLE projection, not a head
        assert abs(float(jnp.mean(a.reshape(12, -1) ** 2)) - 1.0) < 1e-3


def test_each_mixer_alone_reads_the_reference_and_tells_its_control(model, cfg, params):
    """The check's two readings of a mixer ALONE (``families/olmo_hybrid/
    server.py``) on a toy runner: the program's Gated DeltaNet mixer over three
    chunks and decode steps on a pool of its own, and its attention over a
    chunk and decode steps, against the reference's; each control of the mixer
    reads far above the model."""
    eng = _engine(cfg, params)
    try:
        runner = eng.runner
        readings = {
            "gdn": lambda v: server.gdn_alone(runner, model, 3, lambda m, p, x: controls.gdn(
                m, p, x, v, starts=(16,))),
            "attn": lambda v: server.attn_alone(runner, model, 3, lambda m, p, x: controls.attention(m, p, x, v)),
        }
        for name, variants in (
            ("gdn", ("state_bf16", "beta_without_its_factor", "gate_mean_over_heads", "carry_dropped", "weights_fp8")),
            ("attn", ("k_norm_left_out", "weights_fp8")),
        ):
            right = readings[name](None)
            assert right["finite"] and max(right["worst"].values()) < TOL / 10
            for variant in variants:
                assert max(readings[name](variant)["worst"].values()) > 2 * TOL, (name, variant)
    finally:
        eng.stop()


# -- the decode step through ops/kda.py's kernel ---------------------------------------------------

def test_decode_through_the_kernel_leaves_the_logits_and_the_pool_where_kda_update_does(monkeypatch):
    """At a toy whose heads join to whole lanes (4 heads of 8 x 64: a pair is
    one lane tile) the decode step with ``ops/kda.py::kernel_serves`` answering
    yes (the kernel then runs in Pallas' interpreter) against the same step
    through ``kda_update``: two real slots and two padding rows, three steps,
    one slot fresh at the first."""
    cfg = oh.OlmoHybridConfig.tiny(gdn_heads=4, gdn_key_dim=8, gdn_value_dim=64)
    params = oh.init_params(cfg, jax.random.PRNGKey(2))
    rng = np.random.default_rng(8)
    tables = np.zeros((4, 8), np.int32)
    tables[0, :3], tables[2, :2] = (1, 2, 3), (4, 5)
    slots = np.array([3, 0, 1, 0], np.int32)
    runs = {}
    for served in (False, True):
        monkeypatch.setattr(kda, "kernel_serves", lambda state, backend=None, heads=None, served=served: served)
        cache = oh.cache_layout(cfg, BS).init(8)
        state = jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.random.default_rng(1).standard_normal(a.shape), a.dtype), oh.state_layout(cfg).init(4))
        decode = jax.jit(lambda p, c, s, *a: oh.paged_decode_step(cfg, p, c, s, *a))
        out = []
        for d in range(3):
            toks = np.array([5 + d, 0, 9 + d, 0], np.int32)
            pos = np.array([7 + d, 0, d, 0], np.int32)  # slot 1's sequence starts at the first step
            cache, state, logits = decode(params, cache, state, toks, pos, tables, pos + 1, slots)
            out.append(np.asarray(logits)[[0, 2]])
        runs[served] = (np.stack(out), {k: np.asarray(v) for k, v in state.items()})
    assert _rel(runs[True][0], runs[False][0]) < 1e-5
    for name in ("gdn_state", "gdn_conv"):
        assert _rel(runs[True][1][name][:, 1:], runs[False][1][name][:, 1:]) < 1e-5
        assert (runs[True][1][name][:, 2] == runs[False][1][name][:, 2]).all()  # a slot nobody holds: untouched
    text = jax.jit(lambda p, c, s, *a: oh.paged_decode_step(cfg, p, c, s, *a)).lower(
        params, oh.cache_layout(cfg, BS).init(8), oh.state_layout(cfg).init(4), np.zeros(4, np.int32),
        np.zeros(4, np.int32), tables, np.ones(4, np.int32), slots).as_text()
    assert text.count("optimization_barrier") == 0  # the kernel aliases the pool in and out: no fusion to clone


# -- which way 30 KV heads attend ------------------------------------------------------------------

def test_thirty_kv_heads_under_one_query_row_each_take_the_kernels_stored_flat():
    """30 KV heads of 128 under ONE query row each: no whole tile of 8 heads, so
    the 5-d cache is refused (as a 5-d pool of 16 x 16 or 128 x 64 is) and the
    cache is stored FLAT, ``[layers, blocks, 16 x 30, 128]`` (a block 120 KB of
    K: 30 whole bf16 tiles, nothing padded), which the decode kernel and the
    chunk's flash kernel both serve: compiled and run against the gather on
    the chip (``ops/paged_attention.py::kernel_serves``, ``models/paged_kv.py::way``)."""
    cfg = oh.OlmoHybridConfig(n_layers=16, layer_types=oh._PERIOD * 4, max_seq_len=4096, dtype=jnp.bfloat16)
    layout = oh.cache_layout(cfg, 16)
    assert layout.flat_blocks and layout.block_shape((30, 128)) == (480, 128) and layout.block_bytes == 16 * 61_440
    flat = jax.ShapeDtypeStruct((4, 5001, 480, 128), jnp.bfloat16)
    five = jax.ShapeDtypeStruct((4, 5001, 16, 30, 128), jnp.bfloat16)
    said = dict(n_kv=30, head_dim=128)
    assert paged_kv.way(1, 64, 30, flat, 4096, backend="tpu", **said) == "kernel"
    assert PA.kernel_serves(1, 30, flat, "tpu", **said) and not PA.kernel_serves(1, 30, five, "tpu", **said)
    assert paged_kv.way(1, 64, 30, five, 4096, backend="tpu", **said) == "gather"
    assert paged_kv.way(1, 64, 30, flat, 4096, backend="cpu", **said) == "gather"
    for chunk in (256, 1024):
        assert paged_kv.way(chunk, 1, 30, flat, 4096, backend="tpu", **said) == "flash"
        assert paged_kv.way(chunk, 1, 30, flat, 4096, backend="cpu", **said) == "gather"
    assert paged_kv.way(16, 64, 30, flat, 4096, backend="tpu", **said) == "gather"  # 480 query rows: no short window
    assert PA.blocks_a_wave((480, 128), 16, 256) == 4  # 1920 rows a wave: 64 tokens of 30 heads
    path = oh.MODEL.attention_path
    cache = jax.eval_shape(lambda: layout.init(600))
    assert path(cfg, 1, cache, backend="tpu") == ("gdn.kernel+kv.kernel", "blocks")
    assert path(cfg, 1, cache, backend="cpu") == ("gdn.update+kv.gather", "table")
    for window in (256, 1024):
        assert path(cfg, window, cache, backend="tpu") == ("gdn.chunk_kernel+kv.flash", "live")
        assert path(cfg, window, cache, backend="cpu") == ("gdn.chunk+kv.gather", "table")
    toy = oh.OlmoHybridConfig.tiny()
    toy_cache = jax.eval_shape(lambda: oh.cache_layout(toy, 8).init(8))
    assert path(toy, 1, toy_cache, backend="tpu") == ("gdn.update+kv.gather", "table")
    assert model_of(cfg).name == "olmo_hybrid" and model_of(cfg).state_layout(cfg).kind == "gdn"


@pytest.mark.parametrize("window", [1, 2], ids=["decode", "window_of_2"])
def test_the_paged_kernel_over_thirty_heads_stored_flat_is_the_gather(window):
    """The kernel in Pallas' interpreter at 30 query heads over 30 KV heads of
    128, blocks of 16 stored flat, ragged contexts, a padding slot: every query
    head is multiplied against every row of a wave and the 29 other heads'
    columns are masked."""
    rng = np.random.default_rng(30)
    H, hd, bs, M, L = 30, 128, 16, 3, 2
    ctxs = (40, 17, 1, 0)
    B, N = len(ctxs), 1 + len(ctxs) * M
    k, v = (jnp.asarray(a) for a in rng.standard_normal((2, L, N, bs, H, hd)).astype(np.float32))
    tables, pos = np.zeros((B, M), np.int32), np.zeros((B, window), np.int32)
    shuffled = rng.permutation(np.arange(1, N))
    for b, ctx in enumerate(ctxs):
        if ctx:
            tables[b] = shuffled[b * M:(b + 1) * M]
            pos[b] = np.minimum(ctx - 1 + np.arange(window), M * bs - 1)
    q = jnp.asarray(rng.standard_normal((B, window, H, hd)).astype(np.float32))
    want = paged_kv.attend_gathered(q, k, v, 1, jnp.asarray(tables), jnp.asarray(pos), H, M * bs)
    flat = lambda a: a.reshape(L, N, bs * H, hd)  # noqa: E731
    have = PA.paged_attention(q, flat(k), flat(v), 1, jnp.asarray(tables), jnp.asarray(pos), interpret=True,
                              n_kv=H, wave_blocks=2)
    assert (np.asarray(have)[-1] == 0).all()  # the padding slot
    np.testing.assert_allclose(np.asarray(have)[:-1], np.asarray(want)[:-1], rtol=2e-5, atol=2e-5)


# -- the engine: the same server, scheduler, runner, block manager and state pool ---------------------

def _engine(cfg, params, **kw):
    fields = dict(num_blocks=40, block_size=BS, prefill_buckets=(8, 16), decode_buckets=(4,),
                  max_decode_batch=4, max_queue_depth=16)
    fields.update(kw)
    return InferenceEngine(cfg, params, EngineConfig(**fields)).start()


def _greedy(forward, params, prompt, n, width=48):
    """``n`` greedy tokens by the full forward pass (one compiled shape: the
    sequence padded behind, which a causal model does not see)."""
    seq = list(prompt)
    for _ in range(n):
        padded = np.zeros((1, width), np.int32)
        padded[0, : len(seq)] = seq
        seq.append(int(jnp.argmax(forward(params, padded)[0, len(seq) - 1])))
    return seq[len(prompt):]


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(4)
    return [list(map(int, rng.integers(1, 256, n))) for n in (5, 27, 19, 33, 21)]


def test_the_engine_serves_through_slots_and_tells_of_both_layouts(cfg, params, prompts):
    """Two slots for five requests, with NO change to the engine for this
    kind of recurrent layer: requests wait for a slot, a slot is reused after
    a finish (its next holder's first chunk starts from zeros), nothing
    leaks, the tokens are the full forward pass's, the launch spans' path
    names both mixers, and the state's description says what the pool's layout
    really holds a sequence beside what the sequence needs."""
    forward = jax.jit(lambda p, t: oh.forward(cfg, p, t))
    wanted = [_greedy(forward, params, p, 6) for p in prompts]
    eng = _engine(cfg, params, max_decode_batch=2, decode_buckets=(2,))  # a slot a running sequence
    try:
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        assert [list(eng.tokens(r)) for r in rids] == wanted
        st = eng.stats()
        assert st["kv_layout"] == {"kind": "kv", "row_width": 2 * 3 * 16, "bytes_per_token": 2 * 2 * 3 * 16 * 4}
        layout = oh.state_layout(cfg)
        needs = 5 * (3 * 8 * 16 * 4 + 3 * 96 * 4)
        assert layout.describe() == {"kind": "gdn", "layers": 5, "bytes_per_seq": needs}
        # the toy's 48 lanes of state are stored as 128 and its 288 of tail as 384
        assert st["state_layout"] == {**layout.describe(), "stored_bytes_per_seq": 5 * (8 * 128 * 4 + 384 * 4)}
        pool = st["state_pool"]
        assert pool["slots"] == 2 and pool["peak_in_use"] == 2 and pool["in_use"] == 0
        assert pool["assigned"] == pool["released"] == 5 and pool["admission_waits"] == 3
        assert st["blocks"]["used_blocks"] == 0 and st["recompiles_after_warmup"] == 0
        assert st["prefix_cache"]["enabled"] is False  # switched off: no state snapshot a block
        assert eng.runner._path_name(1) == "gdn.update+kv.gather" and eng.runner._path_name(16) == "gdn.chunk+kv.gather"
        assert eng.runner.held_experts is None
    finally:
        eng.stop()


def test_a_preempted_request_re_derives_its_state_from_position_zero(cfg, params, prompts):
    """A pool too small for two long requests at once: one is preempted
    (blocks and slot given back), re-admitted, and its tokens are those of an
    undisturbed run (its first chunk after re-admission starts from zeros)."""
    forward = jax.jit(lambda p, t: oh.forward(cfg, p, t))
    want = [_greedy(forward, params, prompts[i], 40, width=80) for i in (1, 3)]
    eng = _engine(cfg, params, num_blocks=17, max_decode_batch=2, decode_buckets=(2,))  # 16 usable blocks; 73 + 67 tokens need 19
    try:
        rids = [eng.submit(prompts[i], max_new_tokens=40) for i in (1, 3)]
        assert [list(eng.tokens(r)) for r in rids] == want
        st = eng.stats()
        assert st["scheduler"]["total_preempted"] >= 1
        assert st["state_pool"]["assigned"] == st["state_pool"]["released"] >= 3
        assert st["state_pool"]["in_use"] == 0 and st["blocks"]["used_blocks"] == 0
    finally:
        eng.stop()


@pytest.mark.parametrize("field,value,reason", [
    ("kv_transfer_enabled", True, "carry no state"),
    ("kv_tier_enabled", True, "without the state"),
    ("speculative_k", 2, "roll-back"),
])
def test_what_cannot_carry_the_state_is_refused_at_construction_with_the_reason(cfg, params, field, value, reason):
    with pytest.raises(ValueError, match=reason) as e:
        InferenceEngine(cfg, params, EngineConfig(num_blocks=40, block_size=BS, prefill_buckets=(8, 16),
                                                  decode_buckets=(4,), max_decode_batch=4, **{field: value}))
    assert field in str(e.value) and "olmo_hybrid" in str(e.value)


def test_export_and_import_and_a_missing_slot_are_refused_on_a_running_engine(cfg, params, prompts):
    eng = _engine(cfg, params)
    try:
        with pytest.raises(RuntimeError, match="per-sequence state"):
            eng.prefill_kv(prompts[1])
        with pytest.raises(ValueError, match="state slot"):
            eng.runner.prefill_chunk(prompts[0], [1] + [0] * 15, 0)  # no slot handed over
        with pytest.raises(NotImplementedError, match="roll-back"):
            model_of(cfg).paged_verify_step(cfg)
    finally:
        eng.stop()
