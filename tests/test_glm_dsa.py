"""``models/glm_dsa.py`` (GLM-5's block: DeepSeek-V3's with a learned sparse
selection inside every attention and a second cached row a token) against the
plain reference of its family, ``perfbench/families/glm_moe_dsa/reference.py``,
on the CPU at a small size: float32 against float32, seeded weights, contexts on
both sides of a toy ``index_topk``. And the model on the engine's normal path:
the drafter's stream against plain decode, the counters of the selection.

The decode / verify window's way on a TPU (both paged kernels, the selection a
mask: ``latent.sparse_paged_serves``) is reached through the ``tiled`` fixture:
the toy at widths of whole tiles, the predicate answered as a TPU answers it,
the kernels in Pallas' interpreter."""

import dataclasses
import math
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

import glm_dsa_controls as controls  # noqa: E402
import rehearsal  # noqa: E402
from perfbench import families  # noqa: E402
from perfbench.families.glm_moe_dsa import reference  # noqa: E402
from ray_tpu.inference import EngineConfig  # noqa: E402
from ray_tpu.inference.engine import InferenceEngine  # noqa: E402
from ray_tpu.models import deepseek_v3 as dsv3, glm_dsa, latent  # noqa: E402
from ray_tpu.models.interface import model_of  # noqa: E402
from ray_tpu.ops import moe as moe_ops  # noqa: E402

CONFIG = "glm-5-744b-a40b-ep16"
TOL = 2e-4
BS = 8
MODEL = glm_dsa.MODEL


@pytest.fixture(scope="module")
def model():
    return rehearsal.tiny_config(CONFIG)


@pytest.fixture(scope="module")
def cfg(model):
    return families.of(model).model_config(model, max_seq_len=model["max_position_embeddings"])


@pytest.fixture(scope="module")
def params(cfg):
    return MODEL.init_params(cfg, jax.random.PRNGKey(5))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(11).integers(1, 256, size=(2, 72)).astype(np.int32)


@pytest.fixture(scope="module")
def tiled(model):
    """The toy at widths the paged kernels serve (a latent row of 128 + 64:
    two a stored row of 384 lanes; an index key of 128; 4 indexer heads; blocks
    of 16), at which a window of two ABSORBS and a chunk of 40 expands, as the
    published widths have it: ``(model, cfg, params, block_size)``."""
    wide = dict(model, kv_lora_rank=128, qk_rope_head_dim=64, qk_head_dim=80, head_dim=64,
                index_head_dim=128, index_n_heads=4)
    cfg = families.of(wide).model_config(wide, max_seq_len=wide["max_position_embeddings"])
    assert latent.absorbs(cfg, 2) and not latent.absorbs(cfg, 40)
    cache = MODEL.cache_layout(cfg, 16).init(2)
    assert latent.sparse_paged_serves(cfg, 2, cache, backend="tpu")
    return wide, cfg, MODEL.init_params(cfg, jax.random.PRNGKey(5)), 16


def _as_on_a_tpu(monkeypatch):
    """``latent.sparse_paged_serves`` answers what it observes of the widths
    and the cache, for a TPU whoever asks."""
    asks = latent.sparse_paged_serves
    monkeypatch.setattr(latent, "sparse_paged_serves", lambda cfg, window, cache, backend=None: asks(cfg, window, cache, "tpu"))


def _doors(request, monkeypatch, door, by_token):
    """``(model, cfg, params, block_size)`` of a step test's case: the toy as
    it is (``by_token``: what the CPU runs), or ``tiled`` with the window's
    predicate answered as on a TPU and every kernel call counted."""
    if door == "by_token":
        return (*by_token, BS), None
    calls = []
    _as_on_a_tpu(monkeypatch)
    real = latent.index_paged.index_scores
    monkeypatch.setattr(latent.index_paged, "index_scores", lambda *a, **kw: calls.append(a[0].shape) or real(*a, **kw))
    return request.getfixturevalue("tiled"), calls


def _rel(have, want):
    return float(np.max(np.abs(np.asarray(have) - np.asarray(want))) / np.max(np.abs(np.asarray(want))))


def test_forward_matches_the_reference_and_the_counts(model, cfg, params, tokens):
    """Positions under the toy ``index_topk`` (24: everything is chosen) and
    well past it (two of three positions left out at 71)."""
    assert cfg.index_topk == 24 and model_of(cfg) is MODEL
    full = np.asarray(jax.jit(lambda p, t: MODEL.forward(cfg, p, t))(params, jnp.asarray(tokens)))
    picks = [(0, 71), (0, 30), (1, 3), (1, 23), (1, 24), (1, 50)]
    for (i, t), want in zip(picks, reference.logits_at(model, params, tokens, picks)):
        assert _rel(full[i, t], want) < TOL, (i, t)
    fam = families.of(model)
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert fam.param_count(model) == MODEL.param_count(cfg) == n
    assert fam.counts.mtp_params(model) == sum(a.size for a in jax.tree_util.tree_leaves(params["mtp"]))
    per_layer = sum(params["dense"][k][0].size for k in params["dense"] if k.startswith("idx_"))
    assert fam.counts.indexer_params(model) == per_layer
    layout = MODEL.cache_layout(cfg, BS)
    assert fam.kv_bytes_per_token(model, 4) == layout.bytes_per_token == (cfg.n_layers + 1) * (24 + 16) * 4
    assert fam.index_bytes_per_token(model, 4) == (cfg.n_layers + 1) * 16 * 4
    drafter = MODEL.drafter(cfg)
    assert (drafter.kind, drafter.window, drafter.cache_layers) == ("mtp", 2, 1)
    assert MODEL.selection(cfg) == 24 and dsv3.MODEL.selection(dsv3.DeepseekV3Config.tiny()) == 0


def test_a_selection_as_wide_as_the_sequence_is_the_dense_model(cfg, params, tokens):
    """``index_topk`` >= the sequence: every position is chosen, and the logits
    are those of the same weights without an indexer (``deepseek_v3``'s model);
    at the toy 24 they are another model's past position 24 and the same before."""
    t = jnp.asarray(tokens[:1])
    wide = np.asarray(MODEL.forward(dataclasses.replace(cfg, index_topk=128), params, t))
    fields = {f: getattr(cfg, f) for f in dsv3.DeepseekV3Config.__dataclass_fields__}
    dense = np.asarray(dsv3.MODEL.forward(dsv3.DeepseekV3Config(**fields), params, t))
    assert _rel(wide, dense) < 1e-6
    sparse = np.asarray(MODEL.forward(cfg, params, t))
    assert _rel(sparse[0, :24], dense[0, :24]) < 1e-6 and _rel(sparse[0, 40:], dense[0, 40:]) > 50 * TOL


def _prefill(cfg, params, cache, row, table, chunks, bucket=40):
    step = jax.jit(lambda p, c, *a: MODEL.paged_prefill_step(cfg, p, c, *a), donate_argnums=(1,))
    start, n = 0, sum(chunks)
    for c in chunks:
        chunk = np.zeros(bucket, np.int32)
        chunk[:c] = row[start : start + c]
        follows = np.int32(row[start + c] if start + c < n else -1)
        cache, logits, _ = step(params, cache, chunk, table, np.int32(start), np.int32(c), follows)
        start += c
    return cache, np.asarray(logits)


@pytest.mark.parametrize(
    "chunks, door",
    [((12,), "by_token"), ((37,), "by_token"), ((13, 24), "by_token"), ((16, 16, 16, 5), "by_token"), ((40, 19), "by_token"),
     ((12,), "paged_kernels"), ((40, 19), "paged_kernels")],
    ids=lambda v: v if isinstance(v, str) else "+".join(map(str, v)),
)
def test_chunked_prefill_then_steps_match_the_reference_main_logits_and_the_modules(
    request, monkeypatch, model, cfg, params, tokens, chunks, door,
):
    """Chunks whose edges split a block of 8 and straddle ``index_topk`` (24),
    then the step of a slot without a draft and windows of two with the
    sequence's own next token as the draft, through BOTH cached rows: the main
    model's logits at both rows of each window and the module's through ITS
    rows, against the reference's full forward pass. A prompt of 12 stays under
    ``index_topk`` through its steps; the others run past it. Slot 1 of a
    bucket of 4; the others pad. ``paged_kernels``: the windows through the two
    paged kernels (a window's scores from the slot's live blocks, its
    attention under the selection as a mask), the chunks as the CPU runs them."""
    (model, cfg, params, bs), calls = _doors(request, monkeypatch, door, (model, cfg, params))
    n = sum(chunks)
    table = np.zeros(128 // bs, np.int32)
    table[: 80 // bs] = np.arange(1, 80 // bs + 1)
    cache = MODEL.cache_layout(cfg, bs).init(16)
    assert cache["latent"].shape[0] == cache["index"].shape[0] == cfg.n_layers + 1
    cache, got_prefill = _prefill(cfg, params, cache, tokens[0], table, chunks)
    drafter = MODEL.drafter(cfg)
    verify = jax.jit(lambda p, c, *a: drafter.verify(cfg, p, c, *a), donate_argnums=(1,))
    draft = jax.jit(lambda p, c, *a: drafter.draft(cfg, p, c, *a), donate_argnums=(1,))
    tables = np.zeros((4, len(table)), np.int32)
    tables[1] = table
    main, module = [(n - 1, got_prefill)], []
    at, first = n - 1, True
    for _ in range(3):
        window, ctx, true = np.zeros((4, 2), np.int32), np.zeros(4, np.int32), np.zeros(4, np.int32)
        window[1], ctx[1], true[1] = tokens[0, at : at + 2], at, 2
        cache, logits, hidden, counters = verify(params, cache, window, tables, ctx, true)
        assert int(counters["load"].sum()) == 2 * cfg.moe_top_k * cfg.n_moe_layers  # two real rows
        main += [(at, np.asarray(logits)[1, 0]), (at + 1, np.asarray(logits)[1, 1])]
        follows = np.zeros((4, 2), np.int32)
        follows[1] = tokens[0, at + 1 : at + 3]
        if first:  # the row that waited, alone, then both
            one = true.copy()
            one[1] = 1
            cache, after, _ = draft(params, cache, hidden, follows, tables, ctx, one)
            module.append((at, np.asarray(after)[1]))
        cache, after, _ = draft(params, cache, hidden, follows, tables, ctx, true)
        module.append((at + 1, np.asarray(after)[1]))
        at, first = at + (1 if first else 2), False
    want_main, want_module = reference.both_logits_at(
        model, params, tokens, [(0, p) for p, _ in main], [(0, p) for p, _ in module])
    for (p, have), want in zip(main, want_main):
        assert _rel(have, want) < TOL, ("main", p)
    for (p, have), want in zip(module, want_module):
        assert _rel(have, want) < TOL, ("mtp", p)
    if calls is not None:
        # a kernel a layer body of each traced program: 1 dense + the scanned expert layers' in ``verify``, the
        # module's in ``draft``, each over the bucket's 4 slots x 2
        assert len(calls) == 3 and set(calls) == {(4, 2, cfg.index_n_heads, cfg.index_head_dim)}


def test_a_chunk_takes_the_first_rung_that_holds_it_and_every_rung_agrees(cfg, params, tokens):
    """At a table of 192 positions the chunk's three parts run at rungs of 48
    (``2 x index_topk``): 48, 96, 144, 192. A chunk at each rung, and the same
    chunk forced onto the table's full width, leave the same logits and rows."""
    long = dataclasses.replace(cfg, max_seq_len=192)
    layout = MODEL.cache_layout(long, BS)
    assert latent.index_rungs(long, 192, BS) == (48, 96, 144, 192)
    assert MODEL.gather_rungs(long, 40, layout.init(2)) == (48, 96, 144, 192)
    assert latent.index_rungs(long, 200, BS) == (200,) and latent.index_rungs(cfg, 128, BS) == (128,)
    row = np.random.default_rng(3).integers(1, 256, size=160).astype(np.int32)
    table = np.zeros(24, np.int32)
    table[:20] = np.arange(1, 21)

    def run(width_cfg):
        cache = MODEL.cache_layout(width_cfg, BS).init(24)
        cache, logits = _prefill(width_cfg, params, cache, row, table, (40, 40, 40, 30))
        return np.asarray(logits), {k: np.asarray(v) for k, v in cache.items()}

    logits, cache = run(long)
    # one rung: the table whole (a width that is no whole number of rungs)
    whole = dataclasses.replace(long, index_topk=24, max_seq_len=192)
    orig = latent.index_rungs
    try:
        latent.index_rungs = lambda cfg, keys, bs: (keys,)
        logits_whole, cache_whole = run(whole)
    finally:
        latent.index_rungs = orig
    assert _rel(logits, logits_whole) < 1e-6
    for name in cache:
        np.testing.assert_allclose(cache[name][:, 1:20], cache_whole[name][:, 1:20], atol=1e-6)


@pytest.fixture(scope="module")
def expanding(cfg):
    """The toy at widths in GLM-5's ratio (latent 32, key 12 + 4, value 16),
    at which a chunk of 40 EXPANDS as the published chunk of 1024 does (at the
    toy's own widths every window absorbs and a chunk takes the decode
    window's way), over a table of 192 positions: rungs of 48."""
    wide = dataclasses.replace(
        cfg, kv_lora_rank=32, qk_nope_head_dim=12, qk_rope_head_dim=4, v_head_dim=16, max_seq_len=192
    )
    assert not latent.absorbs(wide, 40) and latent.absorbs(wide, 2)
    assert latent.index_rungs(wide, 192, BS) == (48, 96, 144, 192)
    return wide, MODEL.init_params(wide, jax.random.PRNGKey(7))


def _rung_prefill(wide, params, chunks):
    row = np.random.default_rng(3).integers(1, 256, size=160).astype(np.int32)
    table = np.zeros(24, np.int32)
    table[:20] = np.arange(1, 21)
    cache, logits = _prefill(wide, params, MODEL.cache_layout(wide, BS).init(24), row, table, chunks)
    return row, logits, {k: np.asarray(v) for k, v in cache.items()}


@pytest.mark.parametrize("rung", [0, 1, 2, 3], ids=["rung_48", "rung_96", "rung_144", "rung_192"])
def test_a_chunk_through_the_selecting_kernel_is_the_masked_softmax_at_every_rung(monkeypatch, expanding, rung):
    """A prompt of 150 in chunks of 40, 40, 40, 30 (the last padded), each
    at its own rung, two ways: the materialised softmax under the mask (what
    the CPU runs) and the kernel's door (``latent.selected_serves`` answered
    as a TPU answers it at GLM-5's widths; the kernel in Pallas' interpreter,
    key tiles of 16, ONE instance behind the switch at the table's width).
    The same logits after the last chunk and the same cache rows; and the
    fallback's own last logits are the full forward pass's."""
    wide, params = expanding
    chunks = (40, 40, 40, 30)
    row, want, want_cache = _rung_prefill(wide, params, chunks[: rung + 1])
    if rung == 3:
        full = np.asarray(MODEL.forward(wide, params, row[None, :150]))[0, 149]
        assert _rel(want, full) < TOL
    calls = []
    real = latent.attend_selected
    monkeypatch.setattr(latent, "selected_serves", lambda cfg, window, cache, keys=None, backend=None: not latent.absorbs(cfg, window))
    monkeypatch.setattr(latent.latent_flash, "_KEY_TILE", 16)
    monkeypatch.setattr(latent, "attend_selected", lambda *a, **kw: calls.append(a[4].shape) or real(*a, **kw))
    _, have, have_cache = _rung_prefill(wide, params, chunks[: rung + 1])
    # one kernel a layer body, at the table's width whatever the rung: 1 dense + 2 expert layers + the MTP module's
    assert calls and set(calls) == {(192, wide.latent_width)}
    assert _rel(have, want) < 1e-5
    n = sum(chunks[: rung + 1])  # the rows of the real positions: a padded query's row is nobody's on either way
    for name in want_cache:
        rows = lambda c: c[name][:, 1:21].reshape(c[name].shape[0], 20 * BS, -1)[:, :n]  # noqa: E731
        np.testing.assert_allclose(rows(have_cache), rows(want_cache), atol=1e-5)


@pytest.mark.parametrize("door", ["by_token", "paged_kernels"])
def test_the_one_program_step_equals_its_two_program_form(request, monkeypatch, model, cfg, params, tokens, door):
    """``paged_mtp_step`` on a slot with an accepted draft, one with a wrong
    one, one without and a padding slot, at a context past ``index_topk``,
    against verify + argmax + draft a slot at a time: the tokens, what was
    accepted, the next drafts. ``paged_kernels``: the bucket's windows of two
    through the paged kernels against the lone slot's (2 x 4 heads are whole
    sublanes; a window of one is not, and keeps the by-token way)."""
    (_, cfg, params, bs), calls = _doors(request, monkeypatch, door, (model, cfg, params))
    n = 45
    drafter = MODEL.drafter(cfg)
    verify = jax.jit(lambda p, c, *a: drafter.verify(cfg, p, c, *a))
    draft = jax.jit(lambda p, c, *a: drafter.draft(cfg, p, c, *a))
    step = jax.jit(lambda p, c, *a: drafter.step(cfg, p, c, *a))
    base = MODEL.cache_layout(cfg, bs).init(40)
    tables = np.zeros((4, 128 // bs), np.int32)
    for slot in range(3):
        tables[slot, : 64 // bs] = np.arange(1, 64 // bs + 1) + 64 // bs * slot
        base, _ = _prefill(cfg, params, base, tokens[0], tables[slot], (40, 5))
    one = lambda v: np.asarray([v], np.int32)  # noqa: E731

    def plain(cache, slot, last, ctx):
        cache, lg, hid, _ = verify(params, cache, one([last, 0]), tables[slot][None], one(ctx), one(1))
        tok = int(np.argmax(np.asarray(lg)[0, 0]))
        cache, dl, _ = draft(params, cache, hid, one([tok, 0]), tables[slot][None], one(ctx), one(1))
        return cache, tok, int(np.argmax(np.asarray(dl)[0]))

    known2 = np.zeros((4, 2), np.int32)
    known2[:3] = tokens[0, n - 1 : n + 1]
    ctx = np.asarray([n - 1] * 3 + [0], np.int32)
    base, (new, accepted, drafts), _ = step(params, base, known2, tables, ctx, np.asarray([2, 2, 2, 0], np.int32),
                                            np.asarray([2, 2, 2, 1], np.int32))
    new, accepted = np.asarray(new), np.asarray(accepted)
    assert list(accepted) == [0, 0, 0, 0] and len({int(t) for t in new[:3, 0]}) == 1
    t1 = int(new[0, 0])
    cache_a, t2, d_after = plain(base, 0, t1, n + 1)
    _, t3, _ = plain(cache_a, 0, t2, n + 2)
    window = np.zeros((4, 2), np.int32)
    window[0], window[1], window[2] = [t1, t2], [t1, (t2 + 1) % 256], [t1, 0]
    ctx = np.asarray([n + 1] * 3 + [0], np.int32)
    _, (new, accepted, drafts), counters = step(
        params, base, window, tables, ctx, np.asarray([2, 2, 1, 0], np.int32), np.ones(4, np.int32))
    new, accepted, drafts = np.asarray(new), np.asarray(accepted), np.asarray(drafts)
    assert list(accepted) == [1, 0, 0, 0]
    assert list(new[0]) == [t2, t3] and new[1, 0] == t2 and new[2, 0] == t2
    assert drafts[1] == drafts[2] == d_after
    # main rows 2 x 5 real rows x top_k x expert layers, + the module's 1 + accepted rows a slot
    assert int(counters["load"].sum()) == cfg.moe_top_k * (5 * cfg.n_moe_layers + 4)
    assert calls is None or {c[:2] for c in calls} == {(4, 2), (1, 2)}


def test_sixteen_shares_of_held_experts_sum_to_the_uncut_layer():
    """Guide section 4's test: 64 experts over 16 ranks of 4; the routed parts
    of all shares, with the shared expert (what every chip computes alike)
    counted ONCE, add up to the uncut reference's whole layer."""
    E, k, D, Fm, T = 64, 8, 64, 32, 96
    rng = np.random.default_rng(7)
    f = lambda *s: jnp.asarray(rng.standard_normal(s) / math.sqrt(s[-2] if len(s) > 1 else 1), jnp.float32)  # noqa: E731
    whole = {"router": f(D, E), "router_bias": 0.3 * f(E), "w_gate": f(E, D, Fm), "w_up": f(E, D, Fm),
             "w_down": f(E, Fm, D), "shared_gate": f(D, Fm), "shared_up": f(D, Fm), "shared_down": f(Fm, D)}
    h = f(T, D) * math.sqrt(T)
    z = lambda lo, hi: reference._Sizes(top_k=k, scaling=2.5, normalise=True, lo=lo, hi=hi)  # noqa: E731
    uncut, _ = reference.expert_ffn(z(0, E), whole, h)
    total = np.asarray(reference.mlp(whole["shared_gate"], whole["shared_up"], whole["shared_down"], h))
    for lo in range(0, E, 4):
        share = {n: whole[n][lo : lo + 4] if n.startswith("w_") else whole[n] for n in
                 ("router", "router_bias", "w_gate", "w_up", "w_down")}
        routed, aux = moe_ops.dropless_moe_ffn(share, h, top_k=k, renormalize=True, scoring="sigmoid", scale=2.5,
                                               held=(lo, lo + 4))
        assert int(aux["load"].sum()) == T * k  # the load is over all 64, whatever is held
        total = total + np.asarray(routed)
    assert _rel(total, uncut) < 1e-5


@pytest.mark.parametrize("variant", controls.VARIANTS)
def test_each_control_reads_another_model_by_fifty_times_the_tolerance(model, cfg, params, tokens, variant):
    """The program's logits past ``index_topk`` against the reference with ONE
    thing wrong: every matrix in float8, the selection left out, the indexer
    without its relu or without its weights, the top ``index_topk`` taken a
    block late. The reference as it is agrees; each control is off by 50 x."""
    full = np.asarray(jax.jit(lambda p, t: MODEL.forward(cfg, p, t))(params, jnp.asarray(tokens)))
    picks = [(0, 71), (1, 50), (1, 60)]
    right = controls.logits_at(model, params, tokens, picks)
    wrong = controls.logits_at(model, params, tokens, picks, variant)
    assert max(_rel(full[i, t], w) for (i, t), w in zip(picks, right)) < TOL
    assert max(_rel(full[i, t], w) for (i, t), w in zip(picks, wrong)) > 50 * TOL


# -- on the engine's normal path -----------------------------------------------------------------

def _engine(cfg, params, **kw):
    base = dict(num_blocks=64, block_size=BS, prefill_buckets=(16, 32), decode_buckets=(4,), max_decode_batch=4,
                warmup=False, prefix_cache_enabled=False)
    base.update(kw)
    return InferenceEngine(cfg, params, EngineConfig(**base)).start()


def _stream(eng, prompt, n):
    return [int(t) for t in eng.generate(list(map(int, prompt)), max_new_tokens=n, temperature=0.0, seed=7)]


def test_the_drafters_stream_is_plain_decodes_and_the_selection_is_counted(cfg, params, tokens):
    """Greedy streams of two prompts (one under ``index_topk``, one past it)
    through the engine with the MTP module as drafter against the engine that
    decodes plainly; ``engine_stats()`` says what the queries chose."""
    prompts = [tokens[0, :50], tokens[1, :10]]
    plain = _engine(cfg, params)
    try:
        want = [_stream(plain, p, 12) for p in prompts]
    finally:
        plain.stop()
    spec = _engine(cfg, params, speculative_k=1, speculative_draft="mtp", speculative_adaptive=False)
    try:
        have = [_stream(spec, p, 12) for p in prompts]
        stats = spec.stats()
    finally:
        spec.stop()
    assert have == want
    sa = stats["sparse_attention"]
    assert sa["queries"] >= 50 + 10 and 0 < sa["queries_past_topk"] < sa["queries"]
    assert sa["chosen"] < sa["live"] and sa["chosen"] <= 24 * sa["queries"]
    assert stats["kv_layout"]["arrays"]["index"]["row_width"] == 16
    assert plain.stats()["sparse_attention"]["queries"] >= 60


def test_the_runner_counts_a_selection_from_its_own_lengths(cfg, params):
    from ray_tpu.inference.model_runner import PagedModelRunner

    runner = PagedModelRunner(cfg, params, num_blocks=32, block_size=BS, prefill_buckets=(16,), decode_buckets=(2,))
    runner._count_selection(0, 16)   # a first chunk: everything chosen
    assert runner.sparse_attention == {"queries": 16, "queries_past_topk": 0, "chosen": 136, "live": 136}
    runner._count_selection(16, 32)  # positions 16..23 choose all, 24..31 choose 24 of 25..32
    assert runner.sparse_attention["queries_past_topk"] == 8
    assert runner.sparse_attention["chosen"] == 136 + sum(range(17, 25)) + 8 * 24
    assert runner.sparse_attention["live"] == sum(range(1, 33))


def test_a_window_on_a_tpu_reads_its_slots_live_blocks_and_the_runner_counts_them(monkeypatch, tiled):
    """``_attention_path`` at the published widths for a described TPU: a
    decode or verify window reads each slot's live blocks through the paged
    kernels, a chunk the key tiles up to its end through the selecting flash
    kernel; off the chip, and at widths the kernels refuse, the table. And the
    runner's ``decode_width`` counts a launch on that path from the slots'
    own lengths: whole blocks of the contexts, nothing for a padding slot."""
    from ray_tpu.inference.model_runner import PagedModelRunner
    from ray_tpu.models.interface import AttentionPath

    full = glm_dsa.GlmDsaConfig(dtype=jnp.bfloat16, max_seq_len=32768)
    cache = jax.eval_shape(lambda: MODEL.cache_layout(full, 16).init(64))
    assert cache["latent"].shape[2:] == (8, 1152) and cache["index"].shape[2:] == (16, 128)
    for window in (1, 2):
        assert glm_dsa._attention_path(full, window, cache, backend="tpu") == AttentionPath("latent.sparse_paged", "blocks")
        assert glm_dsa._attention_path(full, window, cache, backend="cpu") == AttentionPath("latent.sparse", "table")
    assert glm_dsa._attention_path(full, 8, cache, backend="tpu") == AttentionPath("latent.sparse", "table")  # 512 query rows
    assert glm_dsa._attention_path(full, 1024, cache, backend="tpu") == AttentionPath("latent.sparse_flash", "live")
    odd = jax.eval_shape(lambda: MODEL.cache_layout(full, 8).init(64))  # blocks of 8: no whole tile
    assert glm_dsa._attention_path(full, 2, odd, backend="tpu") == AttentionPath("latent.sparse", "table")

    _, cfg, params, bs = tiled
    _as_on_a_tpu(monkeypatch)
    runner = PagedModelRunner(cfg, params, num_blocks=32, block_size=bs, prefill_buckets=(16,), decode_buckets=(4,),
                              verify_buckets=(2,))
    assert runner.attention_paths[2] == AttentionPath("latent.sparse_paged", "blocks")
    assert runner.attention_paths[1] == AttentionPath("latent.sparse", "table")  # 4 query rows: no whole sublanes
    runner._count_width([37, 70, 16], bucket=4, window=2)
    width = runner.max_blocks_per_seq * bs
    assert runner.decode_width == {"launches": 1, "width_tokens": width, "needed_tokens": 70,
                                   "live_tokens": 37 + 70 + 16, "gathered_tokens": (3 + 5 + 1) * bs}
    runner._count_width([37, 70, 16], bucket=4, window=1)  # the table, for every slot of the bucket
    assert runner.decode_width["gathered_tokens"] == (3 + 5 + 1) * bs + 4 * width
