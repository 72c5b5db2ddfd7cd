"""``models/kimi_linear.py`` (gated delta-rule layers with a per-sequence
state pool beside latent attention layers with a paged cache, sigmoid
routing with a shared expert over a held range of experts) against the plain
reference of its family, ``perfbench/families/kimi_linear/reference.py``, on
the CPU at a small size: float32 against float32, seeded weights. And the
state slots through the manager, the scheduler and the engine."""

import dataclasses
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

import kimi_linear_controls as controls  # noqa: E402
import rehearsal  # noqa: E402
from perfbench import families  # noqa: E402
from perfbench.families.kimi_linear import reference  # noqa: E402
from ray_tpu.inference import EngineConfig  # noqa: E402
from ray_tpu.inference.engine import InferenceEngine  # noqa: E402
from ray_tpu.inference.kv_cache import PagedBlockManager  # noqa: E402
from ray_tpu.inference.scheduler import ContinuousBatchingScheduler, Request  # noqa: E402
from ray_tpu.models import kimi_linear as kl  # noqa: E402
from ray_tpu.models import latent  # noqa: E402
from ray_tpu.models.interface import model_of  # noqa: E402

CONFIG = "kimi-linear-48b-a3b-ep16"
TOL = 2e-4
BS = 8


@pytest.fixture(scope="module")
def model():
    return rehearsal.tiny_config(CONFIG)


@pytest.fixture(scope="module")
def cfg(model):
    return families.of(model).model_config(model, max_seq_len=model["max_position_embeddings"])


@pytest.fixture(scope="module")
def params(cfg):
    return kl.init_params(cfg, jax.random.PRNGKey(5))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(11).integers(1, 256, size=(2, 60)).astype(np.int32)


def _rel(have, want):
    return float(np.max(np.abs(np.asarray(have) - np.asarray(want))) / np.max(np.abs(np.asarray(want))))


# -- the whole model through both pools ----------------------------------------------------------

def _steps(cfg):
    prefill = jax.jit(lambda p, c, s, *a: kl.paged_prefill_step(cfg, p, c, s, *a), donate_argnums=(1, 2))
    decode = jax.jit(lambda p, c, s, *a: kl.paged_decode_step(cfg, p, c, s, *a), donate_argnums=(1, 2))
    return prefill, decode


def _prefill(step, params, cache, state, row_tokens, table, chunks, slot, bucket=40):
    start = 0
    for c in chunks:
        chunk = np.zeros(bucket, np.int32)
        chunk[:c] = row_tokens[start : start + c]
        cache, state, logits, _ = step(
            params, cache, state, chunk, table, np.int32(start), np.int32(c), np.int32(slot)
        )
        start += c
    return cache, state, np.asarray(logits)


@pytest.mark.parametrize("chunks", [(37,), (13, 24), (16, 16, 5), (7, 9, 11, 10), (32, 5)],
                         ids=lambda c: "+".join(map(str, c)))
def test_chunked_prefill_then_decode_match_the_reference(model, cfg, params, tokens, chunks):
    """Chunks with a padded tail whose edges split a block of 8 and a
    sub-chunk of the chunked form, then three decode steps, through the latent
    cache AND the state slots (a slot that held another sequence's trash),
    against the reference's full forward pass: logits, not tokens."""
    n = sum(chunks)
    table = np.zeros(8, np.int32)
    table[:8] = np.arange(1, 9)
    cache = kl.cache_layout(cfg, BS).init(16)
    state = jax.tree_util.tree_map(lambda a: a + 3.0, kl.state_layout(cfg).init(4))  # trash in every slot
    prefill, decode = _steps(cfg)
    cache, state, got_prefill = _prefill(prefill, params, cache, state, tokens[0], table, chunks, slot=2)
    tables = np.zeros((4, 8), np.int32)
    tables[1] = table  # rows 0, 2 and 3 of the batch are padding
    slots = np.array([0, 2, 0, 0], np.int32)
    have = [got_prefill]
    for d in range(3):
        toks, pos = np.zeros(4, np.int32), np.zeros(4, np.int32)
        toks[1], pos[1] = tokens[0, n + d], n + d
        cache, state, got, counters = decode(params, cache, state, toks, pos, tables, pos + 1, slots)
        assert int(counters["load"].sum()) == cfg.moe_top_k * cfg.n_moe_layers  # one real row
        have.append(np.asarray(got)[1])
    picks = [(0, n - 1 + i) for i in range(4)]
    for h, w in zip(have, reference.logits_at(model, params, tokens, picks)):
        assert _rel(h, w) < TOL
    # nothing but slot 2 (and the null slot, padding's) was written
    assert float(jnp.min(state["kda_state"][:, 1])) == 3.0 and float(jnp.min(state["kda_state"][:, 3])) == 3.0


def test_decode_slots_gather_their_context_at_their_own_width(model, cfg, params, tokens):
    """A decode batch whose slots hold contexts of unlike length (13 and 37
    under a table of 64, one crossing a block's edge while decoding, padding
    slots between them), against the reference: each slot reads its own
    context whole, through the table gathered at its width."""
    lens, tables = (37, 13), np.zeros((4, 8), np.int32)
    tables[0, :6], tables[2, :3] = np.arange(1, 7), np.arange(7, 10)
    cache, state = kl.cache_layout(cfg, BS).init(16), kl.state_layout(cfg).init(4)
    prefill, decode = _steps(cfg)
    for i, row, slot in ((0, 0, 3), (1, 2, 1)):
        cache, state, _ = _prefill(prefill, params, cache, state, tokens[i], tables[row], (lens[i],), slot=slot)
    slots = np.array([3, 0, 1, 0], np.int32)
    have = []
    for d in range(4):
        toks, pos = np.zeros(4, np.int32), np.zeros(4, np.int32)
        toks[[0, 2]], pos[[0, 2]] = [tokens[0, 37 + d], tokens[1, 13 + d]], [37 + d, 13 + d]
        cache, state, got, _ = decode(params, cache, state, toks, pos, tables, pos + 1, slots)
        have += [np.asarray(got)[0], np.asarray(got)[2]]
    picks = [(i, n + d) for d in range(4) for i, n in enumerate(lens)]
    for h, w in zip(have, reference.logits_at(model, params, tokens, picks)):
        assert _rel(h, w) < TOL


def test_decode_through_the_kernel_over_latent_rows_equals_the_gather(cfg, tokens, monkeypatch):
    """``paged_decode_step`` with the attending layers' absorbed path through
    ``ops/latent_paged.py`` (forced: the predicate as it reads on a TPU; the
    kernel then runs in Pallas' TPU interpreter) at widths the layout stores
    in whole tiles: two sequences on scattered blocks and slots, a padding
    row between them, one context crossing a block's edge while decoding. The
    logits, both pools' live parts and the path's name against the gather's."""
    cfg = dataclasses.replace(cfg, kv_lora_rank=48, qk_rope_head_dim=16)
    params = kl.init_params(cfg, jax.random.PRNGKey(6))
    bs, lens = 16, (37, 14)

    def run():
        cache, state = kl.cache_layout(cfg, bs).init(12), kl.state_layout(cfg).init(4)
        assert cache["latent"].shape[2:] == (8, 128)
        tables = np.zeros((4, 4), np.int32)
        tables[0], tables[2, :2] = [5, 2, 9, 7], [3, 8]
        prefill, decode = _steps(cfg)
        for i, row, slot in ((0, 0, 3), (1, 2, 1)):
            cache, state, _ = _prefill(prefill, params, cache, state, tokens[i], tables[row], (lens[i],), slot=slot)
        slots, have = np.array([3, 0, 1, 0], np.int32), []
        for d in range(3):
            toks, pos = np.zeros(4, np.int32), np.zeros(4, np.int32)
            toks[[0, 2]], pos[[0, 2]] = [tokens[0, 37 + d], tokens[1, 14 + d]], [37 + d, 14 + d]
            cache, state, got, _ = decode(params, cache, state, toks, pos, tables, pos + 1, slots)
            have.append(np.asarray(got)[[0, 2]])
        return np.stack(have), np.asarray(cache["latent"])[:, 1:], np.asarray(state["kda_state"])[:, 1:]

    want = run()
    assert kl.MODEL.attention_path(cfg, 1, None) == ("kda.update+latent.absorbed", "slots")
    monkeypatch.setattr(latent, "paged_serves", lambda cfg, window, cache, backend=None: latent.absorbs(cfg, window))
    assert kl.MODEL.attention_path(cfg, 1, None) == ("kda.update+latent.paged", "blocks")
    for h, w in zip(run(), want):
        assert np.isfinite(h).all() and _rel(h, w) < TOL


def test_the_attention_path_reads_blocks_where_the_kernel_serves():
    """At the published widths on a TPU, over the cache as the layout stores
    it (a block of 16 as ``[8, 1152]``), decode reads each slot's own live
    blocks; off the chip, or over a cache stored a block a row, the gather."""
    cfg = kl.KimiLinearConfig(dtype=jnp.bfloat16)
    cache = jax.eval_shape(lambda: kl.cache_layout(cfg, 16).init(8))
    assert cache["latent"].shape == (7, 8, 8, 1152)
    path = kl.MODEL.attention_path
    assert path(cfg, 1, cache, backend="tpu") == ("kda.kernel+latent.paged", "blocks")
    assert path(cfg, 1, cache, backend="cpu") == ("kda.update+latent.absorbed", "slots")
    rows = {"latent": jax.ShapeDtypeStruct((7, 8, 9216), jnp.bfloat16)}
    assert path(cfg, 1, rows, backend="tpu") == ("kda.kernel+latent.absorbed", "slots")
    assert path(cfg, 1024, cache, backend="cpu") == ("kda.chunk+latent.expanded", "table")


# -- the decode update of a layer's slab through the kernel (ops/kda.py) ---------------------------

def _wide():
    """One configuration with the published head width (128: whole lanes), 2
    KDA layers and an attending one."""
    return kl.KimiLinearConfig.tiny(
        n_layers=3, mla_layers=(3,), kda_heads=2, kda_head_dim=128, kda_chunk=8
    )


def _primitives(jaxpr, name):
    """How many equations of that primitive a jaxpr holds, its sub-jaxprs' too."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _primitives(sub, name)
    return n


def _decode_jaxpr(cfg, slots=4):
    params = jax.eval_shape(lambda: kl.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: kl.cache_layout(cfg, 16).init(8))
    state = jax.eval_shape(lambda: kl.state_layout(cfg).init(slots + 1))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    return jax.make_jaxpr(lambda *a: kl.paged_decode_step(cfg, *a))(
        params, cache, state, i32(slots), i32(slots), i32(slots, 4), i32(slots), i32(slots)
    )


def test_the_kda_kernel_serves_the_published_head_width_and_the_toy_keeps_its_program(cfg, monkeypatch):
    """``kda.kernel_serves`` reads the pool's shape and the backend, nothing
    else: yes at 128-wide heads on a TPU; no off the chip and no at the toy's
    16, whose decode program is the one it was (one ``kda.update`` a layer,
    no kernel) whatever the predicate would say of another pool."""
    published = jax.eval_shape(lambda: kl.state_layout(kl.KimiLinearConfig()).init(65))["kda_state"]
    assert published.shape == (20, 65, 32, 128, 128)
    assert kl.kda.kernel_serves(published, backend="tpu") and not kl.kda.kernel_serves(published, backend="cpu")
    toy = jax.eval_shape(lambda: kl.state_layout(cfg).init(5))["kda_state"]
    assert not kl.kda.kernel_serves(toy, backend="tpu")
    was = _decode_jaxpr(cfg)
    assert _primitives(was.jaxpr, "pallas_call") == 0 and _primitives(was.jaxpr, "optimization_barrier") == 0
    monkeypatch.setattr(kl.kda, "kernel_serves", lambda state, backend=None: False)
    assert str(_decode_jaxpr(cfg)) == str(was)


def test_where_the_kernel_serves_a_decode_step_holds_one_call_a_kda_layer_and_no_barrier(cfg, monkeypatch):
    """Traced as on a TPU (nothing runs): the 128-wide model's decode step
    calls ``kda_update`` once a KDA layer and has no ``optimization_barrier``
    (the kernel aliases the pool: there is no in-place fusion for XLA to
    clone); the toy's, which the kernel does not serve, keeps the barrier
    before its first write into the pool, as the parent's program on a TPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    wide = _wide()
    served = _decode_jaxpr(wide).jaxpr
    assert _primitives(served, "pallas_call") == wide.n_kda_layers == 2
    assert str(served).count("name=kda_update") == 1  # ONE traced kernel, the layer an operand
    assert _primitives(served, "optimization_barrier") == 0
    cache_of = lambda c: jax.eval_shape(lambda: kl.cache_layout(c, 16).init(8))  # noqa: E731
    assert kl.MODEL.attention_path(wide, 1, cache_of(wide)).name.startswith("kda.kernel+")
    assert kl.MODEL.attention_path(wide, 16, cache_of(wide)).name.startswith("kda.chunk+")
    kept = _decode_jaxpr(cfg).jaxpr
    assert _primitives(kept, "optimization_barrier") == 1 and "kda_update" not in str(kept)
    assert kl.MODEL.attention_path(cfg, 1, cache_of(cfg)).name.startswith("kda.update+")


def test_prefill_then_decode_through_the_kda_kernel_leave_the_logits_and_the_pool_where_kda_update_does(monkeypatch):
    """A chunked prefill of two sequences onto scattered slots, then 8 decode
    steps of the two together with two padding rows, at the published head
    width: once through ``kda_update`` (the CPU's path) and once through the
    kernel (the predicate as it reads on a TPU; Pallas' TPU interpreter).
    The logits of every step AND both arrays of the state pool agree; a
    sequence that STARTS in a decode step (its slot held garbage) does too."""
    cfg = _wide()
    params = kl.init_params(cfg, jax.random.PRNGKey(7))
    tokens = np.random.default_rng(3).integers(1, 256, size=(3, 40)).astype(np.int32)

    def run():
        cache, state = kl.cache_layout(cfg, 16).init(12), kl.state_layout(cfg).init(5)
        state = {k: jnp.full_like(v, 3.0) for k, v in state.items()}  # what earlier holders left
        tables = np.zeros((4, 4), np.int32)
        tables[0, :3], tables[2, :2], tables[3, :1] = [5, 2, 9], [3, 8], [7]
        prefill, decode = _steps(cfg)
        for i, row, slot, chunks in ((0, 0, 3, (16, 5)), (1, 2, 1, (9,))):
            cache, state, _ = _prefill(prefill, params, cache, state, tokens[i], tables[row], chunks, slot=slot, bucket=16)
        slots, have = np.array([3, 0, 1, 4], np.int32), []
        for d in range(8):
            toks, pos = np.zeros(4, np.int32), np.zeros(4, np.int32)
            toks[[0, 2]], pos[[0, 2]] = [tokens[0, 21 + d], tokens[1, 9 + d]], [21 + d, 9 + d]
            if d >= 3:  # the third sequence starts in the decode batch, at position 0, on slot 4
                toks[3], pos[3] = tokens[2, d - 3], d - 3
            live = tables if d >= 3 else np.where(np.arange(4)[:, None] == 3, 0, tables)
            cache, state, got, _ = decode(params, cache, state, toks, pos, live, pos + 1, slots)
            have.append(np.asarray(got)[[0, 2, 3] if d >= 3 else [0, 2]].ravel())
        return np.concatenate(have), np.asarray(state["kda_state"])[:, 1:], np.asarray(state["kda_conv"], np.float32)[:, 1:]

    want = run()
    assert kl.MODEL.attention_path(cfg, 1, None).name == "kda.update+latent.absorbed"
    monkeypatch.setattr(kl.kda, "kernel_serves", lambda state, backend=None: state.shape[-1] % 128 == 0)
    assert kl.MODEL.attention_path(cfg, 1, None).name == "kda.kernel+latent.absorbed"
    for h, w in zip(run(), want):
        assert np.isfinite(h).all() and _rel(h, w) < 1e-5


def test_forward_matches_the_reference_and_the_counts(model, cfg, params, tokens):
    full = np.asarray(jax.jit(lambda p, t: kl.forward(cfg, p, t))(params, jnp.asarray(tokens)))
    picks = [(0, 59), (1, 3), (1, 40)]
    for (i, t), want in zip(picks, reference.logits_at(model, params, tokens, picks)):
        assert _rel(full[i, t], want) < TOL
    fam = families.of(model)
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert fam.param_count(model) == kl.param_count(cfg) == n
    layout, state = kl.cache_layout(cfg, BS), kl.state_layout(cfg)
    # the cache counts the layers that WRITE rows (2 of 7), the state the layers that recur (5)
    assert layout.n_layers == cfg.n_mla_layers == 2 and state.n_layers == cfg.n_kda_layers == 5
    assert cfg.kinds == ("kda", "kda", "kda", "mla", "kda", "kda", "mla")
    assert fam.kv_bytes_per_token(model, 4) == layout.bytes_per_token == 2 * 24 * 4
    assert fam.state_bytes_per_seq(model, 4) == state.bytes_per_seq == 5 * (4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)
    assert state.describe() == {"kind": "kda", "layers": 5, "bytes_per_seq": state.bytes_per_seq}


@pytest.mark.parametrize("variant", controls.VARIANTS)
def test_every_control_reads_not_correct(model, cfg, params, tokens, variant):
    """Each wrong twin of the reference is told from the model by a wide
    margin in float32, on the logits or on the KDA layer alone."""
    m = dict(model, serving={"engine": {"prefill_buckets": [16]}})
    full = np.asarray(jax.jit(lambda p, t: kl.forward(cfg, p, t))(params, jnp.asarray(tokens)))
    picks = [(0, 59), (1, 40)]
    wrong = controls.logits_at(m, params, tokens, picks, variant)
    logits = max(_rel(full[i, t], w) for (i, t), w in zip(picks, wrong))
    p = params["layers"][1]
    h = jnp.asarray(np.random.default_rng(3).standard_normal((40, cfg.dim)), jnp.float32)
    have = reference.kda(reference.sizes(m), p, h)
    assert _rel(have, controls.kda(m, p, h, None)) == 0.0
    alone = _rel(have, controls.kda(m, p, h, variant))
    assert max(logits, alone) > 50 * TOL, (variant, logits, alone)


# -- the chunked form against the recurrence ---------------------------------------------------

@pytest.mark.parametrize("chunk,fast", [(8, False), (16, False), (8, True)])
def test_the_chunked_form_is_the_recurrence(chunk, fast):
    """``kda_chunked`` (sub-chunks, the WY form) against ``kda_update`` once a
    position and against the reference's scan, from a state that is not zero;
    ``fast``: decays of e^-30 a position, under which a form that divides by
    the running decay overflows."""
    rng = np.random.default_rng(chunk + fast)
    B, T, H, d = 2, 48, 3, 16
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    q = jnp.asarray(unit(rng.standard_normal((B, T, H, d))) * d ** -0.5, jnp.float32)
    k = jnp.asarray(unit(rng.standard_normal((B, T, H, d))), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, T, H, d)), jnp.float32)
    g = -jnp.asarray(rng.uniform(1e-3, 30.0 if fast else 2.0, (B, T, H, d)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0, 1, (B, T, H)), jnp.float32)
    S0 = jnp.asarray(rng.standard_normal((B, H, d, d)), jnp.float32)
    S, outs = S0, []
    for t in range(T):
        S, o = kl.kda_update(S, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        outs.append(o)
    S_c, o_c = kl.kda_chunked(S0, q, k, v, g, beta, chunk)
    assert bool(jnp.all(jnp.isfinite(o_c)))
    assert _rel(o_c, jnp.stack(outs, axis=1)) < 1e-5 and _rel(S_c, S) < 1e-5
    # from zeros, the family's reference (one sequence at a time)
    _, o_0 = kl.kda_chunked(jnp.zeros_like(S0), q, k, v, g, beta, chunk)
    assert _rel(o_0[0], reference.kda_recurrence(q[0], k[0], v[0], g[0], beta[0], q.shape[1])[0]) < 1e-5


@pytest.mark.parametrize("rho", [0.0, 0.8, 0.95])
def test_the_chunked_form_holds_with_keys_of_a_head_alike(rho):
    """Keys with a common part (pairwise cosine ``rho^2``), beta near 1, a
    slow decay: the sub-chunk's ``(I + L)^-1`` by blocks of 16 stays with the
    recurrence; the product formula over all 64 positions cancelled to
    nothing there (state 1e16 times off at cosine 0.64: found on the chip by
    the check's reading of the pool, hidden from the logits by the head norm)."""
    rng = np.random.default_rng(0)
    B, T, H, d = 1, 128, 2, 64
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)  # noqa: E731
    common = unit(rng.standard_normal((1, 1, H, d)))
    k = unit(rho * common + np.sqrt(1 - rho ** 2) * unit(rng.standard_normal((B, T, H, d))))
    q = unit(rng.standard_normal((B, T, H, d))) * d ** -0.5
    v = rng.standard_normal((B, T, H, d))
    q, k, v, g, beta = (jnp.asarray(a, jnp.float32)
                        for a in (q, k, v, np.full((B, T, H, d), -1e-3), np.full((B, T, H), 0.95)))
    S, outs = jnp.zeros((B, H, d, d), jnp.float32), []
    for t in range(T):
        S, o = kl.kda_update(S, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        outs.append(o)
    S_c, o_c = kl.kda_chunked(jnp.zeros_like(S), q, k, v, g, beta, 64)
    assert _rel(S_c, S) < 2e-4 and _rel(o_c, jnp.stack(outs, axis=1)) < 2e-4


@pytest.mark.parametrize("n", [8, 16, 37, 40, 64, 128])
def test_the_inverse_of_a_sub_chunk_by_blocks(n):
    """``(I + L)^-1`` of a strictly lower ``L`` whose entries all lie in 0.5
    .. 0.9 (the worst a sub-chunk's keys can do), at the sizes a window
    gives: whole blocks, 4 x 10, and an odd size padded."""
    L = jnp.asarray(np.tril(np.random.default_rng(n).uniform(0.5, 0.9, (3, n, n)), -1), jnp.float32)
    inv = kl._unit_lower_inverse(L, lambda eq, a, b: jnp.einsum(eq, a, b, precision="highest"))
    assert float(jnp.max(jnp.abs(inv @ (jnp.eye(n) + L) - jnp.eye(n)))) < 2e-4


def test_a_padded_tail_leaves_the_state_and_the_convolution_at_the_last_real_row(cfg, params):
    """Past ``true_len`` beta = 0 and g = 0: the state after a padded chunk is
    the state after its real rows, and the convolution's tail is cut from the
    last REAL inputs, whatever the padding rows hold."""
    p = params["layers"][0]
    rng = np.random.default_rng(2)
    h = jnp.asarray(rng.standard_normal((1, 16, cfg.dim)), jnp.float32)
    S0 = jnp.zeros((1, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim), jnp.float32)
    tail0 = jnp.zeros((1, cfg.conv_kernel - 1, 3 * cfg.kda_width), jnp.float32)
    out, S, tail = kl._kda_mix(cfg, p, h[:, :11], S0, tail0, jnp.ones((1, 11), bool))
    padded = h.at[:, 11:].set(1e4)
    out_p, S_p, tail_p = kl._kda_mix(cfg, p, padded, S0, tail0, (jnp.arange(16) < 11)[None])
    assert _rel(out_p[:, :11], out) < 1e-5 and _rel(S_p, S) < 1e-5 and _rel(tail_p, tail) == 0.0
    assert bool(jnp.all(jnp.isfinite(out_p)))


# -- one chip's share of the experts ---------------------------------------------------------------

def test_sixteen_shares_of_sixteen_experts_sum_to_the_whole_layer(model):
    """Sixteen chips each holding ONE of sixteen experts: their routed parts,
    and the shared expert (computed alike on every chip) counted once, add up
    to the uncut reference's whole layer."""
    whole = dict(model, num_experts=16, deployment={**model["deployment"], "num_experts_total": 16,
                                                   "held_experts": [0, 16]})
    fam = families.of(whole)
    cfg = fam.model_config(whole, max_seq_len=64)
    p = kl.init_params(cfg, jax.random.PRNGKey(7))["layers"][2]
    h = jnp.asarray(np.random.default_rng(0).standard_normal((24, cfg.dim)), jnp.float32)
    want, _ = reference.expert_ffn(reference.sizes(whole), p, h)
    shared = np.asarray(kl.gated_mlp(h, p["shared_gate"], p["shared_up"], p["shared_down"]))
    total = shared.copy()
    for e in range(16):
        share = dict(whole, num_experts=1, deployment={**whole["deployment"], "held_experts": [e, e + 1]})
        c = fam.model_config(share, max_seq_len=64)
        held = {**p, **{k: p[k][e : e + 1] for k in ("w_gate", "w_up", "w_down")}}
        out, aux = kl._ffn(c, held, h[None], jnp.ones((1, 24), bool), True)
        total += np.asarray(out[0]) - shared
        assert int(aux["load"].sum()) == 24 * cfg.moe_top_k  # routed over all sixteen
    assert _rel(total, want) < TOL


# -- the check's drive: the pool as the serving programs leave it ------------------------------------

@pytest.mark.parametrize("fault", [None, "pool_bf16", "slot_mix_up", "state_dropped_at_chunk_edge", "decay_left_out"])
def test_the_pool_s_reading_tells_a_fault_on_the_serving_path(model, cfg, params, fault):
    """``families/kimi_linear/server.py::drive``: three sequences on
    scattered slots through the runner's own prefill and decode programs (a
    chunk edge, padded tails, six decode steps), then the pool's state of
    the driven slots against the reference's recurrence. The model reads to
    float32's rounding; a pool kept in bfloat16, a sequence decoded once on
    another's slot, a state dropped at a chunk's edge and a decay left out
    each read orders above it in the first KDA layer's state."""
    from perfbench.families.kimi_linear import server
    from ray_tpu.inference.model_runner import PagedModelRunner

    runner = PagedModelRunner(cfg, params, num_blocks=64, block_size=BS, prefill_buckets=(16, 32),
                              decode_buckets=(4,), state_slots=4)
    assert server.check_slots(3, 4) == [2, 3, 4] and server.check_slots(4, 64)[:2] == [18, 47] and len(set(server.check_slots(64, 64))) == 64
    if fault == "pool_bf16":
        runner.state = {**runner.state, "kda_state": runner.state["kda_state"].astype(jnp.bfloat16)}
    if fault == "slot_mix_up":
        decode, calls = runner.decode, []

        def mixed(*args, slots, **kw):
            calls.append(slots)
            return decode(*args, slots=slots[1:] + slots[:1] if len(calls) == 3 else slots, **kw)

        runner.decode = mixed
    variant = fault if fault in controls.VARIANTS else None
    got = server.drive(
        runner, model, 7, [40, 12, 20], 6,
        lambda m, p, t, picks, lengths: controls.logits_at(m, p, t, picks, variant, lengths),
    )
    assert [p for i, p in got["positions"] if i == 0] == [39, 40, 41, 45]  # the last prompt position, steps 0, 1 and 5
    state = got["state"]
    assert state["finite"] and len(state["by_layer"]["kda_state"]) == len(state["by_layer"]["kda_conv"]) == 5
    if fault is None:
        assert state["worst"]["deep"] < 1e-4 and max(got["rel_err"]) < TOL
    else:
        assert state["worst"]["first"] > 1e-3 and state["worst"]["deep"] >= state["worst"]["first"]


def test_the_runner_counts_each_decode_slot_at_its_own_gather_width(cfg, params):
    """``decode_width["gathered_tokens"]`` follows the rule the program
    gathers by: each REAL slot the table at its width, a padding slot
    nothing; not the batch bucket x the table."""
    from ray_tpu.inference.model_runner import PagedModelRunner

    runner = PagedModelRunner(cfg, params, num_blocks=64, block_size=BS, prefill_buckets=(16, 32),
                              decode_buckets=(4,), state_slots=4)
    assert runner.attention_paths[1].reads == "slots"
    width = runner.max_blocks_per_seq
    rows = [list(range(1 + 8 * i, 9 + 8 * i)) + [0] * (width - 8) for i in range(2)]
    runner.decode([5, 6], [37, 13], rows, [38, 14], slots=[1, 2])
    dw = runner.decode_width
    assert (dw["launches"], dw["live_tokens"], dw["gathered_tokens"]) == (1, 52, 2 * width * BS)
    assert dw["width_tokens"] == width * BS


# -- the slot pool: manager and scheduler (host only) ----------------------------------------------

def test_the_manager_hands_out_and_takes_back_slots_with_the_blocks():
    blocks = PagedBlockManager(16, 8, state_slots=2)
    assert blocks.slot_of("a") == 0 and blocks.has_free_slot()
    assert blocks.grow_to("a", 10) and blocks.assign_slot("a") == 1 and blocks.assign_slot("a") == 1
    assert blocks.grow_to("b", 10) and blocks.assign_slot("b") == 2 and not blocks.has_free_slot()
    blocks.free("a")
    assert blocks.slot_of("a") == 0 and blocks.has_free_slot()
    assert blocks.evict("b") and blocks.slot_stats() == {
        "slots": 2, "in_use": 0, "peak_in_use": 2, "assigned": 2, "released": 2, "admission_waits": 0,
    }
    none = PagedBlockManager(16, 8)  # a model whose layers all attend: no pool, nobody waits
    assert none.has_free_slot() and none.assign_slot("a") == 0 and none.slot_stats()["slots"] == 0


def test_a_request_waits_for_a_slot_holding_nothing_and_every_way_out_gives_it_back():
    blocks = PagedBlockManager(32, 8, state_slots=1)
    sched = ContinuousBatchingScheduler(blocks, max_decode_batch=4, max_prefill_chunk=16)
    a, b, c = (Request(request_id=r, prompt=list(range(1, 12))) for r in "abc")
    for r in (a, b, c):
        sched.add(r)
    plan = sched.schedule()
    assert [p[0] for p in plan.prefills] == [a] and blocks.slot_of("a") == 1
    assert blocks.owned("b") == [] and blocks.slot_admission_waits == 1  # b waits, holding nothing
    sched.schedule()
    assert blocks.slot_admission_waits == 1  # counted once a request
    assert sched.finish(a) and blocks.slot_of("a") == 0
    sched.schedule()
    assert blocks.slot_of("b") == 1 and sched.cancel("b") is b and blocks.slot_of("b") == 0
    sched.schedule()
    assert blocks.slot_of("c") == 1 and blocks.slot_admission_waits == 2
    c.prefill_pos = len(c.prompt)
    assert sched._preempt_one(Request(request_id="x", prompt=[1])) and blocks.slot_of("c") == 0
    assert blocks.free_blocks == blocks.usable_blocks and blocks.slot_stats()["released"] == 3


# -- the engine ------------------------------------------------------------------------------------

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


@pytest.fixture(scope="module")
def wanted(cfg, params, prompts):
    forward = jax.jit(lambda p, t: kl.forward(cfg, p, t))
    return [_greedy(forward, params, p, 6) for p in prompts]


def test_the_engine_serves_through_slots_and_tells_of_both_layouts(cfg, params, prompts, wanted):
    """Two slots for five requests: requests wait for a slot, a slot is
    reused after a finish (its next holder's first chunk starts from zeros),
    nothing leaks, and the tokens are the full forward pass's."""
    eng = _engine(cfg, params, max_decode_batch=2, decode_buckets=(2,))  # a slot a running sequence
    try:
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        assert [list(eng.tokens(r)) for r in rids] == wanted
        st = eng.stats()
        assert st["kv_layout"] == {"kind": "latent", "row_width": 24, "bytes_per_token": 2 * 24 * 4}
        assert st["state_layout"] == {**kl.state_layout(cfg).describe(),
                                      "stored_bytes_per_seq": kl.state_layout(cfg).stored_bytes_per_seq}
        pool = st["state_pool"]
        assert pool["slots"] == 2 and pool["peak_in_use"] == 2 and pool["in_use"] == 0
        assert pool["assigned"] == pool["released"] == 5 and pool["admission_waits"] == 3
        assert st["blocks"]["used_blocks"] == 0 and st["recompiles_after_warmup"] == 0
        assert st["prefix_cache"]["enabled"] is False  # switched off: no state snapshot a block
    finally:
        eng.stop()


def test_greedy_slots_are_picked_on_the_device_and_sampled_ones_still_get_their_logits(cfg, params, prompts, wanted):
    """An all-greedy decode batch reads back one token a slot, taken on the
    device by the ONE decode program: the same tokens as the host's argmax
    over the logits; a batch with a sampled request in it reads the logits of
    the same program, and nothing compiles late."""
    eng = _engine(cfg, params)
    try:
        programs = eng.stats()["startup"]["warmup_programs"]
        assert [k for k in programs if k.startswith("paged_decode_step")] == ["paged_decode_step[4x128]"]
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        assert [list(eng.tokens(r)) for r in rids] == wanted
        warm, cold = (eng.submit(prompts[0], max_new_tokens=6, temperature=t, seed=3) for t in (0.0, 0.9))
        assert list(eng.tokens(warm)) == wanted[0] and len(list(eng.tokens(cold))) == 6
        assert eng.stats()["recompiles_after_warmup"] == 0
    finally:
        eng.stop()


@pytest.mark.parametrize("family", ["kimi_linear", "llama"])
def test_the_picks_of_a_decode_step_are_the_argmax_of_the_logits_it_keeps(cfg, params, family):
    """``decode(greedy=True)`` of the runner returns ``[n]`` int32, the first
    largest of each real slot's logits as ``np.argmax`` has it, from the same
    compiled program that returns the logits to any other caller: on a model
    with a state pool (two slots fed alike) and on one without."""
    from ray_tpu.inference.model_runner import PagedModelRunner
    from ray_tpu.models import llama

    if family == "llama":
        cfg = llama.LlamaConfig.tiny()
        params = llama.init_params(cfg, jax.random.PRNGKey(0))
    runner = PagedModelRunner(cfg, params, num_blocks=40, block_size=BS, prefill_buckets=(16,),
                              decode_buckets=(4,), state_slots=2 if family == "kimi_linear" else 0)
    runner.warmup()
    width = runner.max_blocks_per_seq
    rows = [[1 + 2 * i, 2 + 2 * i] + [0] * (width - 2) for i in range(2)]
    prompt = list(range(3, 14))
    for i in range(2):
        runner.prefill_chunk(prompt, rows[i], 0, slot=i + 1)
    step = lambda i, greedy: runner.decode([5], [11], [rows[i]], [12], slots=[i + 1], greedy=greedy)  # noqa: E731
    logits, picks = step(0, False), step(1, True)
    assert logits.shape == (1, cfg.vocab_size) and logits.dtype == np.float32
    assert picks.shape == (1,) and picks.dtype == np.int32 and int(picks[0]) == int(np.argmax(logits[0]))
    assert runner.compile_count() == len(runner.warmup_programs)


def test_a_repeated_prompt_takes_no_prefix_hit_and_a_cancelled_slot_starts_from_zeros(cfg, params, prompts, wanted):
    eng = _engine(cfg, params, max_decode_batch=1, decode_buckets=(1,), prefix_cache_enabled=True)  # ONE slot
    try:
        assert list(eng.generate(prompts[1], max_new_tokens=6)) == wanted[1]
        rid = eng.submit(prompts[3], max_new_tokens=40)
        stream = eng.tokens(rid)
        next(stream)  # it holds the one slot and has written its state
        assert eng.cancel(rid) and eng.wait_idle(10)
        assert list(eng.generate(prompts[1], max_new_tokens=6)) == wanted[1]  # the same prompt, the same slot
        st = eng.stats()
        assert st["prefix_cache"]["hits_total"] == 0 and st["state_pool"]["in_use"] == 0
    finally:
        eng.stop()


def test_a_preempted_request_re_derives_its_state_from_position_zero(cfg, params, prompts):
    """A pool too small for two long requests at once: one is preempted
    (blocks and slot given back), re-admitted, and its tokens are those of an
    undisturbed run (its first chunk after re-admission starts from zeros)."""
    forward = jax.jit(lambda p, t: kl.forward(cfg, p, t))
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
    assert field in str(e.value)


def test_export_and_import_are_refused_on_a_running_engine(cfg, params, prompts):
    eng = _engine(cfg, params)
    try:
        with pytest.raises(RuntimeError, match="per-sequence state"):
            eng.prefill_kv(prompts[1])
        with pytest.raises(RuntimeError, match="per-sequence state"):
            eng.import_kv_blocks(prompts[1], np.zeros((1, 2, 2, BS * 24), np.float32))
        with pytest.raises(ValueError, match="state slot"):
            eng.runner.prefill_chunk(prompts[0], [1] + [0] * 15, 0)  # no slot handed over
        with pytest.raises(NotImplementedError, match="roll-back"):
            model_of(cfg).paged_verify_step(cfg)
    finally:
        eng.stop()


def test_the_models_that_were_there_have_no_state_description(cfg):
    from ray_tpu.models import llama, xing4

    assert llama.MODEL.state_layout is None and xing4.MODEL.state_layout is None
    assert model_of(cfg).state_layout(cfg).kind == "kda"
    eng = InferenceEngine(llama.LlamaConfig.tiny(), llama.init_params(llama.LlamaConfig.tiny(), jax.random.PRNGKey(0)),
                          EngineConfig(num_blocks=16, block_size=8, prefill_buckets=(8,), decode_buckets=(2,),
                                       max_decode_batch=2, warmup=False))
    st = eng.stats()
    assert st["state_layout"] is None and st["state_pool"]["slots"] == 0 and eng.runner.state is None
