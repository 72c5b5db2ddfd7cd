"""``models/deepseek_v3.py`` (the DeepSeek-V3 block: ``models/xing4.py``'s body
with the plain residual, group-limited routing, a value head of its own width,
and the MTP module kept as the drafter) against the plain reference of its
family, ``perfbench/families/deepseek_v3/reference.py``, on the CPU at a small
size: float32 against float32, seeded weights. And the drafter on the engine's
normal path: a verify window of two with an oracle draft and a wrong one
against plain decode, greedy and sampled, one launch and two."""

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

import rehearsal  # noqa: E402
from perfbench import families  # noqa: E402
from perfbench.families.deepseek_v3 import reference  # noqa: E402
from ray_tpu.inference import EngineConfig  # noqa: E402
from ray_tpu.inference.engine import InferenceEngine  # noqa: E402
from ray_tpu.inference.speculative import MtpDrafts  # noqa: E402
from ray_tpu.models import deepseek_v3 as dsv3, latent, xing4  # noqa: E402
from ray_tpu.models.interface import model_of  # noqa: E402
from ray_tpu.ops import latent_flash, moe as moe_ops  # noqa: E402

CONFIG = "gigachat3.1-702b-a36b-ep16"
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
    return dsv3.init_params(cfg, jax.random.PRNGKey(5))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(11).integers(1, 256, size=(2, 60)).astype(np.int32)


def _rel(have, want):
    return float(np.max(np.abs(np.asarray(have) - np.asarray(want))) / np.max(np.abs(np.asarray(want))))


# -- the model and its module through the latent paged cache ----------------------------------

def _prefill(cfg, params, cache, row, table, chunks, bucket=40):
    step = jax.jit(lambda p, c, *a: dsv3.paged_prefill_step(cfg, p, c, *a), donate_argnums=(1,))
    start, n = 0, sum(chunks)
    for c in chunks:
        chunk = np.zeros(bucket, np.int32)
        chunk[:c] = row[start : start + c]
        follows = np.int32(row[start + c] if start + c < n else -1)
        cache, logits, _ = step(params, cache, chunk, table, np.int32(start), np.int32(c), follows)
        start += c
    return cache, np.asarray(logits)


@pytest.mark.parametrize("chunks", [(37,), (13, 24), (16, 16, 5), (32, 5)], ids=lambda c: "+".join(map(str, c)))
def test_chunked_prefill_then_steps_match_the_reference_main_logits_and_the_modules(model, cfg, params, tokens, chunks):
    """Chunks whose edges split a block of 8 (the MTP module's row at a chunk's
    last position takes the NEXT chunk's first token), then the step of a slot
    without a draft (the window one position earlier, both tokens committed:
    the row that waited for the first output token), then windows of two with
    the sequence's own next token as the draft: the main model's logits at
    BOTH rows, and the module's logits through ITS cache row, against the
    reference's full forward pass. Slot 1 of a bucket of 4; the others pad."""
    n = sum(chunks)
    table = np.arange(1, 9, dtype=np.int32)
    cache = dsv3.cache_layout(cfg, BS).init(16)
    assert cache["latent"].shape[0] == cfg.n_layers + 1  # ONE more row a token
    cache, got_prefill = _prefill(cfg, params, cache, tokens[0], table, chunks)
    verify = jax.jit(lambda p, c, *a: dsv3.paged_mtp_verify(cfg, p, c, *a), donate_argnums=(1,))
    draft = jax.jit(lambda p, c, *a: dsv3.paged_mtp_draft(cfg, p, c, *a), donate_argnums=(1,))
    tables = np.zeros((4, 8), np.int32)
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


def test_forward_matches_the_reference_and_the_counts(model, cfg, params, tokens):
    full = np.asarray(jax.jit(lambda p, t: dsv3.forward(cfg, p, t))(params, jnp.asarray(tokens)))
    picks = [(0, 59), (1, 3), (1, 40)]
    for (i, t), want in zip(picks, reference.logits_at(model, params, tokens, picks)):
        assert _rel(full[i, t], want) < TOL
    fam = families.of(model)
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    assert fam.param_count(model) == dsv3.param_count(cfg) == n
    assert fam.counts.mtp_params(model) == sum(a.size for a in jax.tree_util.tree_leaves(params["mtp"]))
    layout = dsv3.cache_layout(cfg, BS)
    assert fam.kv_bytes_per_token(model, 4) == layout.bytes_per_token == (cfg.n_layers + 1) * 24 * 4
    assert model_of(cfg) is dsv3.MODEL and model_of(xing4.Xing4Config.tiny()) is xing4.MODEL
    drafter = dsv3.MODEL.drafter(cfg)
    assert (drafter.kind, drafter.window, drafter.cache_layers) == ("mtp", 2, 1)
    assert dsv3.MODEL.drafter(dataclasses.replace(cfg, n_mtp_layers=0)) is None
    assert all(m.drafter is None for m in (xing4.MODEL, model_of(rehearsal_llama())))


def rehearsal_llama():
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig.tiny()


def test_the_one_program_step_equals_its_two_program_form(cfg, params, tokens):
    """``paged_mtp_step`` on four slots at once: a draft that is the model's
    own next token (accepted: two new tokens), a wrong one (one), a slot
    without a draft (``known`` 2: one new token, the module over both
    positions) and a padding slot, against ``paged_mtp_verify`` + argmax +
    ``paged_mtp_draft`` a slot at a time: the tokens, what was accepted, the
    next drafts, and the cache both leave."""
    table = np.arange(1, 9, dtype=np.int32)
    n = 21
    verify = jax.jit(lambda p, c, *a: dsv3.paged_mtp_verify(cfg, p, c, *a))
    draft = jax.jit(lambda p, c, *a: dsv3.paged_mtp_draft(cfg, p, c, *a))
    step = jax.jit(lambda p, c, *a: dsv3.paged_mtp_step(cfg, p, c, *a))
    base = dsv3.cache_layout(cfg, BS).init(40)
    tables = np.zeros((4, 8), np.int32)
    for slot in range(3):
        tables[slot] = table + 8 * slot
        base, _ = _prefill(cfg, params, base, tokens[0], tables[slot], (n,))
    one = lambda v: np.asarray([v], np.int32)  # noqa: E731
    # the model's own next two tokens after tokens[0, :n + 1], by the two-program form
    def plain(cache, slot, last, ctx):
        cache, lg, hid, _ = verify(params, cache, one([last, 0]), tables[slot][None], one(ctx), one(1))
        tok = int(np.argmax(np.asarray(lg)[0, 0]))
        cache, dl, _ = draft(params, cache, hid, one([tok, 0]), tables[slot][None], one(ctx), one(1))
        return cache, tok, int(np.argmax(np.asarray(dl)[0]))

    # every slot first takes the known-2 step (the module's row at n - 1 waits for it)
    known2 = np.zeros((4, 2), np.int32)
    known2[:3] = tokens[0, n - 1 : n + 1]
    ctx = np.asarray([n - 1] * 3 + [0], np.int32)
    base, (new, accepted, drafts), _ = step(params, base, known2, tables, ctx, np.asarray([2, 2, 2, 0], np.int32),
                                            np.asarray([2, 2, 2, 1], np.int32))
    new, accepted = np.asarray(new), np.asarray(accepted)
    assert list(accepted) == [0, 0, 0, 0] and len({int(t) for t in new[:3, 0]}) == 1
    t1 = int(new[0, 0])
    cache_a, t2, d_after = plain(base, 0, t1, n + 1)
    cache_b, t3, _ = plain(cache_a, 0, t2, n + 2)
    window = np.zeros((4, 2), np.int32)
    window[0] = [t1, t2]                      # the oracle: accepted
    window[1] = [t1, (t2 + 1) % 256]          # wrong: rejected
    window[2] = [t1, 0]                       # no draft rides (true_len 1)
    ctx = np.asarray([n + 1] * 3 + [0], np.int32)
    out_cache, (new, accepted, drafts), counters = step(
        params, base, window, tables, ctx, np.asarray([2, 2, 1, 0], np.int32), np.ones(4, np.int32))
    new, accepted, drafts = np.asarray(new), np.asarray(accepted), np.asarray(drafts)
    assert list(accepted) == [1, 0, 0, 0]
    assert list(new[0]) == [t2, t3] and new[1, 0] == t2 and new[2, 0] == t2
    # the next drafts: after the LAST committed position of each slot
    assert drafts[1] == drafts[2] == d_after  # one position committed: the module after t2 at n + 1
    # main rows 2 x 5 real rows x top_k x expert layers, + the module's 1 + accepted rows a slot
    assert int(counters["load"].sum()) == cfg.moe_top_k * (5 * cfg.n_moe_layers + (2 + 1 + 1))
    assert int(counters["routed_rows"].sum()) == 5 * cfg.n_moe_layers + 4
    # the cache of slot 2 (no draft) equals the two-program form's on the same context
    # (up to the context's end: the row past it is stale in both, and nobody's)
    lat = lambda c, blocks: np.asarray(c["latent"])[:, blocks].reshape(cfg.n_layers + 1, -1, 24)[:, : n + 2]  # noqa: E731
    np.testing.assert_allclose(lat(out_cache, tables[2]), lat(cache_a, tables[0]), atol=1e-6)


# -- group-limited routing ----------------------------------------------------------------------

def _by_hand(s, b, top_k, n_group, topk_group, scale):
    """The rule in plain numpy, a row at a time."""
    T, E = s.shape
    gates = np.zeros((T, E))
    for t in range(T):
        c = s[t] + b
        per_group = np.sort(c.reshape(n_group, E // n_group), axis=-1)
        score = per_group[:, -1] + per_group[:, -2]
        stays = np.argsort(-score)[:topk_group]
        allowed = np.isin(np.arange(E) // (E // n_group), stays)
        kept = np.argsort(-np.where(allowed, c, -np.inf))[:top_k]
        gates[t, kept] = scale * s[t, kept] / s[t, kept].sum()
    return gates


@pytest.mark.parametrize("E, n_group, topk_group, top_k", [(32, 8, 4, 8), (16, 4, 2, 3), (8, 4, 1, 2)])
def test_group_limited_route_against_the_rule_in_plain_numpy(E, n_group, topk_group, top_k):
    rng = np.random.default_rng(3)
    D, T = 24, 300
    router = jnp.asarray(rng.standard_normal((D, E)) / math.sqrt(D), jnp.float32)
    bias = jnp.asarray(0.2 * rng.standard_normal(E), jnp.float32)
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    g, e, probs = moe_ops.route(router, x, top_k=top_k, renormalize=True, scoring="sigmoid", bias=bias,
                                scale=2.5, n_group=n_group, topk_group=topk_group)
    have = np.zeros((T, E))
    np.put_along_axis(have, np.asarray(e), np.asarray(g), axis=1)
    s = 1 / (1 + np.exp(-(np.asarray(x, np.float64) @ np.asarray(router, np.float64))))
    want = _by_hand(s, np.asarray(bias, np.float64), top_k, n_group, topk_group, 2.5)
    z = {"n_group": n_group, "topk_group": topk_group, "top_k": top_k, "scaling": 2.5, "normalise": True}
    ref, margin = reference.gates(reference._Sizes(z), router, bias, x)
    sure = np.asarray(margin) > 1e-4
    assert sure.mean() > 0.9
    assert np.abs(have - want)[sure].max() < 1e-5 and np.abs(np.asarray(ref) - want)[sure].max() < 1e-5
    # the limit engages: some rows' kept set is not the plain top-k of s + b, and the counter says how many
    plain_kept = np.argsort(-(s + np.asarray(bias, np.float64)), axis=-1)[:, :top_k]
    changed = np.array([set(a) != set(np.flatnonzero(w)) for a, w in zip(plain_kept, want)])
    p = {"router": router, "router_bias": bias,
         **{k: jnp.zeros((E, D, 8) if k != "w_down" else (E, 8, D), jnp.float32) for k in ("w_gate", "w_up", "w_down")}}
    _, aux = moe_ops.dropless_moe_ffn(p, x, top_k=top_k, renormalize=True, scoring="sigmoid", scale=2.5,
                                      n_group=n_group, topk_group=topk_group)
    assert 0 < changed.sum() and abs(int(aux["group_changed"]) - changed.sum()) <= (~sure).sum()
    assert int(aux["routed_rows"]) == T


@pytest.mark.parametrize("which", ["xing4", "kimi_linear"])
def test_one_group_is_todays_route_bit_for_bit(which):
    """``n_group`` 1 (Xing4, Kimi-Linear) takes the code that was there: the
    same outputs to the bit, and the same lowered program, as a copy of the
    parent's ``route`` kept here."""
    def parents_route(router, x, *, top_k, renormalize, scoring, bias, scale):
        logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
        probs = jax.nn.sigmoid(logits) if scoring == "sigmoid" else jax.nn.softmax(logits, axis=-1)
        if bias is None:
            gates, experts = jax.lax.top_k(probs, top_k)
        else:
            _, experts = jax.lax.top_k(probs + bias.astype(jnp.float32), top_k)
            gates = jnp.take_along_axis(probs, experts, axis=-1)
        if renormalize:
            gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        if scale != 1.0:
            gates = gates * scale
        return gates, experts.astype(jnp.int32), probs

    if which == "xing4":
        cfg = xing4.Xing4Config.tiny()
        p = {k: v[0] for k, v in xing4.init_params(cfg, jax.random.PRNGKey(1))["moe"].items()}
        kw = dict(top_k=cfg.moe_top_k, renormalize=True, scoring="sigmoid", bias=p["router_bias"],
                  scale=cfg.routed_scaling_factor)
        assert (cfg.n_group, cfg.topk_group) == (1, 1)
    else:
        from ray_tpu.models import kimi_linear as kl

        cfg = kl.KimiLinearConfig.tiny()
        params = kl.init_params(cfg, jax.random.PRNGKey(1))
        p = next(layer["ffn"] if "ffn" in layer else layer for layer in params["layers"]
                 if "router" in layer.get("ffn", layer))
        kw = dict(top_k=cfg.moe_top_k, renormalize=True, scoring="sigmoid", bias=p.get("router_bias"),
                  scale=cfg.routed_scaling_factor)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((64, cfg.dim)), jnp.float32)
    have = moe_ops.route(p["router"], x, **kw)
    want = parents_route(p["router"], x, **kw)
    for a, b in zip(have, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    lowered = lambda f: jax.jit(lambda r, x, b: f(r, x, **{**kw, "bias": b})).lower(  # noqa: E731
        p["router"], x, kw["bias"]).as_text()
    strip = lambda t: [l.split("loc(")[0] for l in t.splitlines() if "module @" not in l]  # noqa: E731
    assert strip(lowered(moe_ops.route)) == strip(lowered(parents_route))


def test_sixteen_shares_of_held_experts_sum_to_the_uncut_layer_under_the_group_limit():
    """Guide section 4's test: 64 experts in 8 groups over 16 ranks of 4; the
    routed parts of all shares, with the shared expert counted ONCE, add up
    to the uncut reference's whole layer, the choice limited to 4 groups."""
    E, k, D, Fm, T = 64, 8, 64, 32, 96
    rng = np.random.default_rng(7)
    f = lambda *s: jnp.asarray(rng.standard_normal(s) / math.sqrt(s[-2] if len(s) > 1 else 1), jnp.float32)  # noqa: E731
    whole = {"router": f(D, E), "router_bias": 0.3 * f(E), "w_gate": f(E, D, Fm), "w_up": f(E, D, Fm),
             "w_down": f(E, Fm, D), "shared_gate": f(D, Fm), "shared_up": f(D, Fm), "shared_down": f(Fm, D)}
    h = f(T, D) * math.sqrt(T)
    z = lambda lo, hi: reference._Sizes(  # noqa: E731
        top_k=k, scaling=2.5, normalise=True, n_group=8, topk_group=4, lo=lo, hi=hi)
    uncut, _ = reference.expert_ffn(z(0, E), whole, h)
    total = np.asarray(reference.mlp(whole["shared_gate"], whole["shared_up"], whole["shared_down"], h))
    for lo in range(0, E, 4):
        share = {n: whole[n][lo : lo + 4] if n.startswith("w_") else whole[n] for n in
                 ("router", "router_bias", "w_gate", "w_up", "w_down")}
        routed, aux = moe_ops.dropless_moe_ffn(share, h, top_k=k, renormalize=True, scoring="sigmoid", scale=2.5,
                                               held=(lo, lo + 4), n_group=8, topk_group=4)
        assert int(aux["load"].sum()) == T * k  # the load is over all 64, whatever is held
        # a row reaches at most 4 of the 8 groups
        total = total + np.asarray(routed)
    assert _rel(total, uncut) < 1e-5
    _, experts, _ = moe_ops.route(whole["router"], h, top_k=k, renormalize=True, scoring="sigmoid",
                                  bias=whole["router_bias"], n_group=8, topk_group=4)
    assert max(len(set(row // 8)) for row in np.asarray(experts)) <= 4


# -- a value head of one and a half lane tiles through the flash kernel -----------------------------

def test_a_192_wide_value_through_the_flash_kernel_equals_the_materialised_softmax():
    """``attend_flash`` (the kernel in Pallas' generic interpreter) against
    ``attend_expanded`` at ``dv`` 192 beside keys of 128 + 64, NaNs planted
    past the live context; and what the predicate says of the widths."""
    cfg = dsv3.DeepseekV3Config.tiny(n_heads=2, kv_lora_rank=32, qk_nope_head_dim=128, qk_rope_head_dim=64,
                                     v_head_dim=192, dtype=jnp.float32)
    rng = np.random.default_rng(0)
    C, S, ctx, true = 128, 256, 70, 100
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    p = {"w_kvb": f(32, 2, 128 + 192) / math.sqrt(32)}
    q_nope, q_rope, rows = f(C, 2, 128), f(C, 2, 64), f(S, 96)
    rows = rows.at[ctx + true :].set(jnp.nan)
    have = latent.attend_flash(cfg, p, q_nope, q_rope, rows, jnp.int32(ctx), jnp.int32(true))
    clean = jnp.nan_to_num(rows)
    mask = (jnp.arange(S)[None, :] <= ctx + jnp.arange(C)[:, None])[None]
    want = latent.attend_expanded(cfg, p, q_nope[None], q_rope[None], clean[None], mask)[0]
    assert have.shape == (C, 2, 192) and bool(jnp.all(jnp.isfinite(have[:true])))
    assert _rel(have[:true], want[:true]) < 1e-5
    serves = lambda dv: latent_flash.kernel_serves(1024, 8192, 128, dv, 64, jnp.bfloat16, backend="tpu")  # noqa: E731
    assert serves(192) and serves(128) and serves(256) and not serves(96) and not serves(160) and not serves(64)
    published = dsv3.DeepseekV3Config(max_seq_len=8192, dtype=jnp.bfloat16)
    cache = {"latent": jax.ShapeDtypeStruct((7, 64, 8, 1152), jnp.bfloat16)}
    assert latent.flash_serves(published, 1024, cache, backend="tpu")
    assert not latent.absorbs(published, 256) and latent.absorbs(published, 2)  # break-even 232 queries


# -- the drafter on the engine's normal path ------------------------------------------------------

def _engine(cfg, params, k, **kw):
    fields = dict(num_blocks=64, block_size=BS, prefill_buckets=(8, 16), decode_buckets=(4,),
                  max_decode_batch=4, speculative_k=k, speculative_draft="mtp", speculative_adaptive=False,
                  prefix_cache_enabled=False)
    return InferenceEngine(cfg, params, EngineConfig(**{**fields, **kw}))


@pytest.fixture(scope="module")
def tiny():
    cfg = dsv3.DeepseekV3Config.tiny()
    return cfg, dsv3.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [[int(t) for t in rng.integers(1, 255, size=n)] for n in (5, 19, 30)]


@pytest.fixture(scope="module")
def plain_streams(tiny, prompts):
    """Plain decode (no speculation) of the same model: greedy and sampled."""
    out = {}
    engine = _engine(*tiny, 0).start()
    try:
        for temp in (0.0, 0.8):
            gens = [engine.generate(p, max_new_tokens=12, temperature=temp, seed=7) for p in prompts]
            out[temp] = [list(g) for g in gens]
    finally:
        engine.stop()
    return out


@pytest.mark.parametrize("temperature", [0.0, 0.8], ids=["greedy_one_launch", "sampled_two_launches"])
@pytest.mark.parametrize("drafts", ["the_modules_own", "oracle", "wrong"])
def test_a_verify_window_leaves_the_stream_plain_decodes(tiny, prompts, plain_streams, temperature, drafts, monkeypatch):
    """The stream is plain decode's whatever is drafted: with the module's own
    drafts (seeded weights: about none accepted), with an ORACLE that drafts
    the plain stream's next token (every draft accepted: two tokens a step)
    and with a draft that is always wrong (one). Greedy batches take ONE
    launch a step, sampled ones two; the books say which and add up."""
    want = plain_streams[temperature]
    flat = {tuple(p): s for p, s in zip(prompts, want)}
    engine = _engine(*tiny, 1)
    if drafts != "the_modules_own":
        def draft_of(self, request_id):
            if request_id not in self._next:
                return None  # no draft yet: the engine's own rule for a fresh slot stays
            req = next(r for r in engine.scheduler.running if r.request_id == request_id)
            stream = flat[tuple(req.prompt)]
            nxt = stream[len(req.generated)] if len(req.generated) < len(stream) else 0
            return nxt if drafts == "oracle" else (nxt + 1) % 256

        monkeypatch.setattr(MtpDrafts, "draft_of", draft_of)
    engine.start()
    try:
        gens = [engine.generate(p, max_new_tokens=12, temperature=temperature, seed=7) for p in prompts]
        got = [list(g) for g in gens]
        spec = engine.stats()["speculative"]
        moe = engine.stats()["moe"]["decode"]
    finally:
        engine.stop()
    assert got == want
    assert spec["draft"] == "mtp" and spec["step_launches"] == spec["launches_fused"] + spec["launches_split"]
    assert (spec["launches_split"] == 0) == (temperature == 0.0) and spec["step_launches"] > 0
    assert spec["committed_tokens"] == 3 * 11  # the first token of each is the prefill's
    if drafts == "oracle":
        assert spec["accepted_tokens"] == spec["proposed_tokens"] > 0 and spec["rollbacks"] == 0
        assert spec["committed_tokens"] > 1.5 * spec["slot_steps"]
    if drafts == "wrong":
        assert spec["accepted_tokens"] == 0 and spec["rollbacks"] == spec["proposed_tokens"] > 0
        assert spec["committed_tokens"] == spec["slot_steps"]
    assert moe["routed_rows"] > 0 and 0 < moe["group_changed"] < moe["routed_rows"]


def test_the_drafters_warm_up_compiles_no_plain_decode_program(tiny):
    engine = _engine(*tiny, 1, warmup=True)
    try:
        programs = {name.split("[")[0] for name in engine.runner.warmup_programs}
        assert programs == {"paged_prefill_step", "paged_mtp_step", "paged_mtp_verify", "paged_mtp_draft",
                            "copy_paged_blocks"}
        assert "paged_mtp_step[4x2x64]" in engine.runner.warmup_programs
        assert engine.stats()["kv_layout"]["bytes_per_token"] == (3 + 1) * 24 * 4
        rid = engine.submit([3, 4, 5, 6, 7, 8, 9, 10, 11], max_new_tokens=6)
        while engine.step():
            pass
        assert engine.stats()["recompiles_after_warmup"] == 0 and rid
    finally:
        engine.stop()
    plain = _engine(*tiny, 0, warmup=True)  # the same model without its drafter: the three plain programs
    try:
        assert {n.split("[")[0] for n in plain.runner.warmup_programs} == {
            "paged_prefill_step", "paged_decode_step", "copy_paged_blocks"}
    finally:
        plain.stop()


@pytest.mark.parametrize("field, kw, reason", [
    ("prefix_cache_enabled", {"prefix_cache_enabled": True}, "another request's continuation"),
    ("kv_transfer_enabled", {"kv_transfer_enabled": True}, "before its last position's row"),
    ("kv_tier_enabled", {"kv_tier_enabled": True}, "whatever followed them"),
    ("speculative_k", {"speculative_k": 2}, "drafts 1 token"),
])
def test_what_the_drafter_cannot_carry_is_refused_at_construction(tiny, field, kw, reason):
    cfg, params = tiny
    fields = dict(num_blocks=64, block_size=BS, prefill_buckets=(8,), decode_buckets=(4,), max_decode_batch=4,
                  speculative_k=1, speculative_draft="mtp", prefix_cache_enabled=False, warmup=False)
    fields.update(kw)
    with pytest.raises(ValueError, match=f"{field}.*cannot run here(.|\n)*{reason}"):
        InferenceEngine(cfg, params, EngineConfig(**fields))


def test_a_model_without_a_drafter_refuses_the_mtp_proposer(tiny):
    xcfg = xing4.Xing4Config.tiny()
    with pytest.raises(ValueError, match="needs a model with a drafter of its own"):
        InferenceEngine(xcfg, xing4.init_params(xcfg, jax.random.PRNGKey(0)), EngineConfig(
            num_blocks=64, block_size=BS, prefill_buckets=(8,), decode_buckets=(4,), max_decode_batch=4,
            speculative_k=1, speculative_draft="mtp", warmup=False))
    cfg, params = tiny
    with pytest.raises(ValueError, match="keeps none"):
        InferenceEngine(dataclasses.replace(cfg, n_mtp_layers=0), params, EngineConfig(
            num_blocks=64, block_size=BS, prefill_buckets=(8,), decode_buckets=(4,), max_decode_batch=4,
            speculative_k=1, speculative_draft="mtp", prefix_cache_enabled=False, warmup=False))


# -- the drafter's step n + 1 launched before step n is read (ISSUE 45) -----------------------------
# A saturated engine (2 decode slots, 6 requests) leaves the ONE-program step unread and names its
# rows in the next one: every stream is what an engine stepped from outside its loop gives (which
# reads every launch in its own step), and what plain decode gives, whatever is drafted.

import time  # noqa: E402
from types import SimpleNamespace  # noqa: E402

from ray_tpu.inference.engine import _END, RequestFailedError  # noqa: E402
from ray_tpu.inference.model_runner import PagedModelRunner, mtp_programs  # noqa: E402
from ray_tpu.inference.scheduler import DECODE, QUEUED  # noqa: E402

AHEAD = dict(num_blocks=64, decode_buckets=(2,), max_decode_batch=2, max_queue_depth=16, warmup=False)
#: what is drafted: the module's own (seeded weights: about none accepted), the plain stream's next
#: token (all accepted: two tokens a slot-step), that + 1 (none), and the two by the position's parity
KINDS = ["seeded", "oracle", "wrong", "mixed"]
NEW = [9, 12, 10, 13, 11, 12]


@pytest.fixture(scope="module")
def six(tiny):
    """Six prompts of lengths apart and what plain decode (no drafter) makes of each: 13 tokens."""
    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(1, 255, size=n)] for n in (5, 19, 30, 12, 23, 9)]
    engine = _engine(*tiny, 0, **AHEAD).start()
    try:
        streams = [list(engine.generate(p, max_new_tokens=13)) for p in prompts]
    finally:
        engine.stop()
    # the oracle's table: (position of the last committed token, that token) -> the next token
    table = np.zeros((tiny[0].max_seq_len + 2, tiny[0].vocab_size), np.int32)
    seen = {}
    for prompt, stream in zip(prompts, streams):
        for i in range(len(stream) - 1):
            key = (len(prompt) + i, stream[i])
            assert seen.setdefault(key, stream[i + 1]) == stream[i + 1], "two streams share a key: other prompts"
            table[key] = stream[i + 1]
    return prompts, streams, table


def _drafting(engine, kind, table):
    """Make the engine's ONE-program step draft ``kind``: the drafter's own step, its draft replaced
    on the device by a lookup of the last committed token at its position."""
    if kind == "seeded":
        return engine
    cfg, V = engine.cfg, engine.cfg.vocab_size
    table = jnp.asarray(table)

    def step(cfg_, params, cache, tokens, tables, ctx_lens, true_lens, known):
        cache, (new, accepted, _), aux = dsv3.paged_mtp_step(cfg_, params, cache, tokens, tables, ctx_lens, true_lens, known)
        both = known >= 2
        last = jnp.take_along_axis(new, jnp.where(both, 0, accepted)[:, None], axis=1)[:, 0]
        pos = ctx_lens + jnp.where(both, 2, 1 + accepted)
        truth = table[jnp.clip(pos, 0, table.shape[0] - 1), last]
        wrong = (truth + 1) % V
        draft = {"oracle": truth, "wrong": wrong, "mixed": jnp.where(pos % 2 == 0, truth, wrong)}[kind]
        return cache, (new, accepted, draft), aux

    runner = engine.runner
    one = mtp_programs(dataclasses.replace(runner.drafter, step=step), cfg, runner.decode_buckets[-1])[0]
    runner._mtp_step_jit = jax.jit(one, donate_argnums=(1,))
    return engine


def _drain(eng, rid, timeout=120.0):
    q, items = eng._out[rid], []
    while not items or not (items[-1] is _END or isinstance(items[-1], Exception)):
        items.append(q.get(timeout=timeout))
    return items


def _read_in_step(tiny, kind, table, submit, **kw):
    """The streams of an engine stepped from outside the loop: every launch read in its own step."""
    eng = _drafting(_engine(*tiny, 1, **{**AHEAD, **kw}), kind, table)
    rids = submit(eng)
    while eng.scheduler.has_work():
        assert eng.step() and eng._unread is None
    spec = eng.stats()["speculative"]
    assert spec["launches_ahead"] == 0 and spec["rows_dropped"] == 0
    return [_drain(eng, r, timeout=1) for r in rids], spec


def _looped(tiny, kind, table, submit, hook=None, **kw):
    eng = _drafting(_engine(*tiny, 1, **{**AHEAD, **kw}), kind, table)
    rids = submit(eng)  # before start(): the loop plans what the direct steps planned
    if hook is not None:
        hook(eng, rids)
    eng.start()
    try:
        got = [_drain(eng, r) for r in rids]
        assert eng.wait_idle() and eng._unread is None
    finally:
        eng.stop()
    st = eng.stats()
    assert st["blocks"]["used_blocks"] == 0 and not eng.spec._next  # every block and draft went back
    assert st["decode_ahead"] == {"launches": 0, "ahead": 0, "dropped": 0}  # _launch_decode's alone
    return eng, rids, got, st["speculative"]


@pytest.mark.parametrize("kind", KINDS)
def test_looking_ahead_streams_what_reading_every_step_streams_whatever_is_drafted(tiny, six, kind):
    prompts, streams, table = six
    submit = lambda eng: [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, NEW)]  # noqa: E731
    want, sync = _read_in_step(tiny, kind, table, submit)
    assert [items[:-1] for items in want] == [s[:n] for s, n in zip(streams, NEW)]  # plain decode's
    _, _, got, spec = _looped(tiny, kind, table, submit)
    assert got == want
    # most steps were launched while the one before was unread, all in the ONE program
    assert spec["launches_split"] == 0 and spec["launches_fused"] == spec["step_launches"]
    assert spec["launches_ahead"] >= spec["step_launches"] // 2 > 0
    # the books count the rows that were read AND committed: a dropped row is in neither
    committed = sum(NEW) - len(NEW)  # the first token of each is the prefill's
    assert spec["committed_tokens"] == committed == sync["committed_tokens"]
    assert spec["committed_tokens"] == spec["slot_steps"] + spec["accepted_tokens"]
    if kind == "oracle":
        assert spec["accepted_tokens"] == spec["proposed_tokens"] > 0 and spec["rollbacks"] == 0
        assert spec["committed_tokens"] > 1.5 * spec["slot_steps"]
        # a draft accepted as a request's LAST token finished it with its next window in flight
        assert spec["rows_dropped"] >= 1
    elif kind == "mixed":
        assert 0 < spec["accepted_tokens"] < spec["proposed_tokens"]
    elif kind == "wrong":
        # no draft accepted: a cap is then met a step ahead, and no row is wasted
        assert spec["accepted_tokens"] == 0 and spec["rows_dropped"] == 0
        assert spec["rollbacks"] == spec["proposed_tokens"] > 0


def _find_eos(streams, upto):
    """(which stream, its tokens, the index of one that did not occur in it before), after the second."""
    return next(
        (n, s, i) for n, s in enumerate(streams) for i in range(2, upto) if s[i] not in s[:i]
    )


@pytest.mark.parametrize("kind", ["wrong", "oracle"])
def test_an_eos_met_a_step_late_drops_its_next_window_and_streams_no_stray_token(tiny, six, kind):
    prompts, streams, table = six
    which, tokens, at = _find_eos(streams, 11)
    eos = tokens[at]

    def submit(eng):
        return [eng.submit(p, max_new_tokens=12, eos_token=eos if i == which else None)
                for i, p in enumerate(prompts)]

    want, _ = _read_in_step(tiny, kind, table, submit)
    assert want[which] == tokens[: at + 1] + [_END]
    assert all(want[i] == streams[i][:12] + [_END] for i in range(6) if i != which)
    _, _, got, spec = _looped(tiny, kind, table, submit)
    assert got == want
    # the host sees the EOS only when it reads the step, and the next window was launched by then
    # (with drafts that are never accepted nothing else is dropped: a cap is met a step ahead)
    assert spec["rows_dropped"] == 1 if kind == "wrong" else spec["rows_dropped"] >= 1
    assert spec["committed_tokens"] <= spec["slot_steps"] + spec["accepted_tokens"]


def _loop_by_hand(eng, each=lambda: None):
    """The loop's own steps (``hold_wakes``: a launch may stay unread) from this thread, ``each``
    called after every one."""
    while eng.scheduler.has_work() or eng._unread is not None:
        eng.step(hold_wakes=True)
        each()
    eng.step()  # nothing to launch: whatever is held goes out


def test_a_cap_reached_inside_an_accepted_window_drops_the_window_after_it(tiny, six):
    """With every draft accepted a request two tokens short of its cap MAY finish in the unread
    step: it is planned (without a draft), and where it did finish its row is dropped."""
    prompts, streams, table = six
    eng = _drafting(_engine(*tiny, 1, **AHEAD), "oracle", table)
    rids = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, NEW)]
    late, lengths = [], {}

    def each():
        for req in (eng._unread.reqs if eng._unread is not None else ()):
            if req.finished:  # read (and ended) in this step with its next window launched before
                late.append((req.request_id, len(req.generated) - lengths.get(req.request_id, 0)))
        lengths.update((r.request_id, len(r.generated)) for r in eng.scheduler.running)

    _loop_by_hand(eng, each)
    assert [_drain(eng, r, timeout=1) for r in rids] == [s[:n] + [_END] for s, n in zip(streams, NEW)]
    spec = eng.stats()["speculative"]
    # each ended on the SECOND token of an accepted window, and each cost exactly its one row
    assert late and all(grew == 2 for _, grew in late) and len({rid for rid, _ in late}) == len(late)
    assert spec["rows_dropped"] == len(late)
    assert spec["accepted_tokens"] == spec["proposed_tokens"] and spec["launches_ahead"] > 0
    assert spec["committed_tokens"] == spec["slot_steps"] + spec["accepted_tokens"] == sum(NEW) - 6
    assert eng.blocks.used_blocks == 0 and eng._unread is None and not eng._held


def _at_launch_ahead(eng, n, act, seen=None):
    """Run ``act`` on the step thread inside the ``n``-th drafter step launched while another is
    unread (a window of every named row is in flight then); ``seen`` collects each such launch's
    windows and ``known``."""
    launch, count = eng.runner.launch_mtp_step, []

    def hooked(windows, known, *a, after=None, **kw):
        if after is not None:
            count.append(1)
            if seen is not None:
                seen.append(([list(w) for w in windows], list(known)))
            if len(count) == n:
                act()
        return launch(windows, known, *a, after=after, **kw)

    eng.runner.launch_mtp_step = hooked


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_a_request_ended_with_a_window_in_flight_streams_a_prefix_and_its_terminal(tiny, six, how):
    prompts, streams, table = six
    submit = lambda eng: [eng.submit(p, max_new_tokens=12) for p in prompts]  # noqa: E731
    ended = []

    def hook(eng, rids):
        def end():
            req = eng._unread.reqs[0]
            assert req.in_flight == 0 and req.state == DECODE
            if how == "cancel":
                assert eng.cancel(req.request_id)
            else:
                req.deadline = SimpleNamespace(expired=True)  # reaped by the next plan
            ended.append(req.request_id)

        _at_launch_ahead(eng, 2, end)

    _, rids, got, spec = _looped(tiny, "wrong", table, submit, hook)
    for rid, have, full in zip(rids, got, streams):
        if rid in ended:
            assert 1 <= len(have) - 1 < 12 and have[:-1] == full[: len(have) - 1]
            assert have[-1] is _END if how == "cancel" else isinstance(have[-1], RequestFailedError)
        else:
            assert have == full[:12] + [_END]
    # a cancel ends it at once: the unread step and the one being made both carried a row of it.
    # A deadline is met by the next plan: the second alone
    assert len(ended) == 1 and spec["rows_dropped"] == (2 if how == "cancel" else 1)


@pytest.mark.parametrize("kind", ["wrong", "oracle"])
def test_a_preempted_request_loses_its_window_in_flight_and_decodes_it_again(tiny, kind):
    rs = np.random.RandomState(4)
    prompts = [[int(t) for t in rs.randint(1, 200, size=n)] for n in (33, 27)]
    plain = _engine(*tiny, 0, **AHEAD).start()
    try:
        streams = [list(plain.generate(p, max_new_tokens=28)) for p in prompts]
    finally:
        plain.stop()
    table = np.zeros((tiny[0].max_seq_len + 2, tiny[0].vocab_size), np.int32)
    for p, s in zip(prompts, streams):
        for i in range(len(s) - 1):
            table[len(p) + i, s[i]] = s[i + 1]
    submit = lambda eng: [eng.submit(p, max_new_tokens=28) for p in prompts]  # noqa: E731
    # the two sequences grow to 61 + 55 tokens (8 + 7 blocks of 8, 11 usable): the pool runs dry
    tight = dict(num_blocks=12)
    want, _ = _read_in_step(tiny, kind, table, submit, **tight)
    assert [items[:-1] for items in want] == streams
    eng, _, got, spec = _looped(tiny, kind, table, submit, **tight)
    assert got == want
    assert eng.stats()["scheduler"]["total_preempted"] >= 1
    assert spec["launches_ahead"] > 0 and spec["rows_dropped"] >= 1


def test_a_slot_fresh_from_prefill_rides_beside_named_rows(tiny, six):
    prompts, streams, table = six
    seen = []
    submit = lambda eng: [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, NEW)]  # noqa: E731
    _, _, got, spec = _looped(tiny, "mixed", table, submit, lambda eng, rids: _at_launch_ahead(eng, 0, None, seen))
    assert got == [s[:n] + [_END] for s, n in zip(streams, NEW)]
    # one launch held a window the unread step leaves on the device (named) AND one whose two
    # tokens the host knows (a slot whose prompt just ended: no draft yet)
    mixed = [(w, k) for w, k in seen if any(x[0] < 0 for x in w) and any(x[0] >= 0 for x in w)]
    assert mixed and any(2 in k for _, k in mixed)
    assert all(k[i] == 1 for w, k in seen for i, x in enumerate(w) if x[0] < 0)


def test_a_batch_that_turns_sampled_is_read_at_once_and_takes_the_two_programs(tiny, six):
    prompts, _, table = six

    def submit(eng, then=lambda: None):
        rids = [eng.submit(p, max_new_tokens=24) for p in prompts[:3]]
        then()
        return rids + [eng.submit(prompts[3], max_new_tokens=10, temperature=0.9, seed=7)]

    want, _ = _read_in_step(tiny, "mixed", table, submit)
    eng = _drafting(_engine(*tiny, 1, **AHEAD), "mixed", table)
    decided, stays = [], eng._stays_unread

    def logged(plan, batch):
        sampled = any(r.temperature > 0.0 for r in eng.scheduler.running)
        decided.append((batch.greedy, sampled, stays(plan, batch), eng._unread is None))
        return decided[-1][2]

    eng._stays_unread = logged

    def started():
        eng.start()
        deadline = time.monotonic() + 60
        while eng.stats()["speculative"]["launches_ahead"] < 2:
            assert time.monotonic() < deadline

    try:
        rids = submit(eng, started)
        got = [_drain(eng, r) for r in rids]
        assert eng.wait_idle() and eng._unread is None
    finally:
        eng.stop()
    assert got == want
    spec = eng.stats()["speculative"]
    assert spec["launches_split"] > 0 and spec["launches_fused"] > 0
    # the loop looked ahead before the sampled request ran; from the plan that admitted it on (its
    # prompt still in prefill) every launch was read in its own step, so that the two-program
    # form, whose windows the host must know, found nothing unread
    assert any(greedy and stayed for greedy, _, stayed, _ in decided)
    assert any(not greedy for greedy, _, _, _ in decided)
    assert all(not stayed for _, sampled, stayed, _ in decided if sampled)
    assert all(none_unread for greedy, _, _, none_unread in decided if not greedy)


def test_no_block_of_a_window_in_flight_is_trimmed(tiny, six):
    prompts, streams, table = six
    eng = _drafting(_engine(*tiny, 1, **AHEAD), "mixed", table)
    rids = [eng.submit(p, max_new_tokens=13) for p in prompts]
    read, crossed = eng._read_decode, []

    def checked(batch, later=None):
        read(batch, later)
        for row, req in enumerate(later.reqs if later is not None else ()):
            if req.state == DECODE and req in batch.reqs:
                # its next window stands at its newest token and writes as far as its draft
                reach = req.context_len + later.windows.riding(row)
                held = len(eng.blocks.owned(req.request_id))
                assert held >= eng.blocks.blocks_for_tokens(reach), (req.request_id, held, reach)
                crossed.append(eng.blocks.blocks_for_tokens(reach) > eng.blocks.blocks_for_tokens(req.context_len))

    eng._read_decode = checked
    _loop_by_hand(eng)
    assert [_drain(eng, r, timeout=1) for r in rids] == [s + [_END] for s in streams]
    # the check had teeth: some window in flight wrote into a block past the committed context's
    assert any(crossed) and eng.stats()["speculative"]["launches_ahead"] > 0
    assert eng.blocks.used_blocks == 0


@pytest.mark.parametrize("leave", ["stop", "wait_idle", "outside_step", "fail_all"])
def test_no_drafter_step_stays_unread(tiny, six, leave):
    prompts, _, table = six
    eng = _drafting(_engine(*tiny, 1, **AHEAD), "mixed", table)
    rids = [eng.submit(p, max_new_tokens=6 if leave == "wait_idle" else 30) for p in prompts]
    if leave == "outside_step":
        while eng._unread is None:
            assert eng.step(hold_wakes=True)
        assert all(r.in_flight is not None and 1 <= r.ahead <= r.ahead_most <= 2 for r in eng._unread.reqs)
        assert eng.step() and eng._unread is None  # a step from outside reads both
        assert all(r.in_flight is None and r.ahead_most == 0 for r in eng.scheduler.running)
        assert eng.stats()["speculative"]["launches_ahead"] == 1
        eng.stop()
        return
    eng.start()
    try:
        deadline = time.monotonic() + 60
        while eng.stats()["speculative"]["launches_ahead"] < 3:
            assert time.monotonic() < deadline
        if leave == "wait_idle":
            assert eng.wait_idle(60)
            assert all(_drain(eng, r)[-1] is _END for r in rids)
        elif leave == "fail_all":
            eng._fail_all(RequestFailedError("failed"))
            assert all(isinstance(_drain(eng, r)[-1], RequestFailedError) for r in rids)
            assert eng.wait_idle(60)
        else:
            eng.stop()
        assert eng._unread is None
    finally:
        eng.stop()
    assert eng._unread is None and not eng._held
    assert eng.blocks.used_blocks == 0 and not eng.spec._next


def test_a_window_named_in_the_step_before_runs_as_the_window_itself(tiny):
    """The ONE program's merge against the same windows given as plain integers, bit for bit: an
    accepted row (its window stands one position further than the host said), a rejected one and
    one without a draft, named out of order by a program of another batch bucket."""
    cfg, params = tiny
    rng = np.random.default_rng(5)
    prompts = [[int(t) for t in rng.integers(1, 255, size=10)] for _ in range(3)]

    def fresh():
        runner = PagedModelRunner(cfg, params, num_blocks=32, block_size=BS, prefill_buckets=(16,),
                                  decode_buckets=(2, 4), verify_buckets=(2,), drafter=True)
        runner.warmup()
        rows = [[1 + 2 * i, 2 + 2 * i] + [0] * (runner.max_blocks_per_seq - 2) for i in range(3)]
        firsts = [int(np.argmax(runner.prefill_chunk(p, r, 0))) for p, r in zip(prompts, rows)]
        # every slot first takes the known-2 step (the module's row at the prompt's end waits for it)
        a = runner.read(runner.launch_mtp_step([[p[-1], t] for p, t in zip(prompts, firsts)], [2] * 3, rows, [9] * 3))
        return runner, rows, a

    runner, rows, a = fresh()
    truth = runner.read(runner.launch_mtp_step([[int(r[0]), int(r[-1])] for r in a], [1] * 3, rows, [11] * 3))[:, 0]
    out = []
    for named in (False, True):
        runner, rows, a = fresh()
        x = [int(r[0]) for r in a]
        windows = [[x[0], int(truth[0])], [x[1], (int(truth[1]) + 1) % 256], [x[2]]]
        b = runner.launch_mtp_step(windows, [1] * 3, rows, [11] * 3)
        if named:
            c = runner.launch_mtp_step([[-1 - 2, 0], [-1 - 0, 0], [-1 - 1]], [1] * 3, [rows[2], rows[0], rows[1]],
                                       [12] * 3, after=b)
            got_b = runner.read(b)
        else:
            got_b = runner.read(b)
            nxt = lambda r: [int(r[r[-2]]), int(r[-1])]  # noqa: E731  the last committed token, the draft
            c = runner.launch_mtp_step(
                [nxt(got_b[2]), nxt(got_b[0]), nxt(got_b[1])[:1]], [1] * 3, [rows[2], rows[0], rows[1]],
                [12 + int(got_b[2][-2]), 12 + int(got_b[0][-2]), 12 + int(got_b[1][-2])])
        assert [int(r[-2]) for r in got_b] == [1, 0, 0]
        got_c = runner.read(c)
        assert got_c.shape == (3, 4) and got_c.dtype == np.int32
        out.append((got_b, got_c, {k: np.asarray(v) for k, v in runner.cache.items()}))
        # one program a batch bucket, whatever bucket made the result it is handed
        assert runner.recompiles_after_warmup() == 0
    for have, want in zip(out[1][:2], out[0][:2]):
        np.testing.assert_array_equal(have, want)
    for name, arr in out[0][2].items():
        np.testing.assert_array_equal(out[1][2][name], arr)
    with pytest.raises(ValueError, match="names a row"):
        runner.launch_mtp_step([[-1, 0]], [1], [rows[0]], [12])
    with pytest.raises(ValueError, match="names a row"):
        runner.launch_mtp_step([[-1, 0]], [1], [rows[0]], [12], greedy=False, after=c)
