"""``models/lfm2.py`` (gated short-convolution layers with a per-sequence
state pool beside grouped-query attention layers of narrow heads with a paged
K/V cache, sigmoid routing with a choice bias over a held range of experts, a
tied head) against the plain reference of its family,
``perfbench/families/lfm2/reference.py``, on the CPU at a small size: float32
against float32, seeded weights. And the state slots through the engine:
the FIRST model with a ``"kv"`` cache AND a state pool."""

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

import lfm2_controls as controls  # noqa: E402
import rehearsal  # noqa: E402
from perfbench import families  # noqa: E402
from perfbench.families.lfm2 import reference  # noqa: E402
from ray_tpu.inference import EngineConfig  # noqa: E402
from ray_tpu.inference.engine import InferenceEngine  # noqa: E402
from ray_tpu.models import lfm2  # noqa: E402
from ray_tpu.models.interface import model_of  # noqa: E402
from ray_tpu.ops import short_conv  # noqa: E402

CONFIG = "lfm2-8b-a1b-ep2"
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
    return lfm2.init_params(cfg, jax.random.PRNGKey(5))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(11).integers(1, 256, size=(2, 60)).astype(np.int32)


def _rel(have, want):
    return float(np.max(np.abs(np.asarray(have) - np.asarray(want))) / np.max(np.abs(np.asarray(want))))


# -- the whole model through both pools ----------------------------------------------------------

def _steps(cfg):
    prefill = jax.jit(lambda p, c, s, *a: lfm2.paged_prefill_step(cfg, p, c, s, *a), donate_argnums=(1, 2))
    decode = jax.jit(lambda p, c, s, *a: lfm2.paged_decode_step(cfg, p, c, s, *a), donate_argnums=(1, 2))
    return prefill, decode


def _prefill(step, params, cache, state, row_tokens, table, chunks, slot, bucket=40):
    start = 0
    for c in chunks:
        chunk = np.full(bucket, 77, np.int32)  # the padding rows hold a real token: its z is not zero
        chunk[:c] = row_tokens[start : start + c]
        cache, state, logits, _ = step(
            params, cache, state, chunk, table, np.int32(start), np.int32(c), np.int32(slot)
        )
        start += c
    return cache, state, np.asarray(logits)


@pytest.mark.parametrize("chunks", [(37,), (36, 1), (35, 2), (34, 3), (16, 16, 5), (7, 1, 2, 3, 24), (1, 1, 1, 34)],
                         ids=lambda c: "+".join(map(str, c)))
def test_chunked_prefill_then_decode_match_the_reference(model, cfg, params, tokens, chunks):
    """Chunks of 1, 2 and 3 rows (shorter than, and as long as, the taps)
    with a padded tail, whose edges split a block of 8, then three decode
    steps, through the K/V cache AND the state slots (a slot that held
    another sequence's trash), against the reference's full forward pass:
    logits, not tokens; and the tail the pool is left with, against the
    reference's ``z`` at the last two positions."""
    n = sum(chunks)
    table = np.arange(1, 9, dtype=np.int32)
    cache = lfm2.cache_layout(cfg, BS).init(16)
    assert cache["k"].shape == (2, 16, BS, 2 * 64)  # a token's heads in one row of whole lanes
    state = jax.tree_util.tree_map(lambda a: a + 3.0, lfm2.state_layout(cfg).init(4))  # trash in every slot
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
    want, tails = reference.logits_at(model, params, tokens[:1], picks, [(n + 3,)])
    for h, w in zip(have, want):
        assert _rel(h, w) < TOL
    for layer, tail in enumerate(tails[0]):
        assert _rel(state["conv_tail"][layer, 2], tail[0].reshape(-1)) < TOL
    # nothing but slot 2 (and the null slot, padding's) was written
    assert float(jnp.min(state["conv_tail"][:, 1])) == 3.0 == float(jnp.max(state["conv_tail"][:, 3]))


def test_two_sequences_swap_slots_and_a_fresh_slot_reads_zeros_whatever_it_held(model, cfg, params, tokens):
    """Sequence A prefills on slot 3 and B on slot 1; then each is served
    AGAIN from position 0 on the other's slot, which holds the other's tail:
    a chunk at ``ctx_len`` 0 reads zeros, so the logits are those of the
    reference, and decode steps of the two together (padding between them)
    go on from their own tails."""
    lens, tables = (37, 13), np.zeros((4, 8), np.int32)
    tables[0, :6], tables[2, :3] = np.arange(1, 7), np.arange(7, 10)
    cache, state = lfm2.cache_layout(cfg, BS).init(16), lfm2.state_layout(cfg).init(4)
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
        cache, state, got, _ = decode(params, cache, state, toks, pos, tables, pos + 1, slots)
        have += [np.asarray(got)[0], np.asarray(got)[2]]
    picks = [(i, n + d) for d in range(4) for i, n in enumerate(lens)]
    for h, w in zip(have, reference.logits_at(model, params, tokens, picks)):
        assert _rel(h, w) < TOL


def test_forward_matches_the_reference_and_the_counts(model, cfg, params, tokens):
    logits = lfm2.forward(cfg, params, jnp.asarray(tokens))
    picks = [(i, t) for i in range(2) for t in (0, 1, 2, 31, 59)]
    want = reference.logits_at(model, params, tokens, picks)
    for (i, t), w in zip(picks, want):
        assert _rel(logits[i, t], w) < TOL
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == lfm2.param_count(cfg) == families.of(model).param_count(model)
    assert "lm_head" not in params  # the embedding is the head
    assert cfg.kinds == ("conv", "conv", "attn", "conv", "attn", "conv", "conv")
    axes = lfm2.logical_axes(cfg)
    assert jax.tree_util.tree_structure(jax.tree_util.tree_map(lambda _: 0, params)) == jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda _: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))
    loss = reference.next_token_loss(model, params, tokens[:, :-1], tokens[:, 1:])
    assert abs(loss - np.log(256)) < 1.0


@pytest.mark.parametrize("variant", controls.VARIANTS)
def test_every_control_reads_not_correct(model, cfg, params, tokens, variant):
    """Each wrong twin of the reference is told from the program by the
    logits of a full forward pass, float32 against float32: orders above the
    model's own reading. (``tail_cut_at_padded_end``: the prompt of 33 = 32 +
    1 ends a padded chunk, and the positions behind it see the difference.)"""
    toy = dict(model, correctness={**model["correctness"], "prompt_lens": [33, 33]})
    if variant == "gate_keeps_bias":
        # a routed expert's output is an eighth of the other sublayers' and the bias 0.03: the logits
        # hardly hear of it; the expert FFN's OWN reading is what holds this control
        p = next(p for p in params["layers"] if "router" in p)
        f = jnp.asarray(np.random.default_rng(0).standard_normal((24, cfg.dim)), jnp.float32)
        have, _ = lfm2._ffn(cfg, p, f[None], jnp.ones((1, 24), bool), True)
        assert _rel(have[0], reference.expert_ffn(reference.sizes(model), p, f)[0]) < TOL
        assert _rel(have[0], controls.expert_ffn(model, p, f, variant)[0]) > 20 * TOL
        return
    logits = lfm2.forward(cfg, params, jnp.asarray(tokens))
    picks = [(i, t) for i in range(2) for t in (33, 34, 59)]
    want = controls.logits_at(toy, params, tokens, picks, variant)
    assert max(_rel(logits[i, t], w) for (i, t), w in zip(picks, want)) > 20 * TOL


def test_the_head_norms_are_a_head_s_own_and_not_the_whole_projection_s(model, cfg, params):
    """q and k are normalised over EACH head's numbers: heads of unlike size
    come out alike. ``models/llama.py``'s ``qk_norm`` (OLMoE) normalises the
    projection whole: another equation, told apart on the same activations."""
    p = next(p for p in params["layers"] if "wq" in p)
    rng = np.random.default_rng(3)
    u = jnp.asarray(rng.standard_normal((1, 12, cfg.dim)), jnp.float32)
    loud = {**p, "wq": p["wq"].at[:, 0].multiply(50.0)}  # one head fifty times as loud
    pos = jnp.arange(12)[None]
    q, _, _ = lfm2._qkv(cfg, loud, u, pos)
    q0, _, _ = lfm2._qkv(cfg, p, u, pos)
    assert _rel(q, q0) < 1e-4  # a head's norm takes its own size out, and no other head hears of it
    z = reference.sizes(model)
    want = reference.attention(z, loud, u[0])
    whole = controls.attention(model, loud, u[0], "qk_norm_whole_projection")
    cache = {k: v[:1] for k, v in lfm2.cache_layout(cfg, BS).init(4).items()}
    table = jnp.arange(1, 4, dtype=jnp.int32)[None]
    _, have = lfm2._attention_mix(cfg, loud, cache, 0, u, pos, jnp.ones((1, 12), bool), table)
    assert _rel(have[0], want) < TOL < 0.05 < _rel(have[0], whole)


# -- the convolution: a chunk, a step, the tail --------------------------------------------------------

@pytest.mark.parametrize("true_len", [1, 2, 3, 11, 16])
def test_a_padded_chunk_leaves_the_tail_at_its_last_real_inputs(true_len):
    """``short_conv.chunk``: the outputs of the real rows are the whole
    sequence's, whatever the padding holds, and the new tail is the last two
    REAL inputs (one real row keeps one input of the old tail)."""
    rng = np.random.default_rng(true_len)
    D, K = 32, 3
    z = jnp.asarray(rng.standard_normal((1, 16, D)), jnp.float32)
    tail = jnp.asarray(rng.standard_normal((1, K - 1, D)), jnp.float32)
    taps = jnp.asarray(rng.standard_normal((K, D)), jnp.float32)
    padded = z.at[:, true_len:].set(1e4)
    c, new_tail = short_conv.chunk(padded, tail, taps, jnp.full((1,), true_len, jnp.int32))
    window = np.concatenate([np.asarray(tail[0]), np.asarray(z[0])])
    want = sum(window[j : j + 16] * np.asarray(taps[j]) for j in range(K))
    np.testing.assert_allclose(np.asarray(c[0, :true_len]), want[:true_len], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(new_tail[0]), window[true_len : true_len + K - 1])
    assert bool(jnp.all(jnp.isfinite(c)))


def test_a_step_moves_its_own_rows_of_the_slab_and_no_other():
    """``short_conv.step``: a decode batch of three real rows and a padding
    row on the null slot, on a pool that holds trash: each real row's output
    and new tail are a chunk's of one position, a fresh row reads zeros, and
    no slot but the named ones (and the null slot) changes."""
    rng = np.random.default_rng(0)
    D, K, L = 32, 3, 2
    pool = jnp.asarray(rng.standard_normal((L, 6, (K - 1) * D)), jnp.float32)
    z = jnp.asarray(rng.standard_normal((4, D)), jnp.float32)
    taps = jnp.asarray(rng.standard_normal((K, D)), jnp.float32)
    slots, fresh = jnp.asarray([4, 0, 2, 5]), jnp.asarray([False, False, True, False])
    c, new = short_conv.step(pool, 1, slots, z, taps, fresh)
    for b in (0, 2, 3):
        s = int(slots[b])
        tail = jnp.zeros((1, K - 1, D)) if bool(fresh[b]) else pool[1, s].reshape(1, K - 1, D)
        want_c, want_tail = short_conv.chunk(z[b][None, None], tail, taps, jnp.ones((1,), jnp.int32))
        np.testing.assert_allclose(np.asarray(c[b]), np.asarray(want_c[0, 0]), rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(new[1, s]), np.asarray(want_tail).reshape(-1))
    untouched = [1, 3]
    np.testing.assert_array_equal(np.asarray(new[1, untouched]), np.asarray(pool[1, untouched]))
    np.testing.assert_array_equal(np.asarray(new[0]), np.asarray(pool[0]))  # the other layer's slab


# -- one chip's share of the experts ---------------------------------------------------------------

def test_two_shares_of_four_experts_sum_to_the_whole_layer(model):
    """Two chips each holding four of eight experts: their parts add up to
    the uncut reference's whole layer (no shared expert to count once)."""
    whole = dict(model, num_experts=8, deployment={**model["deployment"], "held_experts": [0, 8]})
    fam = families.of(whole)
    cfg = fam.model_config(whole, max_seq_len=64)
    p = lfm2.init_params(cfg, jax.random.PRNGKey(7))["layers"][2]
    h = jnp.asarray(np.random.default_rng(0).standard_normal((24, cfg.dim)), jnp.float32)
    want, _ = reference.expert_ffn(reference.sizes(whole), p, h)
    total = 0.0
    for lo, hi in ((0, 4), (4, 8)):
        share = dict(whole, num_experts=4, deployment={**whole["deployment"], "held_experts": [lo, hi]})
        c = fam.model_config(share, max_seq_len=64)
        held = {**p, **{k: p[k][lo:hi] for k in ("w_gate", "w_up", "w_down")}}
        out, aux = lfm2._ffn(c, held, h[None], jnp.ones((1, 24), bool), True)
        ref_share, _ = reference.expert_ffn(reference.sizes(share), held, h)
        assert _rel(out[0], ref_share) < TOL  # each share by itself is the reference's share
        total = total + np.asarray(out[0])
        assert int(aux["load"].sum()) == 24 * cfg.moe_top_k  # routed over all eight
        assert 0 < int(aux["load"][lo:hi].sum()) < 24 * cfg.moe_top_k  # some of them to the absent
    assert _rel(total, want) < TOL


# -- the check's drive: the pool as the serving programs leave it ------------------------------------

@pytest.mark.parametrize("fault", [None, "slot_mix_up", "oldest_tap_dropped", "tail_cut_at_padded_end"])
def test_the_pool_s_reading_tells_a_fault_on_the_serving_path(model, cfg, params, fault):
    """``families/lfm2/server.py::drive``: three sequences on scattered slots
    through the runner's own prefill and decode programs (a chunk edge, a
    chunk of ONE row, padded tails, six decode steps), then the pool's tails of
    the driven slots after the prefill and after the last step against the
    reference's ``z``. The model reads to float32's rounding; a sequence whose
    LAST step ran on another's slot reads orders above it in the pool after
    the decode (a tail is two positions deep: an earlier mix-up is overwritten
    by then, and only the logits of its step tell it), a tail cut at the padded
    end in the pool after the prefill."""
    from perfbench.families.lfm2 import server
    from ray_tpu.inference.model_runner import PagedModelRunner

    runner = PagedModelRunner(cfg, params, num_blocks=64, block_size=BS, prefill_buckets=(16, 32),
                              decode_buckets=(4,), state_slots=4)
    if fault == "slot_mix_up":
        decode, calls = runner.decode, []

        def mixed(*args, slots, **kw):
            calls.append(slots)
            return decode(*args, slots=slots[1:] + slots[:1] if len(calls) == 6 else slots, **kw)

        runner.decode = mixed
    variant = fault if fault in controls.VARIANTS else None
    got = server.drive(
        runner, model, 7, [40, 33, 20], 6,
        lambda m, p, t, picks, ats: controls.logits_at(m, p, t, picks, variant, ats),
    )
    assert [p for i, p in got["positions"] if i == 1] == [32, 33, 34, 38]  # the last prompt position, steps 0, 1 and 5
    state = got["state"]
    assert state["finite"] and len(state["by_layer"]["prefill"]) == len(state["by_layer"]["decode"]) == 5
    assert set(state["worst"]) == {"prefill.first", "prefill.deep", "decode.first", "decode.deep"}
    if fault is None:
        assert max(state["worst"].values()) < 1e-4 and max(got["rel_err"]) < TOL
    elif fault == "slot_mix_up":
        assert state["worst"]["prefill.deep"] < 1e-4 < 1e-2 < state["worst"]["decode.first"]
    elif fault == "tail_cut_at_padded_end":
        assert state["worst"]["prefill.first"] > 0.5 and max(got["rel_err"]) > 20 * TOL
    else:  # the tails are inputs, not outputs: a dropped tap shows in the layers behind the first, and in the logits
        assert state["worst"]["prefill.first"] < 1e-4 < 1e-3 < state["worst"]["decode.deep"]
        assert max(got["rel_err"]) > 20 * TOL


def test_each_mixer_alone_reads_the_reference_and_tells_its_control(model, cfg, params):
    """The three readings of a mixer ALONE (``server.py``) on the toy runner:
    the program's convolution (chunks with a padded tail and one row, then
    steps on a pool), its attention (a chunk, then decode steps over the
    cache) and its expert FFN read the reference to float32's rounding, and
    each control orders above it."""
    from perfbench.families.lfm2 import server
    from ray_tpu.inference.model_runner import PagedModelRunner

    runner = PagedModelRunner(cfg, params, num_blocks=64, block_size=BS, prefill_buckets=(16, 32),
                              decode_buckets=(4,), state_slots=4)
    fam = families.of(model)
    n2 = max(1, int(32 * server.TAIL_SHARE))
    edges = (32 + n2, 32 + n2 + 1)
    readings = {
        "conv": lambda v: server.conv_alone(runner, model, 3, lambda m, p, u: controls.conv(m, p, u, v, edges)),
        "attn": lambda v: server.attn_alone(runner, model, 3, lambda m, p, u: controls.attention(m, p, u, v)),
        "ffn": lambda v: server.expert_ffn_alone(runner, model, 3, lambda m, p, f: controls.expert_ffn(m, p, f, v)),
    }
    for name, variants in (("conv", ("oldest_tap_dropped", "tail_cut_at_padded_end")),
                           ("attn", ("qk_norm_whole_projection",)), ("ffn", ("gate_keeps_bias", "weights_fp8"))):
        right = readings[name](None)
        assert right["finite"] and max(right["worst"].values()) < TOL
        for variant in variants:
            assert max(readings[name](variant)["worst"].values()) > 20 * TOL, (name, variant)
    assert fam.reference_conv and fam.reference_attention and fam.reference_expert_ffn


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


@pytest.fixture(scope="module")
def wanted(cfg, params, prompts):
    forward = jax.jit(lambda p, t: lfm2.forward(cfg, p, t))
    return [_greedy(forward, params, p, 6) for p in prompts]


def test_the_engine_serves_through_slots_and_tells_of_both_layouts(cfg, params, prompts, wanted):
    """Two slots for five requests, with NO change to the engine for this
    kind of recurrent layer: requests wait for a slot, a slot is reused after
    a finish (its next holder's first chunk starts from zeros), nothing
    leaks, the tokens are the full forward pass's, and the launch spans' path
    names both mixers."""
    eng = _engine(cfg, params, max_decode_batch=2, decode_buckets=(2,))  # a slot a running sequence
    try:
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        assert [list(eng.tokens(r)) for r in rids] == wanted
        st = eng.stats()
        assert st["kv_layout"] == {"kind": "kv", "row_width": 2 * 2 * 64, "bytes_per_token": 2 * 2 * 2 * 64 * 4}
        assert st["state_layout"].pop("stored_bytes_per_seq") >= lfm2.state_layout(cfg).bytes_per_seq  # the toy's lanes pad
        assert st["state_layout"] == lfm2.state_layout(cfg).describe() == {
            "kind": "short_conv", "layers": 5, "bytes_per_seq": 5 * 2 * 256 * 4}
        pool = st["state_pool"]
        assert pool["slots"] == 2 and pool["peak_in_use"] == 2 and pool["in_use"] == 0
        assert pool["assigned"] == pool["released"] == 5 and pool["admission_waits"] == 3
        assert st["blocks"]["used_blocks"] == 0 and st["recompiles_after_warmup"] == 0
        assert st["prefix_cache"]["enabled"] is False  # switched off: no state snapshot a block
        assert st["moe"]["decode"]["launches"] > 0 and st["moe"]["decode"]["held_assignments"] < st["moe"]["decode"]["assignments"]
        assert eng.runner._path_name(1) == "conv.step+gather" and eng.runner._path_name(16) == "conv.chunk+gather"
        assert eng.runner.held_experts == (0, 4)
    finally:
        eng.stop()


def test_a_preempted_request_re_derives_its_state_from_position_zero(cfg, params, prompts):
    """A pool too small for two long requests at once: one is preempted
    (blocks and slot given back), re-admitted, and its tokens are those of an
    undisturbed run (its first chunk after re-admission starts from zeros)."""
    forward = jax.jit(lambda p, t: lfm2.forward(cfg, p, t))
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
    assert field in str(e.value) and "lfm2" in str(e.value)


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


# -- which path a program takes ---------------------------------------------------------------------

def test_the_attention_path_names_both_mixers_and_reads_blocks_where_the_kernels_serve():
    """At the published widths on a TPU a decode step takes the paged kernel
    (each slot's live blocks) and a chunk the flash kernel (the live key
    tiles); on the CPU, and at widths that are not whole lanes, the gather
    over the table. ``llama``'s own predicates answer as they did."""
    from ray_tpu.models import llama
    from ray_tpu.ops import latent_flash, paged_attention

    cfg = lfm2.Lfm2Config(dtype=jnp.bfloat16, held_experts=(0, 16))
    cache = jax.eval_shape(lambda: lfm2.cache_layout(cfg, 16).init(600))
    assert cache["k"].shape == (6, 600, 16, 512)
    path = lfm2.MODEL.attention_path
    assert path(cfg, 1, cache, backend="tpu") == ("conv.step+kernel", "blocks")
    assert path(cfg, 1, cache, backend="cpu") == ("conv.step+gather", "table")
    for window in (256, 1024):
        assert path(cfg, window, cache, backend="tpu") == ("conv.chunk+flash", "live")
        assert path(cfg, window, cache, backend="cpu") == ("conv.chunk+gather", "table")
    toy = lfm2.Lfm2Config.tiny()
    toy_cache = jax.eval_shape(lambda: lfm2.cache_layout(toy, 8).init(8))
    assert toy_cache["k"].shape == (2, 8, 8, 2, 16)  # no whole lanes: heads apart, the gather
    assert path(toy, 1, toy_cache, backend="tpu") == ("conv.step+gather", "table")
    assert lfm2.MODEL.key_tile(cfg, 1024, cache) == 1  # the CPU: the chunk is not the kernel's
    # the other configurations' answers: a head of 64 without its pairs, or without its width said, is refused as ever
    assert not latent_flash.kernel_serves(1024, 8192, 64, 64, 0, jnp.bfloat16, backend="tpu")
    k5 = jax.ShapeDtypeStruct((6, 600, 16, 8, 64), jnp.bfloat16)
    assert not paged_attention.kernel_serves(1, 32, k5, backend="tpu")
    mistral = llama.LlamaConfig(dim=4096, n_heads=32, n_kv_heads=8, dtype=jnp.bfloat16)
    k128 = jax.ShapeDtypeStruct((16, 600, 16, 8, 128), jnp.bfloat16)
    assert llama._attention_path(mistral, 1, {"k": k128}).name == "gather"  # the CPU
    assert model_of(cfg).name == "lfm2" and model_of(cfg).state_layout(cfg).kind == "short_conv"
