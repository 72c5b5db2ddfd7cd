"""``models/jamba.py`` (Mamba-1 selective state-space layers with a
per-sequence state pool of two arrays beside multi-query attention layers
without positions and a paged K/V cache of ONE head, a dense MLP, a tied head)
against the plain reference of its family,
``perfbench/families/jamba/reference.py``, on the CPU at a small size: float32
against float32, seeded weights. ``ops/selective_scan.py``'s two kernels in
Pallas' TPU interpreter against their ``jnp`` forms. And the state slots
through the engine: the THIRD kind of state in the pool (``mamba1``)."""

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

import jamba_controls as controls  # noqa: E402
import rehearsal  # noqa: E402
from perfbench import families  # noqa: E402
from perfbench.families.jamba import reference  # noqa: E402
from ray_tpu.inference import EngineConfig  # noqa: E402
from ray_tpu.inference.engine import InferenceEngine  # noqa: E402
from ray_tpu.models import jamba  # noqa: E402
from ray_tpu.models.interface import model_of  # noqa: E402
from ray_tpu.ops import selective_scan  # noqa: E402

CONFIG = "ai21-jamba2-3b"
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
    return jamba.init_params(cfg, jax.random.PRNGKey(5))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(11).integers(1, 256, size=(2, 60)).astype(np.int32)


def _rel(have, want):
    return float(np.max(np.abs(np.asarray(have) - np.asarray(want))) / np.max(np.abs(np.asarray(want))))


# -- the whole model through both pools ----------------------------------------------------------

def _steps(cfg):
    prefill = jax.jit(lambda p, c, s, *a: jamba.paged_prefill_step(cfg, p, c, s, *a), donate_argnums=(1, 2))
    decode = jax.jit(lambda p, c, s, *a: jamba.paged_decode_step(cfg, p, c, s, *a), donate_argnums=(1, 2))
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
    return np.asarray(state["ssm"][layer, slot]).reshape(-1), np.asarray(state["conv_tail"][layer, slot])


@pytest.mark.parametrize("chunks", [(37,), (36, 1), (35, 2), (34, 3), (16, 16, 5), (7, 1, 2, 3, 24), (1, 1, 1, 34)],
                         ids=lambda c: "+".join(map(str, c)))
def test_chunked_prefill_then_decode_match_the_reference(model, cfg, params, tokens, chunks):
    """Chunks of 1, 2 and 3 rows (shorter than the taps) with a padded tail,
    whose edges split a block of 8, then three decode steps, through the K/V
    cache AND the state slots (a slot that held another sequence's trash),
    against the reference's full forward pass: logits, not tokens; and the
    state and the tail the pool is left with, against the reference's."""
    n = sum(chunks)
    table = np.arange(1, 9, dtype=np.int32)
    cache = jamba.cache_layout(cfg, BS).init(16)
    assert cache["k"].shape == (1, 16, BS, 1, 16)  # ONE attending layer of five, one KV head
    state = jax.tree_util.tree_map(lambda a: a + 3.0, jamba.state_layout(cfg).init(4))  # trash in every slot
    assert state["ssm"].shape == (4, 4, 4, 1, 128) and state["conv_tail"].shape == (4, 4, 3 * 128)
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
    for layer, ((h, tail),) in enumerate(kept[0]):
        have_h, have_tail = _kept_of(state, layer, 2)
        assert _rel(have_h, h.reshape(-1)) < TOL and _rel(have_tail, tail.reshape(-1)) < TOL
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
    cache, state = jamba.cache_layout(cfg, BS).init(16), jamba.state_layout(cfg).init(4)
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
    logits = jamba.forward(cfg, params, jnp.asarray(tokens))
    picks = [(i, t) for i in range(2) for t in (0, 1, 2, 31, 59)]
    want = reference.logits_at(model, params, tokens, picks)
    for (i, t), w in zip(picks, want):
        assert _rel(logits[i, t], w) < TOL
    n = sum(x.size for x in jax.tree_util.tree_leaves(params))
    assert n == jamba.param_count(cfg) == families.of(model).param_count(model)
    assert "lm_head" not in params  # the embedding is the head
    assert cfg.kinds == ("mamba", "mamba", "attn", "mamba", "mamba")
    axes = jamba.logical_axes(cfg)
    assert jax.tree_util.tree_structure(jax.tree_util.tree_map(lambda _: 0, params)) == jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda _: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))
    loss = reference.next_token_loss(model, params, tokens[:, :-1], tokens[:, 1:])
    assert abs(loss - np.log(256)) < 1.0
    # Mamba's initialisation: A = -(1 .. N) a state index, the step size in [1e-3, 1e-1] where the projection adds nothing
    p = params["layers"][0]
    np.testing.assert_allclose(np.exp(np.asarray(p["A_log"][:, 7])), np.arange(1, cfg.d_state + 1), rtol=1e-6)
    dt0 = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert 1e-3 * 0.99 <= dt0.min() < 5e-3 and 5e-2 < dt0.max() <= 1e-1 * 1.01


@pytest.mark.parametrize("variant", controls.VARIANTS)
def test_every_control_reads_not_correct(model, cfg, params, tokens, variant):
    """Each wrong twin of the reference is told from the program by the
    logits of a full forward pass, float32 against float32: orders above the
    model's own reading. (``carry_dropped`` and ``padded_row_advances``: the
    prompt of 33 = 32 + 1 crosses a chunk edge and ends a padded chunk, and
    the positions behind see the difference. The precision twins read least
    over 60 positions, 5e-4 and 2e-3: hundreds of times the model's 1e-6.)"""
    toy = dict(model, correctness={**model["correctness"], "prompt_lens": [33, 33]})
    logits = jamba.forward(cfg, params, jnp.asarray(tokens))
    picks = [(i, t) for i in range(2) for t in (33, 34, 59)]
    right = reference.logits_at(model, params, tokens, picks)
    assert max(_rel(logits[i, t], w) for (i, t), w in zip(picks, right)) < TOL / 10
    want = controls.logits_at(toy, params, tokens, picks, variant)
    assert max(_rel(logits[i, t], w) for (i, t), w in zip(picks, want)) > TOL


def test_attention_has_no_position_term(model, cfg, params):
    """The same activations at other positions of a sequence give the same q,
    k and v: only the causal mask knows the order. A rotary twin is told."""
    p = next(p for p in params["layers"] if "wq" in p)
    u = jnp.asarray(np.random.default_rng(3).standard_normal((1, 12, cfg.dim)), F32)
    cache = {k: v[:1] for k, v in jamba.cache_layout(cfg, BS).init(8).items()}
    table = jnp.arange(1, 7, dtype=jnp.int32)[None]
    valid = jnp.ones((1, 12), bool)
    _, at0 = jamba._attention_mix(cfg, p, cache, 0, u, jnp.arange(12)[None], valid, table)
    want = reference.attention(reference.sizes(model), p, u[0])
    assert _rel(at0[0], want) < TOL < 0.05 < _rel(at0[0], controls.attention(model, p, u[0], "rotary_added"))


# -- the recurrence: the kernels against the plain form -----------------------------------------------

def _scan_inputs(rng, T, N, D):
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (T, D))), F32)
    x, Bm, Cm = (jnp.asarray(rng.standard_normal(s), F32) for s in ((T, D), (T, N), (T, N)))
    A = -jnp.exp(jnp.asarray(rng.standard_normal((N, D)) * 0.3, F32))
    return dt, x, Bm, Cm, A


@pytest.mark.parametrize("fresh", [False, True])
@pytest.mark.parametrize("positions", [8, 16])
def test_the_scan_kernel_is_the_plain_recurrence_and_writes_one_slot(monkeypatch, fresh, positions):
    """``ssm_scan`` in Pallas' TPU interpreter over two blocks of channels and
    one or two blocks of positions: ``y`` and the slot's new state are the
    ``jnp`` form's, rows with ``dt = 0`` (the padding) leave the state where it
    was, and no other slot or layer of the pool changes."""
    monkeypatch.setattr(selective_scan, "_SCAN_POSITIONS", positions)
    rng = np.random.default_rng(positions)
    N, D, T = 4, 2048, 16
    pool = jnp.asarray(rng.standard_normal((2, 5, *selective_scan.state_shape(N, D))), F32)
    dt, x, Bm, Cm, A = _scan_inputs(rng, T, N, D)
    dt = dt.at[11:].set(0.0)
    y0, p0 = selective_scan.chunk(pool, 1, 3, fresh, dt, x, Bm, Cm, A, kernel=False)
    y1, p1 = selective_scan.chunk(pool, 1, 3, fresh, dt, x, Bm, Cm, A, kernel=True, interpret=True)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p0), rtol=2e-5, atol=2e-5)
    untouched = np.asarray(pool).copy()
    got = np.asarray(p1).copy()
    got[1, 3] = untouched[1, 3] = 0.0
    np.testing.assert_array_equal(got, untouched)
    # the padded rows did not advance it: the state after 11 rows alone
    _, short = selective_scan.chunk(pool, 1, 3, fresh, dt[:11], x[:11], Bm[:11], Cm[:11], A, kernel=False)
    np.testing.assert_allclose(np.asarray(p1[1, 3]), np.asarray(short[1, 3]), rtol=2e-5, atol=2e-5)


def test_the_update_kernel_moves_its_own_slots_of_the_slab_and_no_other():
    """``ssm_update`` in the interpreter: three real rows and padding on the
    null slot, on a pool that holds trash: each real row's output and new
    state are the ``jnp`` form's, a fresh row reads zeros, and no slot but the
    named ones (and the null slot) changes."""
    rng = np.random.default_rng(0)
    N, D = 4, 1024
    pool = jnp.asarray(rng.standard_normal((2, 6, *selective_scan.state_shape(N, D))), F32)
    dt, x, Bm, Cm, A = _scan_inputs(rng, 4, N, D)
    slots, fresh = jnp.asarray([4, 0, 2, 5], jnp.int32), jnp.asarray([False, False, True, False])
    y0, p0 = selective_scan.step(pool, 1, slots, fresh, dt, x, Bm, Cm, A, kernel=False)
    y1, p1 = selective_scan.step(pool, 1, slots, fresh, dt, x, Bm, Cm, A, kernel=True, interpret=True)
    real = [0, 2, 3]
    np.testing.assert_allclose(np.asarray(y1)[real], np.asarray(y0)[real], rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(p1[:, 1:]), np.asarray(p0[:, 1:]), rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(p1[1, [1, 3]]), np.asarray(pool[1, [1, 3]]))
    np.testing.assert_array_equal(np.asarray(p1[0]), np.asarray(pool[0]))  # the other layer's slab
    # the fresh row started from zeros: its state is its own input alone
    h = (dt[2] * x[2])[None] * Bm[2][:, None]
    np.testing.assert_allclose(np.asarray(p1[1, 2]).reshape(N, D), np.asarray(h), rtol=2e-5, atol=2e-6)


def test_what_takes_the_kernels_is_decided_from_shapes_and_backend():
    """The published widths on a TPU (a float32 pool, the channels of a state
    index in whole registers); not the CPU, not toy widths, not another dtype.
    The state is stored with the state index major and no lane padded."""
    assert selective_scan.state_shape(16, 5120) == (16, 40, 128)
    assert selective_scan.state_shape(4, 96) == (4, 1, 96)
    pool = jax.ShapeDtypeStruct((26, 257, 16, 40, 128), F32)
    assert selective_scan.kernel_serves(pool, "tpu") and not selective_scan.kernel_serves(pool, "cpu")
    assert not selective_scan.kernel_serves(jax.ShapeDtypeStruct((4, 5, 4, 1, 128), F32), "tpu")  # 128 channels: no whole register
    assert not selective_scan.kernel_serves(jax.ShapeDtypeStruct((26, 257, 16, 40, 128), jnp.bfloat16), "tpu")
    with pytest.raises(ValueError, match="whole block"):
        selective_scan.chunk(jnp.zeros((1, 2, 4, 8, 128)), 0, 1, False, *(jnp.zeros(s) for s in (
            (300, 1024), (300, 1024), (300, 4), (300, 4), (4, 1024))), kernel=True, interpret=True)


# -- the check's drive: the pool as the serving programs leave it ------------------------------------

def _runner(cfg, params):
    from ray_tpu.inference.model_runner import PagedModelRunner

    return PagedModelRunner(cfg, params, num_blocks=64, block_size=BS, prefill_buckets=(16, 32),
                            decode_buckets=(4,), state_slots=4)


@pytest.mark.parametrize("fault", [None, "slot_mix_up", "carry_dropped", "padded_row_advances", "state_bf16"])
def test_the_pool_s_reading_tells_a_fault_on_the_serving_path(model, cfg, params, fault):
    """``families/jamba/server.py::drive``: three sequences on scattered slots
    through the runner's own prefill and decode programs (a chunk edge, a
    chunk of ONE row, padded tails, six decode steps), then the pool's state
    and tail of the driven slots after the prefill and after the last step
    against the reference's. The model reads to float32's rounding; a sequence
    whose LAST step ran on another's slot reads orders above it in the pool
    after the decode; a dropped carry, a padded row that advances and a state
    kept in bfloat16 in the state after the prefill (the first layer's tail
    hears of none: the deeper layers' tails are inputs that the layers before
    them made)."""
    from perfbench.families.jamba import server

    runner = _runner(cfg, params)
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
    assert [p for i, p in got["positions"] if i == 1] == [32, 33, 34, 35, 36, 37, 38]  # the last prompt position, every step
    state = got["state"]
    assert state["finite"] and all(len(v) == 4 for v in state["by_layer"].values())
    assert set(state["worst"]) == {f"{i}.{a}.{d}" for i in ("prefill", "decode") for a in ("ssm", "tail")
                                   for d in ("first", "deep")}
    if fault is None:
        assert max(state["worst"].values()) < 1e-4 and max(got["rel_err"]) < TOL
    elif fault == "slot_mix_up":
        assert max(state["worst"]["prefill.ssm.deep"], state["worst"]["prefill.tail.deep"]) < 1e-4
        assert min(state["worst"]["decode.ssm.first"], state["worst"]["decode.tail.first"]) > 1e-2
    else:
        assert state["worst"]["prefill.tail.first"] < 1e-4 < 1e-3 < state["worst"]["prefill.ssm.first"]
        assert max(got["rel_err"]) > 5 * TOL


def test_each_mixer_alone_reads_the_reference_and_tells_its_control(model, cfg, params):
    """The two readings of a mixer ALONE (``server.py``) on the toy runner:
    the program's Mamba mixer (chunks with a padded tail and one row, then
    steps on pools of its own) and its attention (a chunk, then decode steps
    over the cache) read the reference to float32's rounding, and each
    control orders above it."""
    from perfbench.families.jamba import server

    runner = _runner(cfg, params)
    n2 = max(1, int(32 * server.TAIL_SHARE))
    starts, padded = (32, 32 + n2, 32 + n2 + 1), {32 + n2: 32 - n2, 32 + n2 + 1: 15}
    readings = {
        "mamba": lambda v: server.mamba_alone(runner, model, 3, lambda m, p, u: controls.mamba(m, p, u, v, starts, padded)),
        "attn": lambda v: server.attn_alone(runner, model, 3, lambda m, p, u: controls.attention(m, p, u, v)),
    }
    for name, variants in (
        ("mamba", ("state_bf16", "decay_bf16", "inner_norm_left_out", "conv_bias_left_out", "carry_dropped",
                   "padded_row_advances", "weights_fp8")),
        ("attn", ("rotary_added", "weights_fp8")),
    ):
        right = readings[name](None)
        assert right["finite"] and max(right["worst"].values()) < TOL / 10
        for variant in variants:  # the decay in bfloat16 reads least over these 59 positions: 7e-4
            assert max(readings[name](variant)["worst"].values()) > 2 * TOL, (name, variant)


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
    leaks, the tokens are the full forward pass's, and the launch spans' path
    names both mixers."""
    forward = jax.jit(lambda p, t: jamba.forward(cfg, p, t))
    wanted = [_greedy(forward, params, p, 6) for p in prompts]
    eng = _engine(cfg, params, max_decode_batch=2, decode_buckets=(2,))  # a slot a running sequence
    try:
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        assert [list(eng.tokens(r)) for r in rids] == wanted
        st = eng.stats()
        assert st["kv_layout"] == {"kind": "kv", "row_width": 2 * 16, "bytes_per_token": 2 * 16 * 4}
        assert st["state_layout"].pop("stored_bytes_per_seq") >= jamba.state_layout(cfg).bytes_per_seq  # the toy's lanes pad
        assert st["state_layout"] == jamba.state_layout(cfg).describe() == {
            "kind": "mamba1", "layers": 4, "bytes_per_seq": 4 * (4 * 128 * 4 + 3 * 128 * 4)}
        pool = st["state_pool"]
        assert pool["slots"] == 2 and pool["peak_in_use"] == 2 and pool["in_use"] == 0
        assert pool["assigned"] == pool["released"] == 5 and pool["admission_waits"] == 3
        assert st["blocks"]["used_blocks"] == 0 and st["recompiles_after_warmup"] == 0
        assert st["prefix_cache"]["enabled"] is False  # switched off: no state snapshot a block
        assert eng.runner._path_name(1) == "ssm.update+gather" and eng.runner._path_name(16) == "ssm.scan+gather"
        assert eng.runner.held_experts is None
    finally:
        eng.stop()


def test_a_preempted_request_re_derives_its_state_from_position_zero(cfg, params, prompts):
    """A pool too small for two long requests at once: one is preempted
    (blocks and slot given back), re-admitted, and its tokens are those of an
    undisturbed run (its first chunk after re-admission starts from zeros)."""
    forward = jax.jit(lambda p, t: jamba.forward(cfg, p, t))
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
    assert field in str(e.value) and "jamba" in str(e.value)


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
    """At the published widths on a TPU a decode step takes the update kernel
    and the paged kernel (each slot's live blocks: ONE KV head under 20 query
    heads, a block stored flat as one whole tile) and a chunk the scan kernel
    and the flash kernel (the live key tiles); on the CPU, and at toy widths,
    the ``jnp`` recurrence and the gather over the table."""
    cfg = jamba.JambaConfig(dtype=jnp.bfloat16)
    cache = jax.eval_shape(lambda: jamba.cache_layout(cfg, 16).init(600))
    assert cache["k"].shape == (2, 600, 16, 128)  # two attending layers; a block [16 x 1, 128]
    layout, state = jamba.cache_layout(cfg, 16), jamba.state_layout(cfg)
    assert layout.bytes_per_token == 1024 and layout.block_bytes == 16_384 and layout.n_layers == 2
    assert state.describe() == {"kind": "mamba1", "layers": 26, "bytes_per_seq": 9_318_400}
    assert [(n, s) for n, s, _ in state.arrays] == [("ssm", (16, 40, 128)), ("conv_tail", (3 * 5120,))]
    path = jamba.MODEL.attention_path
    assert path(cfg, 1, cache, backend="tpu") == ("ssm.update.kernel+kernel", "blocks")
    assert path(cfg, 1, cache, backend="cpu") == ("ssm.update+gather", "table")
    for window in (256, 1024):
        assert path(cfg, window, cache, backend="tpu") == ("ssm.scan.kernel+flash", "live")
        assert path(cfg, window, cache, backend="cpu") == ("ssm.scan+gather", "table")
    toy = jamba.JambaConfig.tiny()
    toy_cache = jax.eval_shape(lambda: jamba.cache_layout(toy, 8).init(8))
    assert toy_cache["k"].shape == (1, 8, 8, 1, 16)  # no whole lanes: the gather
    assert path(toy, 1, toy_cache, backend="tpu") == ("ssm.update+gather", "table")
    assert jamba.MODEL.key_tile(cfg, 1024, cache) == 1  # the CPU: the chunk is not the kernel's
    assert model_of(cfg).name == "jamba" and model_of(cfg).state_layout(cfg).kind == "mamba1"
    assert jamba.param_count(cfg) == 3_029_337_472
