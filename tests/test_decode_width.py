"""Table-width buckets of the model runner (ISSUE 25): decode and verify
gather the block table only as wide as the rung that covers the batch's
longest context. Cluster-free and CPU-runnable; the model is tiny in every
width but ``max_seq_len`` 4096, so the ladder has the benchmark's two rungs
(2048 and 4096 tokens at ``block_size`` 16).

Where the paged-attention kernel serves decode and verify (ISSUE 30) the
width costs nothing: one full-width program a batch bucket, and the counter
says what the kernel reads. The last cases force that path on the CPU by
patching the selection predicate (the kernel then runs in Pallas' TPU
interpreter); the program has no option for it."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.inference.engine import EngineConfig, InferenceEngine  # noqa: E402
from ray_tpu.inference.model_runner import PagedModelRunner, table_width_ladder  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, init_params  # noqa: E402
from ray_tpu.ops import paged_attention as PA  # noqa: E402

BS = 16
NUM_BLOCKS = 400
COUNTERS = ("launches", "width_tokens", "needed_tokens", "live_tokens", "gathered_tokens")


@pytest.mark.parametrize(
    "max_seq_len, tokens",
    [
        (64, (64,)),
        (1024, (1024,)),
        (2048, (2048,)),
        (4096, (2048, 4096)),
        (32768, (2048, 4096, 8192, 16384, 32768)),
        (3000, (2048, 3008)),
    ],
)
def test_ladder_is_one_rung_to_2048_then_doublings_then_the_full_width(max_seq_len, tokens):
    ladder = table_width_ladder(max_seq_len, BS)
    assert tuple(w * BS for w in ladder) == tokens
    assert ladder[-1] == -(-max_seq_len // BS)  # the last rung is max_blocks_per_seq itself
    assert list(ladder) == sorted(set(ladder))


@pytest.fixture(scope="module")
def cfg():
    return LlamaConfig.tiny(max_seq_len=4096)


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(cfg, jax.random.PRNGKey(0))


def _runner(cfg, params):
    return PagedModelRunner(
        cfg, params, num_blocks=NUM_BLOCKS, block_size=BS, prefill_buckets=(64,),
        decode_buckets=(4,), verify_buckets=(4,),
    )


@pytest.fixture(scope="module")
def runners(cfg, params):
    """The runner as it is, and one held to the full width (its ladder cut to
    the last rung): the program every batch ran before there were rungs."""
    narrow, full = _runner(cfg, params), _runner(cfg, params)
    full.table_widths = full.table_widths[-1:]
    return narrow, full


def _fill(runner, seed=0):
    """A cache of noise, the same in every runner it is given to: what lies
    past a slot's context must not matter, so it is not zeros."""
    shape = runner.cache["k"].shape
    k, v = jax.random.normal(jax.random.PRNGKey(seed), (2, *shape), jnp.float32)
    runner.cache = {"k": k, "v": v}
    return np.asarray(k), np.asarray(v)


def _rows(runner, ctx_lens):
    """Distinct blocks for each slot's context, null-padded to the full width."""
    rows, nxt = [], 1
    for ctx in ctx_lens:
        n = -(-ctx // BS)
        rows.append(list(range(nxt, nxt + n)) + [0] * (runner.max_blocks_per_seq - n))
        nxt += n
    assert nxt <= NUM_BLOCKS
    return rows


def _written(runner, before):
    """Where the cache differs from ``before``: (k or v, layer, block, offset)."""
    now = (np.asarray(runner.cache["k"]), np.asarray(runner.cache["v"]))
    return {
        (which, *map(int, idx[:3]))
        for which in (0, 1)
        for idx in np.argwhere((now[which] != before[which]).any(axis=(3, 4)))
    }, now


def _both(runners, seed, call):
    """Run ``call(runner)`` on the same cache of noise in both runners:
    per runner its logits, where it wrote, the cache after, and what it
    added to its counter."""
    out = []
    for runner in runners:
        before = _fill(runner, seed)
        start = dict(runner.decode_width)
        logits = call(runner)
        counted = {k: runner.decode_width[k] - start[k] for k in COUNTERS}
        out.append((logits, *_written(runner, before), counted))
    return out


def _assert_same(narrow, full):
    """Float32 round-off: the two sum the same terms, and zeros past the
    context. The same K/V rows written, to the same values."""
    np.testing.assert_allclose(narrow[0], full[0], rtol=1e-5, atol=1e-5)
    assert narrow[1] == full[1]
    for x, y in zip(narrow[2], full[2]):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "ctx_lens, rung",
    [((5, 300, 2048), 2048), ((5, 300, 2049), 4096), ((5, 4096, 300), 4096)],
)
def test_decode_at_the_rung_is_decode_at_the_full_width(runners, ctx_lens, rung):
    narrow, full = _both(
        runners, 0,
        lambda r: r.decode(
            [7, 8, 9], [c - 1 for c in ctx_lens], _rows(r, ctx_lens), list(ctx_lens)
        ),
    )
    assert narrow[0].shape == (3, runners[0].cfg.vocab_size)
    _assert_same(narrow, full)
    # each slot's own position in every layer, K and V (the fourth slot is
    # padding and writes the null block)
    assert len(narrow[1]) == 2 * runners[0].cfg.n_layers * 4
    assert narrow[3] == {
        "launches": 1, "width_tokens": rung, "needed_tokens": max(ctx_lens),
        "live_tokens": sum(ctx_lens), "gathered_tokens": 4 * rung,
    }
    assert full[3] == {**narrow[3], "width_tokens": 4096, "gathered_tokens": 4 * 4096}


@pytest.mark.parametrize("ctx, rung", [(2044, 2048), (2046, 4096)])
def test_verify_at_the_rung_is_verify_at_the_full_width(runners, ctx, rung):
    """A window of 4 after ``ctx`` cached tokens: 2044 + 4 fits the 2048
    rung, 2046 + 4 crosses it. The second slot's window is shorter than
    the window bucket, the other two slots are padding."""
    windows = [[3, 4, 5, 6], [9, 8]]
    narrow, full = _both(
        runners, 1,
        lambda r: np.concatenate(
            r.verify_batch(windows, _rows(r, [ctx + 4, 42]), [ctx, 40])
        ),
    )
    assert narrow[0].shape == (4 + 2, runners[0].cfg.vocab_size)
    _assert_same(narrow, full)
    assert narrow[3] == {
        "launches": 1, "width_tokens": rung, "needed_tokens": ctx + 4,
        "live_tokens": ctx + 4 + 42, "gathered_tokens": 4 * rung,
    }
    assert full[3]["width_tokens"] == 4096


def test_counter_matches_a_hand_count_for_a_scripted_batch(cfg, params):
    runner = _runner(cfg, params)
    assert runner.decode_width == dict.fromkeys(COUNTERS, 0)
    script = [(5, 300, 2048), (5, 300, 2049), (17,), (4096, 2, 2, 2)]
    for ctx_lens in script:
        runner.decode(
            [1] * len(ctx_lens), [c - 1 for c in ctx_lens], _rows(runner, ctx_lens),
            list(ctx_lens),
        )
    # by hand: rungs 2048, 4096, 2048, 4096; one decode bucket of 4 slots
    assert runner.decode_width == {
        "launches": 4,
        "width_tokens": 2048 + 4096 + 2048 + 4096,
        "needed_tokens": 2048 + 2049 + 17 + 4096,
        "live_tokens": 2353 + 2354 + 17 + 4102,
        "gathered_tokens": 4 * (2048 + 4096 + 2048 + 4096),
    }
    # two shapes of one program, compiled as they came (no warm-up here)
    assert runner.compile_count() == 2


def _engine(cfg, params):
    return InferenceEngine(
        cfg, params,
        EngineConfig(
            num_blocks=NUM_BLOCKS, block_size=BS, prefill_buckets=(64, 256), decode_buckets=(4,),
            max_decode_batch=4, prefix_cache_enabled=False,
        ),
    ).start()


@pytest.fixture(scope="module")
def engine(cfg, params):
    eng = _engine(cfg, params)
    try:
        yield eng
    finally:
        eng.stop()


def test_a_request_growing_over_a_rung_compiles_nothing(engine):
    """A full ``warmup()`` holds every (decode bucket x rung) pair: a context
    that grows from under 2048 to over it changes program, not the count."""
    runner = engine.runner
    assert runner.table_widths == (128, 256)
    # prefill buckets + decode buckets x rungs + the COW copy
    assert runner.compile_count() == 2 + 1 * 2 + 1
    assert {f"paged_decode_step[4x{t}]" for t in (2048, 4096)} <= set(
        engine.stats()["startup"]["warmup_programs"]
    )
    calls = []
    decode = runner.launch_decode  # the engine launches and reads in two calls since ISSUE 39
    runner.launch_decode = lambda *a, **kw: calls.append(max(a[3])) or decode(*a, **kw)
    try:
        prompt = [int(t) for t in np.random.RandomState(0).randint(1, 256, size=2034)]
        start = engine.stats()["decode_width"]
        tokens = list(engine.generate(prompt, max_new_tokens=30))
        end = engine.stats()["decode_width"]
    finally:
        runner.launch_decode = decode
    assert len(tokens) == 30
    got = {k: end[k] - start[k] for k in COUNTERS}
    # the first token comes from prefill; each later one from a decode launch
    # whose context holds the prompt and what was generated: 2035 ... 2063
    assert calls == list(range(2035, 2064))
    under = sum(1 for c in calls if c <= 2048)
    assert 0 < under < len(calls)
    assert got == {
        "launches": len(calls),
        "width_tokens": 2048 * under + 4096 * (len(calls) - under),
        "needed_tokens": sum(calls),
        "live_tokens": sum(calls),
        "gathered_tokens": 4 * (2048 * under + 4096 * (len(calls) - under)),
    }
    assert got["needed_tokens"] <= got["width_tokens"]
    assert got["live_tokens"] <= got["gathered_tokens"]
    assert engine.stats()["recompiles_after_warmup"] == 0
    assert runner.compile_count() == 2 + 1 * 2 + 1


# -- the kernel path, forced: the width costs nothing ---------------------------------

@pytest.fixture
def kernel_forced(monkeypatch):
    """The predicate as it reads on a TPU at whole tiles: short windows take
    the kernel, a prefill chunk the gather."""
    monkeypatch.setattr(
        PA, "kernel_serves",
        lambda window, n_heads, k_cache, backend=None: window * n_heads <= 64,
    )


@pytest.mark.parametrize(
    "script",
    [[(5, 300, 2048, 1000), (5, 300, 2049, 1000)], [(17, 4096, 2, 33), (2047, 40, 2049, 900)]],
    ids=["over_the_2048_rung", "the_full_width_and_back"],
)
def test_kernel_decode_is_one_program_and_counts_live_blocks(cfg, params, runners, kernel_forced, script):
    """Full buckets whose longest context crosses what used to be a rung: the
    logits are the ladder's, ONE program serves both, and the counter reads
    each slot's live blocks."""
    runner = _runner(cfg, params)
    assert [runner.attention_paths[c] for c in (1, 4)] == [("kernel", "blocks")] * 2 and runner.table_widths == (256,)
    for ctx_lens in script:
        call = lambda r: r.decode(  # noqa: E731
            [7, 8, 9, 10], [c - 1 for c in ctx_lens], _rows(r, ctx_lens), list(ctx_lens)
        )
        kernel, ladder = _both((runner, runners[0]), 2, call)
        _assert_same(kernel, ladder)
        blocks = sum(-(-c // BS) for c in ctx_lens)
        assert kernel[3] == {
            "launches": 1, "width_tokens": 4096, "needed_tokens": max(ctx_lens),
            "live_tokens": sum(ctx_lens), "gathered_tokens": blocks * BS,
        }
        mean = sum(ctx_lens) / len(ctx_lens)
        assert kernel[3]["live_tokens"] / kernel[3]["gathered_tokens"] >= 1 - BS / mean
    assert runner.compile_count() == 1  # the ladder compiled one a rung
    assert [runners[0].attention_paths[c] for c in (1, 4)] == [("gather", "table")] * 2


def test_kernel_reads_nothing_for_a_padding_slot_and_serves_verify(cfg, params, runners, kernel_forced):
    runner = _runner(cfg, params)
    windows = [[3, 4, 5, 6], [9, 8]]
    call = lambda r: np.concatenate(  # noqa: E731
        r.verify_batch(windows, _rows(r, [2046 + 4, 42]), [2046, 40])
    )
    kernel, ladder = _both((runner, runners[0]), 1, call)
    # the null block is where the two differ: a padding slot's trash is the
    # kernel's zeros through a layer there, the gather's attention elsewhere
    null = lambda written: {w for w in written if w[2] == 0}  # noqa: E731
    assert null(kernel[1]) == null(ladder[1])
    sides = [
        (logits, written - null(written), [np.delete(x, 0, axis=1) for x in cache])
        for logits, written, cache, _ in (kernel, ladder)
    ]
    _assert_same(*sides)
    assert ladder[3]["width_tokens"] == 4096  # 2046 + 4 crossed the rung there
    # 129 + 3 live blocks; the two padding slots read nothing
    assert kernel[3] == {
        "launches": 1, "width_tokens": 4096, "needed_tokens": 2050,
        "live_tokens": 2050 + 42, "gathered_tokens": (129 + 3) * BS,
    }


def test_kernel_engine_warms_one_decode_program_and_never_recompiles(cfg, params, engine, kernel_forced):
    """The request of ``test_a_request_growing_over_a_rung_compiles_nothing``
    on an engine whose decode takes the kernel: one decode program instead of
    two, the same greedy tokens, nothing compiled on the way over 2048."""
    eng = _engine(cfg, params)
    try:
        runner = eng.runner
        assert runner.table_widths == (256,)
        assert runner.compile_count() == 2 + 1 + 1  # prefill buckets, ONE decode program, the COW copy
        programs = set(eng.stats()["startup"]["warmup_programs"])
        assert "paged_decode_step[4x4096]" in programs and "paged_decode_step[4x2048]" not in programs
        prompt = [int(t) for t in np.random.RandomState(0).randint(1, 256, size=2034)]
        start = eng.stats()["decode_width"]
        tokens = list(eng.generate(prompt, max_new_tokens=30))
        end = eng.stats()["decode_width"]
        assert tokens == list(engine.generate(prompt, max_new_tokens=30))  # the ladder's engine
        got = {k: end[k] - start[k] for k in COUNTERS}
        contexts = range(2035, 2064)
        assert got == {
            "launches": 29, "width_tokens": 29 * 4096, "needed_tokens": sum(contexts),
            "live_tokens": sum(contexts),
            "gathered_tokens": sum(-(-c // BS) for c in contexts) * BS,  # three padding slots read nothing
        }
        assert eng.stats()["recompiles_after_warmup"] == 0
        assert runner.compile_count() == 2 + 1 + 1
    finally:
        eng.stop()
