"""What a decode or verify launch is handed and what it reads (ISSUE 25, 30,
48): the block table at ONE width, ``max_blocks_per_seq``, whatever the
batch's contexts, so ONE program a batch bucket on every backend; the gather
reads the table as wide as it is, and ``decode_width`` counts that.
Cluster-free and CPU-runnable; the model is tiny in every width but
``max_seq_len`` 4096 (the benchmark's table at ``block_size`` 16).

Where the paged-attention kernel serves decode and verify (ISSUE 30) a slot
reads its own live blocks alone, and the counter says so. The last cases force
that path on the CPU by patching the selection predicate (the kernel then runs
in Pallas' TPU interpreter) and hold the gather, the only fallback, against
it; the program has no option for either."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.inference.engine import EngineConfig, InferenceEngine  # noqa: E402
from ray_tpu.inference.model_runner import PagedModelRunner  # noqa: E402
from ray_tpu.models.llama import LlamaConfig, init_params  # noqa: E402
from ray_tpu.ops import paged_attention as PA  # noqa: E402

BS = 16
NUM_BLOCKS = 400
COUNTERS = ("launches", "width_tokens", "needed_tokens", "live_tokens", "gathered_tokens", "multiplied_tokens")
#: a K/V cache counts the kernel's waves too (ISSUE 57); the gather has none
WAVES = ("waves", "single_wait_waves")


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
def gather(cfg, params):
    """The runner as it is off the chip: decode and verify gather the table."""
    runner = _runner(cfg, params)
    assert [runner.attention_paths[c] for c in (1, 4)] == [("gather", "table")] * 2
    return runner


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


def _assert_same(one, other):
    """Float32 round-off: the two sum the same terms, and zeros past the
    context. The same K/V rows written, to the same values."""
    np.testing.assert_allclose(one[0], other[0], rtol=1e-5, atol=1e-5)
    assert one[1] == other[1]
    for x, y in zip(one[2], other[2]):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-5)


def test_counter_matches_a_hand_count_for_a_scripted_batch(cfg, params):
    runner = _runner(cfg, params)
    assert runner.decode_width == dict.fromkeys(COUNTERS + WAVES, 0)
    script = [(5, 300, 2048), (5, 300, 2049), (17,), (4096, 2, 2, 2)]
    for ctx_lens in script:
        runner.decode(
            [1] * len(ctx_lens), [c - 1 for c in ctx_lens], _rows(runner, ctx_lens),
            list(ctx_lens),
        )
    # by hand: the table's 4096 a launch; one decode bucket of 4 slots, padding and all
    assert runner.decode_width == {
        "launches": 4,
        "width_tokens": 4 * 4096,
        "needed_tokens": 2048 + 2049 + 17 + 4096,
        "live_tokens": 2353 + 2354 + 17 + 4102,
        "gathered_tokens": 4 * 4 * 4096,
        "multiplied_tokens": 4 * 4 * 4096,  # the gather multiplies what it reads
        "waves": 0, "single_wait_waves": 0,
    }
    # one shape of one program, whatever the contexts (no warm-up here)
    assert runner.compile_count() == 1


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


def test_a_request_growing_through_2048_compiles_nothing(engine):
    """Off the chip too ``warmup()`` compiles ONE decode program a batch
    bucket, at the table's width: a context that grows from under 2048 (once a
    rung of its own) to over it runs that program throughout."""
    runner = engine.runner
    assert runner.attention_paths[1] == ("gather", "table")
    # prefill buckets + decode buckets + the COW copy
    assert runner.compile_count() == 2 + 1 + 1
    assert [p for p in engine.stats()["startup"]["warmup_programs"] if p.startswith("paged_decode_step")] == [
        "paged_decode_step[4x4096]"
    ]
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
        "width_tokens": 4096 * len(calls),
        "needed_tokens": sum(calls),
        "live_tokens": sum(calls),
        "gathered_tokens": 4 * 4096 * len(calls),
        "multiplied_tokens": 4 * 4096 * len(calls),
    }
    assert engine.stats()["recompiles_after_warmup"] == 0
    assert runner.compile_count() == 2 + 1 + 1


# -- the kernel path, forced: the width costs nothing ---------------------------------

@pytest.fixture
def kernel_forced(monkeypatch):
    """The predicate as it reads on a TPU at whole tiles: short windows take
    the kernel, a prefill chunk the gather."""
    monkeypatch.setattr(
        PA, "kernel_serves",
        lambda window, n_heads, k_cache, backend=None, n_kv=None, head_dim=None: window * n_heads <= 64,
    )


def _but_the_null_block(side):
    """A side of :func:`_both` without the null block: where a batch has
    padding slots the two paths differ there alone (a padding slot's trash is
    the kernel's zeros through a layer, the gather's attention elsewhere)."""
    logits, written, cache, _ = side
    return logits, {w for w in written if w[2] != 0}, [np.delete(x, 0, axis=1) for x in cache]


@pytest.mark.parametrize(
    "script",
    [
        [(5, 300, 2048, 1000), (5, 300, 2049, 1000)], [(17, 4096, 2, 33), (2047, 40, 2049, 900)],
        [(5, 300, 2048)], [(5, 300, 2049)], [(5, 4096, 300)],
    ],
    ids=["through_2048", "the_full_width_and_back", "a_padding_slot_to_2048", "a_padding_slot_over_2048",
         "a_padding_slot_at_the_full_width"],
)
def test_kernel_decode_is_one_program_and_counts_live_blocks(cfg, params, gather, kernel_forced, script):
    """Batches whose longest context crosses 2048 or fills the table: the
    gather at the table's width, the only fallback, gives the kernel's logits
    and writes the kernel's rows; ONE program serves every batch on either
    path; the kernel's counter reads each slot's live blocks, the gather's the
    batch bucket at the table's width."""
    runner = _runner(cfg, params)
    assert [runner.attention_paths[c] for c in (1, 4)] == [("kernel", "blocks")] * 2
    compiled = gather.compile_count()
    for ctx_lens in script:
        call = lambda r: r.decode(  # noqa: E731
            list(range(7, 7 + len(ctx_lens))), [c - 1 for c in ctx_lens], _rows(r, ctx_lens), list(ctx_lens)
        )
        kernel, table = _both((runner, gather), 2, call)
        assert kernel[0].shape == (len(ctx_lens), cfg.vocab_size)
        _assert_same(_but_the_null_block(kernel), _but_the_null_block(table))
        # each slot's own position in every layer, K and V (a padding slot writes the null block)
        assert len(table[1]) == 2 * cfg.n_layers * 4
        blocks = sum(-(-c // BS) for c in ctx_lens)
        assert kernel[3] == {
            "launches": 1, "width_tokens": 4096, "needed_tokens": max(ctx_lens),
            "live_tokens": sum(ctx_lens), "gathered_tokens": blocks * BS,
            # the toy's default wave is the whole table (256 blocks of [16, 2, 16]): one wave a real slot
            "multiplied_tokens": len(ctx_lens) * 4096,
        }
        assert table[3] == {**kernel[3], "gathered_tokens": 4 * 4096, "multiplied_tokens": 4 * 4096}
        mean = sum(ctx_lens) / len(ctx_lens)
        assert kernel[3]["live_tokens"] / kernel[3]["gathered_tokens"] >= 1 - BS / mean
    assert runner.compile_count() == 1
    assert gather.compile_count() - compiled <= 1  # the one decode program, if no case before compiled it


@pytest.mark.parametrize("ctx", [2046, 2044], ids=["over_2048", "to_2048"])
def test_kernel_reads_nothing_for_a_padding_slot_and_serves_verify(cfg, params, gather, kernel_forced, ctx):
    """A window of 4 after ``ctx`` cached tokens; the second slot's window is
    shorter than the window bucket, the other two slots are padding."""
    runner = _runner(cfg, params)
    windows = [[3, 4, 5, 6], [9, 8]]
    call = lambda r: np.concatenate(  # noqa: E731
        r.verify_batch(windows, _rows(r, [ctx + 4, 42]), [ctx, 40])
    )
    kernel, table = _both((runner, gather), 1, call)
    assert kernel[0].shape == (4 + 2, cfg.vocab_size)
    assert {w for w in kernel[1] if w[2] == 0} == {w for w in table[1] if w[2] == 0}
    _assert_same(_but_the_null_block(kernel), _but_the_null_block(table))
    # the first slot's live blocks + 3; the two padding slots read nothing
    assert kernel[3] == {
        "launches": 1, "width_tokens": 4096, "needed_tokens": ctx + 4,
        "live_tokens": ctx + 4 + 42, "gathered_tokens": (-(-(ctx + 4) // BS) + 3) * BS,
        "multiplied_tokens": 2 * 4096,
    }
    assert table[3] == {**kernel[3], "gathered_tokens": 4 * 4096, "multiplied_tokens": 4 * 4096}


def test_kernel_engine_warms_one_decode_program_and_never_recompiles(cfg, params, engine, kernel_forced):
    """The request of ``test_a_request_growing_through_2048_compiles_nothing``
    on an engine whose decode takes the kernel: the same one decode program a
    bucket, the same greedy tokens, nothing compiled on the way over 2048."""
    eng = _engine(cfg, params)
    try:
        runner = eng.runner
        assert runner.attention_paths[1] == ("kernel", "blocks")
        assert runner.compile_count() == 2 + 1 + 1  # prefill buckets, ONE decode program, the COW copy
        programs = set(eng.stats()["startup"]["warmup_programs"])
        assert [p for p in programs if p.startswith("paged_decode_step")] == ["paged_decode_step[4x4096]"]
        prompt = [int(t) for t in np.random.RandomState(0).randint(1, 256, size=2034)]
        start = eng.stats()["decode_width"]
        tokens = list(eng.generate(prompt, max_new_tokens=30))
        end = eng.stats()["decode_width"]
        assert tokens == list(engine.generate(prompt, max_new_tokens=30))  # the gather's engine
        got = {k: end[k] - start[k] for k in COUNTERS}
        contexts = range(2035, 2064)
        assert got == {
            "launches": 29, "width_tokens": 29 * 4096, "needed_tokens": sum(contexts),
            "live_tokens": sum(contexts),
            "gathered_tokens": sum(-(-c // BS) for c in contexts) * BS,  # three padding slots read nothing
            "multiplied_tokens": 29 * 4096,  # one wave as wide as the table
        }
        assert eng.stats()["recompiles_after_warmup"] == 0
        assert runner.compile_count() == 2 + 1 + 1
    finally:
        eng.stop()


# -- what the kernel multiplies: whole waves, a group's own size (ISSUE 57) ---------------------------

def test_multiplied_tokens_are_each_groups_waves_by_its_layers(kernel_forced, monkeypatch):
    """Two layer groups, a full layer and two that keep 64: a wave of the
    kernel is ``blocks_a_wave`` blocks (here 4 of the full group, 8 of the
    window group: its span of 5 in ONE wave of whole lane tiles), every wave is
    multiplied whole, and a FULL one is waited for once. By hand, for contexts
    of 5, 300 and 130 in blocks of 16."""
    monkeypatch.setattr(PA, "_WAVE_ROWS", 16)  # 16 x 128 numbers a wave: 4 of the toy's [16, 2, 16] blocks
    cfg = LlamaConfig.tiny(max_seq_len=512, n_layers=3, layer_windows=(0, 64, 64))
    runner = PagedModelRunner(
        cfg, init_params(cfg, jax.random.PRNGKey(1)), num_blocks=(80, 40), block_size=BS,
        prefill_buckets=(64,), decode_buckets=(4,),
    )
    assert runner.attention_paths[1].reads == "blocks" and runner._wave_blocks == (4, 8)
    assert runner._wave_blocks == tuple(
        PA.blocks_a_wave(runner.cache[name].shape[2:], BS, 32, keeps) for name, keeps in (("k", 0), ("k.window", 64))
    )
    ctx_lens = (5, 300, 130)
    rows, nxt = [], [1, 1]
    for ctx in ctx_lens:
        row = np.zeros((2, 32), np.int32)
        for g, first in enumerate((0, max(0, ctx - 64) // BS)):  # a window's blocks from its first live one on
            n = -(-ctx // BS) - first
            row[g, first:first + n] = np.arange(nxt[g], nxt[g] + n)
            nxt[g] += n
        rows.append(row)
    start = dict(runner.decode_width)
    logits = runner.decode([3, 4, 5], [c - 1 for c in ctx_lens], rows, list(ctx_lens))
    assert np.isfinite(logits).all()
    got = {k: runner.decode_width[k] - start[k] for k in runner.decode_width}
    full, window = [1, 19, 9], [1, 5, 5]  # live blocks a slot: all of them; from the window's first on
    waves = (sum(-(-n // 4) for n in full), sum(-(-n // 8) for n in window))
    assert waves == (9, 3)
    assert got == {
        "launches": 1, "width_tokens": 512, "needed_tokens": 300,
        "live_tokens": (435 + 2 * (5 + 64 + 64)) / 3, "gathered_tokens": (29 * 16 + 2 * 11 * 16) / 3,
        "window_read_tokens": 2 * 11 * 16 / 3,
        "multiplied_tokens": (9 * 4 * 16 + 2 * 3 * 8 * 16) // 3,
        "waves": (9 + 2 * 3) // 3,
        "single_wait_waves": (sum(n // 4 for n in full) + 2 * sum(n // 8 for n in window)) // 3,
    }
    assert got["multiplied_tokens"] == 448 and got["single_wait_waves"] == 2
    assert got["live_tokens"] <= got["gathered_tokens"] <= got["multiplied_tokens"]


@pytest.mark.parametrize("family", ["llama", "lfm2", "jamba"])
def test_every_kv_cache_counts_what_it_multiplies(family):
    """The counter is absent from no path the K/V kernel can serve: every
    model whose cache is K/V rows has it from construction (the latent
    models' kernel, ``ops/latent_paged.py``, keeps its own loops: no key)."""
    from ray_tpu.models import interface, jamba, lfm2, xing4

    cfgs = {"llama": LlamaConfig.tiny(), "lfm2": lfm2.Lfm2Config.tiny(), "jamba": jamba.JambaConfig.tiny()}
    cfg = cfgs[family]
    model = interface.model_of(cfg)
    runner = PagedModelRunner(
        cfg, model.init_params(cfg, jax.random.PRNGKey(0)), num_blocks=16, block_size=BS,
        prefill_buckets=(16,), decode_buckets=(4,), state_slots=2 if model.state_layout else 0,
    )
    assert runner.cache_layout.kind == "kv"
    assert {"multiplied_tokens", *WAVES} <= set(runner.decode_width) and len(runner._wave_blocks) == 1
    latent = xing4.Xing4Config.tiny()
    other = PagedModelRunner(
        latent, interface.model_of(latent).init_params(latent, jax.random.PRNGKey(0)), num_blocks=16,
        block_size=BS, prefill_buckets=(16,), decode_buckets=(4,),
    )
    assert other.cache_layout.kind == "latent" and "multiplied_tokens" not in other.decode_width
