"""What a prefill chunk's attention reads of the table (ISSUE 32): the
runner's ``prefill_width`` account, and Xing4's chunk through the flash
kernel (``ops/latent_flash.py``) against the materialised softmax it
replaces, on a runner and on an engine.

The toy config has ``kv_lora_rank`` 32, so that a chunk of 32 queries EXPANDS
(``xing4.absorbs``: at the toy's own 16 every window absorbs and the
expanded path never runs) while decode absorbs, as at the published widths.
On the CPU the program takes the materialised softmax; ``flash_forced``
patches the selection predicate as ``tests/test_decode_width.py`` does for
the paged-attention kernel (the kernel then runs in Pallas' interpreter, at
tiles of 16): the program has no option for it."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu.inference.engine import EngineConfig, InferenceEngine  # noqa: E402
from ray_tpu.inference.model_runner import PagedModelRunner  # noqa: E402
from ray_tpu.models import latent, xing4  # noqa: E402
from ray_tpu.models.interface import model_of  # noqa: E402
from ray_tpu.models.llama import LlamaConfig  # noqa: E402
from ray_tpu.ops import latent_flash  # noqa: E402

BS, CHUNK, MAX_SEQ, TILE = 8, 32, 128, 16
COUNTERS = ("launches", "width_tokens", "live_tokens", "read_tokens", "expanded_tokens")
PROMPT = [int(t) for t in np.random.RandomState(3).randint(1, 256, size=100)]
#: context + chunk of the prompt's four launches (32, 32, 32, 4 tokens)
LIVE = (32, 64, 96, 100)
#: ... rounded up to ``latent.key_rungs``' whole tiles of 16: what the kernel reads (ISSUE 53)
EXPANDED = 32 + 64 + 96 + 112


@pytest.fixture(scope="module")
def cfg():
    cfg = xing4.Xing4Config.tiny(kv_lora_rank=32, max_seq_len=MAX_SEQ)
    assert not xing4.absorbs(cfg, CHUNK) and xing4.absorbs(cfg, 1)
    return cfg


@pytest.fixture(scope="module")
def params(cfg):
    return xing4.init_params(cfg, jax.random.PRNGKey(2))


@pytest.fixture
def flash_forced(monkeypatch):
    """The predicate as it reads on a TPU at whole tiles, and tiles the toy
    table holds eight of."""
    monkeypatch.setattr(latent, "flash_serves", lambda *a, **kw: True)
    monkeypatch.setattr(latent_flash, "_QUERY_TILE", TILE)
    monkeypatch.setattr(latent_flash, "_KEY_TILE", TILE)


def _runner(cfg, params):
    return PagedModelRunner(
        cfg, params, num_blocks=40, block_size=BS, prefill_buckets=(CHUNK,), decode_buckets=(4,)
    )


def _prefill(runner, prompt=PROMPT):
    """The prompt a chunk at a time into blocks 1..; the logits of every
    chunk's last row, and the cache rows of the prompt's positions."""
    width = runner.max_blocks_per_seq
    n = -(-len(prompt) // BS)
    row = list(range(1, n + 1)) + [0] * (width - n)
    logits = [
        runner.prefill_chunk(prompt[at : at + CHUNK], row, at) for at in range(0, len(prompt), CHUNK)
    ]
    cache = next(iter(runner.cache.values()))  # the latent rows, or K
    rows = np.asarray(cache)[:, 1 : n + 1].reshape(cache.shape[0], n * BS, -1)[:, : len(prompt)]
    return np.stack(logits), rows


def _expected(read, expanded=None):
    """``expanded``: the key positions the program gathers (and a latent
    model's expands) in front of its attention; what the attention reads
    unless told (ISSUE 53: the latent chunk's rungs are the kernel's key
    tiles; at the toy's own tile, the table's width, there is one rung)."""
    return {
        "launches": len(LIVE), "width_tokens": len(LIVE) * MAX_SEQ, "live_tokens": sum(LIVE),
        "read_tokens": read, "expanded_tokens": read if expanded is None else expanded,
    }


def test_the_flash_chunk_is_the_materialised_chunk_on_a_runner(cfg, params, request):
    """A prompt of four chunks (the last padded) through both programs: the
    same logits a chunk and the same cache rows of the prompt, and each
    runner counts what ITS program's attention reads: the table's width a
    launch, or context + chunk in whole key tiles."""
    table = _runner(cfg, params)
    assert table.prefill_width == dict.fromkeys(COUNTERS, 0)
    want_logits, want_rows = _prefill(table)
    assert table.attention_paths[CHUNK] == ("latent.expanded", "table")
    assert table.prefill_width == _expected(len(LIVE) * MAX_SEQ)

    request.getfixturevalue("flash_forced")
    live = _runner(cfg, params)
    have_logits, have_rows = _prefill(live)
    assert live.attention_paths[CHUNK] == ("latent.flash", "live")
    assert live.attention_paths[1] == ("latent.absorbed", "slots")  # off the chip decode keeps the gather
    # ... and on a TPU at the published widths reads each slot's own live blocks
    big = xing4.Xing4Config(dtype=jax.numpy.bfloat16)
    stored = jax.eval_shape(lambda: xing4.cache_layout(big, 16).init(8))
    assert model_of(big).attention_path(big, 1, stored, backend="tpu") == ("latent.paged", "blocks")
    # ... and gathers and expands the same whole key tiles, and no other
    assert live.prefill_width == _expected(sum(-(-n // TILE) * TILE for n in LIVE), EXPANDED) == _expected(32 + 64 + 96 + 112, EXPANDED)
    np.testing.assert_allclose(have_logits, want_logits, rtol=0, atol=2e-5 * np.abs(want_logits).max())
    np.testing.assert_allclose(have_rows, want_rows, rtol=0, atol=2e-5 * np.abs(want_rows).max())
    assert live.compile_count() == table.compile_count()


def _engine(cfg, params):
    return InferenceEngine(
        cfg, params,
        EngineConfig(
            num_blocks=40, block_size=BS, prefill_buckets=(CHUNK,), decode_buckets=(4,),
            max_decode_batch=4, prefix_cache_enabled=False,
        ),
    ).start()


def _generate(cfg, params, n=12):
    eng = _engine(cfg, params)
    try:
        start = eng.stats()["prefill_width"]
        tokens = list(eng.generate(PROMPT, max_new_tokens=n))
        end = eng.stats()["prefill_width"]
        assert eng.stats()["recompiles_after_warmup"] == 0
        return tokens, {k: end[k] - start[k] for k in COUNTERS}, eng.runner.attention_paths[CHUNK].name
    finally:
        eng.stop()


def test_the_flash_chunk_gives_the_engine_the_same_greedy_tokens(cfg, params, request):
    """The same request on two engines: the prompt's four chunks through the
    materialised softmax and through the kernel, then twelve decode steps
    over what they left in the cache. ``engine_stats()["prefill_width"]``
    counts the launches of the window for each."""
    want, table, name = _generate(cfg, params)
    assert name == "latent.expanded" and table == _expected(len(LIVE) * MAX_SEQ)
    request.getfixturevalue("flash_forced")
    have, live, name = _generate(cfg, params)
    assert name == "latent.flash" and live == _expected(32 + 64 + 96 + 112, EXPANDED)
    assert len(want) == 12 and have == want


def test_prefill_width_counts_every_model():
    """``models/llama.py``'s chunk gathers the table it is handed and attends
    over all of it: ``read_tokens`` is the width, whatever the context, and
    so is ``expanded_tokens`` (its gather takes no rungs)."""
    llama = LlamaConfig.tiny(max_seq_len=MAX_SEQ)
    runner = _runner(llama, model_of(llama).init_params(llama, jax.random.PRNGKey(0)))
    _prefill(runner)
    assert runner.attention_paths[CHUNK].reads == "table"
    # ... and a K/V cache counts the updates of its chunks' writes: 32 / 8 + 1 whole blocks, K and V of every layer (PR 66)
    written = len(LIVE) * 2 * llama.n_layers * (CHUNK // BS + 1)
    assert runner.prefill_width == {**_expected(len(LIVE) * MAX_SEQ), "written_updates": written}
    assert model_of(llama).key_tile(llama, CHUNK, runner.cache) == 1
    assert model_of(llama).gather_rungs(llama, CHUNK, runner.cache) == ()


def test_a_long_verify_window_takes_the_kernel_a_slot_at_a_time(cfg, params, request):
    """No deployment verifies 32 positions a slot, but such a window EXPANDS
    like a chunk and has a batch axis: the kernel then runs once a slot
    (``lax.map``), a padded window's real rows alone, and gives the
    materialised softmax's logits."""

    def verify():
        r = PagedModelRunner(cfg, params, num_blocks=40, block_size=BS, prefill_buckets=(CHUNK,),
                             decode_buckets=(2,), verify_buckets=(CHUNK,))
        width = r.max_blocks_per_seq
        rows = [list(range(1 + 8 * slot, 9 + 8 * slot)) + [0] * (width - 8) for slot in range(2)]
        for slot, n in enumerate((20, 29)):
            r.prefill_chunk(PROMPT[:n], rows[slot], 0)
        out = r.verify_batch([PROMPT[20:52], PROMPT[29:50]], rows, [20, 29])
        return r.attention_paths[CHUNK].name, out

    name, want = verify()
    assert name == "latent.expanded"
    request.getfixturevalue("flash_forced")
    name, have = verify()
    assert name == "latent.flash" and [len(x) for x in have] == [32, 21]
    for h, w in zip(have, want):
        np.testing.assert_allclose(h, w, rtol=0, atol=2e-5 * np.abs(w).max())
