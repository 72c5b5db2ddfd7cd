"""A plain GQA configuration's prefill chunk through the flash kernel
(ISSUE 51): ``models/paged_kv.py::way`` asks SHAPES and the backend,
so Mistral (32 heads over 8 KV heads of 128) and OLMoE (16 over 16) take the
lines a full layer of Mellum2 takes: K and V gathered through the table,
``ops/latent_flash.py`` over them, no score past the live context.

The CPU never runs the kernel by itself. Here the backend question is answered
as a TPU answers it (``latent_flash.kernel_serves`` told ``backend="tpu"``, the
shape questions left to it; tiles of 128 so that a toy table holds four) and
the kernel runs in Pallas' interpreter, against ``attend_gathered``'s
materialised softmax. No number here is a speed: the two ways' times on the
chip are PERF.md's (PR 51)."""

import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from perfbench import families  # noqa: E402
from perfbench.harness import cells  # noqa: E402
from ray_tpu.inference.model_runner import PagedModelRunner  # noqa: E402
from ray_tpu.models import llama as L  # noqa: E402
from ray_tpu.models import paged_kv  # noqa: E402
from ray_tpu.models.interface import model_of  # noqa: E402
from ray_tpu.ops import latent_flash  # noqa: E402
from ray_tpu.ops import paged_attention as paged_attn  # noqa: E402

TILE, BS, KEYS, CHUNK, HD = 128, 16, 512, 128, 128
M = KEYS // BS
SHAPES = {"mistral_32_over_8": (32, 8), "olmoe_16_over_16": (16, 16), "mqa_20_over_1": (20, 1)}
BENCH_CONFIGS = ("mistral-7b-v0.3-16l", "olmoe-1b-7b-0125-12l")


def _answering_as(serves, backend: str):
    """The flash kernel's predicate with the backend question answered, whoever asks and however."""
    return lambda window, keys, dk, dv, ds, dtype, _=None, **kw: serves(window, keys, dk, dv, ds, dtype, backend, **kw)


@pytest.fixture
def as_on_a_tpu(monkeypatch):
    """The backend question answered as a TPU answers it, every shape question
    left to the predicate; tiles a toy table holds four of. Returns the list
    the kernel's calls are noted in."""
    real_serves, real_flash, calls = latent_flash.kernel_serves, latent_flash.flash_attention, []
    monkeypatch.setattr(latent_flash, "kernel_serves", _answering_as(real_serves, "tpu"))
    monkeypatch.setattr(latent_flash, "_QUERY_TILE", TILE)
    monkeypatch.setattr(latent_flash, "_KEY_TILE", TILE)

    def noted(q, k, v, *args, **kw):
        calls.append((q.shape, k.shape, kw.get("group"), kw.get("window")))
        return real_flash(q, k, v, *args, **kw)

    monkeypatch.setattr(latent_flash, "flash_attention", noted)
    return calls


def _plain(heads: int, kv: int, dtype=jnp.float32, **more):
    fields = dict(dim=64, n_heads=heads, n_kv_heads=kv, attn_head_dim=HD, max_seq_len=KEYS, dtype=dtype)
    return L.LlamaConfig.tiny(**{**fields, **more})


def _shapes(cfg):
    return {"n_kv": cfg.n_kv_heads, "head_dim": cfg.head_dim}


def _flash_serves(cfg, k_cache, B: int, C: int, keys: int) -> bool:
    """Whether ``B`` chunks of ``C`` queries of a full layer of ``cfg`` go the flash way."""
    return paged_kv.way(C, B, cfg.n_heads, k_cache, keys, **_shapes(cfg)) == "flash"


def _attention(cfg, q, cache, layer, table, pos, valid=None):
    return paged_kv.attention(q, cache["k"], cache["v"], layer, table, pos, valid, **_shapes(cfg))


def _chunk(heads: int, kv: int, dtype, seed: int, live: int):
    """Queries of one chunk, a cache of two layers whose table is shuffled, and
    the same cache with NaNs planted in every row past ``live`` positions of
    the sequence (stale rows, or never written) and in every block the table
    does not name."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((1, CHUNK, heads, HD)), dtype)
    table = 1 + rng.permutation(M)
    k, v = (rng.standard_normal((2, M + 3, BS, kv, HD)).astype(np.float32) for _ in range(2))
    clean, stale = {}, {}
    for name, a in (("k", k), ("v", v)):
        zeroed, poisoned = a.copy(), np.full_like(a, np.nan)
        for j, blk in enumerate(table):
            n = int(np.clip(live - j * BS, 0, BS))
            zeroed[:, blk, n:] = 0.0
            poisoned[:, blk, :n] = a[:, blk, :n]
        clean[name], stale[name] = jnp.asarray(zeroed, dtype), jnp.asarray(poisoned, dtype)
    return q, clean, stale, jnp.asarray(table, jnp.int32)[None]


@pytest.mark.parametrize("ctx_len, true_len", [(0, CHUNK), (256, CHUNK), (0, 100), (208, 37), (KEYS - CHUNK, CHUNK)],
                         ids=["context_0", "mid_table", "short_true_len", "mid_table_short", "the_tables_last_tile"])
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_a_plain_chunk_through_the_flash_way_is_the_materialised_chunk(as_on_a_tpu, shape, dtype, tol, ctx_len, true_len):
    """``paged_kv.attention`` of a configuration WITHOUT layer kinds takes the
    flash way for a chunk in whole tiles (one call, grouped by ``n_heads //
    n_kv_heads``, no window) and gives what ``attend_gathered`` gives on
    every real row, with stale rows past the live context that would poison
    a sum that read them."""
    heads, kv = SHAPES[shape]
    cfg = _plain(heads, kv, dtype)
    q, clean, stale, table = _chunk(heads, kv, dtype, seed=ctx_len + true_len, live=ctx_len + true_len)
    idx = jnp.arange(CHUNK, dtype=jnp.int32)
    pos, valid = (ctx_len + idx)[None], (idx < true_len)[None]
    assert _flash_serves(cfg, stale["k"], 1, CHUNK, KEYS)
    have = _attention(cfg, q, stale, 1, table, pos, valid)
    assert as_on_a_tpu == [((heads, CHUNK, HD), (kv, KEYS, HD), heads // kv, None)]
    want = paged_kv.attend_gathered(q, clean["k"], clean["v"], 1, table, pos, kv, KEYS)
    have, want = (np.asarray(a, np.float32)[0, :true_len] for a in (have, want))
    assert np.isfinite(have).all()
    np.testing.assert_allclose(have, want, rtol=0, atol=tol * np.abs(want).max())


def test_what_the_kernel_does_not_take_keeps_the_materialised_softmax(as_on_a_tpu):
    """A batch of sequences (verify), a chunk that is no whole tile and heads
    that are no whole lanes stay where they were, on a TPU too: no call."""
    cfg = _plain(16, 8)
    q, clean, _, table = _chunk(16, 8, jnp.float32, seed=3, live=KEYS)
    idx = jnp.arange(CHUNK, dtype=jnp.int32)[None]
    two = _attention(cfg, jnp.tile(q, (2, 1, 1, 1)), clean, 0, jnp.tile(table, (2, 1)), jnp.tile(idx, (2, 1)))
    odd = _attention(cfg, q[:, :24], clean, 0, table, idx[:, :24])
    assert two.shape == (2, CHUNK, 16, HD) and odd.shape == (1, 24, 16, HD)
    narrow = L.LlamaConfig.tiny(max_seq_len=KEYS)  # heads of 16
    cache = jax.eval_shape(lambda: L.init_paged_kv_cache(narrow, 8, BS))
    assert not _flash_serves(narrow, cache["k"], 1, CHUNK, KEYS)
    assert not as_on_a_tpu


# -- shapes decide, nothing else ---------------------------------------------------------------------------

def _others(cfg):
    """Configurations whose head shapes, cache and table are ``cfg``'s and
    whose every OTHER field differs: layer kinds, the rope, experts, QK-norm,
    depth, widths that are not a head's."""
    n = cfg.n_layers
    return [
        dataclasses.replace(cfg, layer_windows=(1024, 0) * (n // 2)),
        dataclasses.replace(cfg, layer_windows=(0,) * n),
        dataclasses.replace(cfg, rope_scaling=L.RopeScaling(factor=4.0, original_max=128), rope_theta=5e5),
        dataclasses.replace(cfg, moe_experts=8, moe_top_k=2, moe_held=(0, 4), qk_norm=True),
        dataclasses.replace(cfg, dim=256, mlp_hidden=96, vocab_size=1000, norm_eps=1e-6, attention_impl="xla"),
    ]


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_only_shapes_and_the_backend_decide_which_chunk_attention_runs(monkeypatch, shape, backend):
    """``paged_kv.way`` gives ONE answer for configurations whose head shapes,
    chunk, batch and table agree, whatever their layer kinds, rope, experts or
    depth (``LlamaConfig`` has no name and no ``model_type`` to ask): the
    answer of the kernel's own predicate for those shapes. A full layer of a
    configuration WITH kinds and a layer of one without are the same
    question."""
    heads, kv = SHAPES[shape]
    monkeypatch.setattr(latent_flash, "kernel_serves", _answering_as(latent_flash.kernel_serves, backend))
    cfg = _plain(heads, kv, jnp.bfloat16, n_layers=4, max_seq_len=4096)
    k_cache = jax.ShapeDtypeStruct((4, 64, BS, kv, HD), jnp.bfloat16)
    f16 = jax.ShapeDtypeStruct(k_cache.shape, jnp.float16)
    questions = [(k_cache, 1, 1024, 4096), (k_cache, 1, 256, 4096), (k_cache, 1, 16, 4096), (k_cache, 1, 1000, 4096),
                 (k_cache, 2, 1024, 4096), (k_cache, 1, 1024, 4000), (f16, 1, 1024, 4096)]
    want = [backend == "tpu" and i < 2 for i in range(len(questions))]
    for other in [cfg, *_others(cfg)]:
        assert [_flash_serves(other, k, B, C, keys) for k, B, C, keys in questions] == want, other


# -- the benchmark's two plain configurations ------------------------------------------------------------

def _bench_cfg(name: str):
    model = cells.config_of(cells.benchmark(), name)
    cfg = families.of(model).model_config(model, max_seq_len=int(model["max_position_embeddings"]))
    engine = model["serving"]["engine"]
    cache = jax.eval_shape(lambda: L.cache_layout(cfg, engine["block_size"]).init(8))
    return cfg, engine, cache


@pytest.mark.parametrize("name", BENCH_CONFIGS)
def test_the_benchmarks_plain_configurations_say_flash_on_a_tpu_and_gather_on_the_cpu(monkeypatch, name):
    """What the launch span's ``path`` and the ``prefill_width`` account go
    by: both prefill buckets answer ``("flash", "live")`` with the kernel's key
    tile on a TPU and ``("gather", "table")`` here; decode keeps its kernel."""
    cfg, engine, cache = _bench_cfg(name)
    model = model_of(cfg)
    assert not cfg.layer_windows and engine["prefill_buckets"] == [256, 1024]
    for bucket in engine["prefill_buckets"]:
        assert model.attention_path(cfg, bucket, cache) == ("gather", "table")
        assert model.key_tile(cfg, bucket, cache) == 1
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # steer the branch; the test's business
    for bucket in engine["prefill_buckets"]:
        assert model.attention_path(cfg, bucket, cache) == ("flash", "live")
        assert model.key_tile(cfg, bucket, cache) == 1024 == latent_flash.tiles(bucket, 4096)[1]
    assert model.attention_path(cfg, 1, cache) == ("kernel", "blocks")
    assert model.key_tile(cfg, 1, cache) == 1


# -- the runner: the same logits, and the account in whole key tiles -------------------------------------

PROMPT_LEN = 3 * CHUNK + 100
LIVE = (128, 256, 384, 484)


def _prefill(runner, prompt):
    n = -(-len(prompt) // BS)
    row = list(range(1, n + 1)) + [0] * (runner.max_blocks_per_seq - n)
    return np.stack([runner.prefill_chunk(prompt[at : at + CHUNK], row, at) for at in range(0, len(prompt), CHUNK)])


@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_runner_counts_whole_key_tiles_for_a_plain_configuration(shape, request):
    """A prompt of four chunks (the last padded) through both programs of a
    runner: the same logits a chunk, and each runner counts what ITS program's
    attention reads: the table's width a launch, or context + chunk in whole
    key tiles."""
    heads, kv = SHAPES[shape]
    cfg = _plain(heads, kv)
    params = L.init_params(cfg, jax.random.PRNGKey(5))
    prompt = np.random.default_rng(7).integers(0, cfg.vocab_size, PROMPT_LEN).tolist()
    make = lambda: PagedModelRunner(  # noqa: E731
        cfg, params, num_blocks=M + 2, block_size=BS, prefill_buckets=(CHUNK,), decode_buckets=(2,))
    table = make()
    want = _prefill(table, prompt)
    assert table.attention_paths[CHUNK] == ("gather", "table")
    # (``expanded_tokens``, PR 53: what the program GATHERS in front of its attention; this model's
    # chunk gathers the table whole on both paths, ROADMAP S3 iv)
    # (``written_updates``, PR 66: a chunk of whole blocks is written by blocks, K and V of every layer, on both paths)
    account = {"launches": 4, "width_tokens": 4 * KEYS, "live_tokens": sum(LIVE), "expanded_tokens": 4 * KEYS,
               "written_updates": 4 * 2 * cfg.n_layers * (CHUNK // BS + 1)}
    assert table.prefill_width == {**account, "read_tokens": 4 * KEYS}

    calls = request.getfixturevalue("as_on_a_tpu")
    live = make()
    have = _prefill(live, prompt)
    assert live.attention_paths[CHUNK] == ("flash", "live") and live._path_name(CHUNK) == "flash"
    assert live.attention_paths[1] == ("gather", "table")  # off the chip decode keeps the gather
    assert len(calls) == cfg.n_layers  # traced once: one call a layer of the ONE prefill program
    read = sum(-(-n // TILE) * TILE for n in LIVE)
    assert read == 128 + 256 + 384 + 512
    assert live.prefill_width == {**account, "read_tokens": read}
    np.testing.assert_allclose(have, want, rtol=0, atol=2e-5 * np.abs(want).max())
    assert live.compile_count() == table.compile_count()


def test_the_kernels_share_of_busy_time_finds_the_calls_by_the_name_they_trace_under():
    """``latent_flash_time_share.longdoc`` (the accepted reader) matches device operations by the
    kernel's ``name``, which the lowered call of these shapes carries."""
    share = cells.layer_metric_spec("latent_flash_time_share.longdoc")
    assert re.search(share["name_regex"], "latent_flash.17") and not re.search(share["name_regex"], "fusion.latent_flash")
    text = jax.jit(lambda q, k: latent_flash.flash_attention(q, k, k, 0, 128, scale=0.1, interpret=False)).trace(
        jnp.zeros((2, 128, 128)), jnp.zeros((2, 128, 128))).lower(lowering_platforms=("tpu",)).as_text()
    assert "latent_flash" in text


# -- ONE chooser for every K/V model: each of the three ways, in each form a block is stored in (ISSUE 61) --

W_BS, W_M, W_LAYERS = 16, 16, 2
W_KEYS = W_BS * W_M

#: ``(query heads, KV heads, head_dim, the block's form, a window the layer keeps)``: the shapes
#: ``models/lfm2.py`` and ``models/jamba.py`` served through copies of the chooser until PR 61, beside
#: ``models/llama.py``'s own (the last: heads of 64 under ``llama.py``'s window, which no copy served)
WAYS_SHAPES = {
    "lfm2_heads_of_64_in_lanes": (32, 8, 64, "in_lanes", 0),
    "lfm2_heads_of_64_five_d": (32, 8, 64, "five_d", 0),
    "jamba_20_over_1_flat": (20, 1, 128, "flat", 0),
    "mistral_32_over_8": (32, 8, 128, "five_d", 0),
    "mellum_4_kv_flat_window": (8, 4, 128, "flat", 48),
    "heads_of_64_window": (16, 4, 64, "five_d", 48),
}


def _stored(a, form: str):
    """``a [L, N, bs, n_kv, hd]`` in the form a ``CacheLayout`` stores it in."""
    L_, N, bs, n_kv, hd = a.shape
    return {"five_d": a, "flat": a.reshape(L_, N, bs * n_kv, hd), "in_lanes": a.reshape(L_, N, bs, n_kv * hd)}[form]


@pytest.mark.parametrize("way", ["kernel", "flash", "gather"])
@pytest.mark.parametrize("shape", list(WAYS_SHAPES))
def test_each_of_the_three_ways_is_the_materialised_attention(monkeypatch, shape, way):
    """``paged_kv.attention`` told the way (the choice is the next test's)
    over a cache in the shape's own form, the kernels in Pallas' interpreter,
    against ``attend_gathered`` over the SAME rows stored ``[.., bs, n_kv,
    hd]``: a decode batch for the kernel's way, one chunk (a context that is no
    whole tile, a padded tail) for the flash way and the gather's."""
    heads, kv, hd, form, keeps = WAYS_SHAPES[shape]
    rng = np.random.default_rng(len(shape))
    B, C = (3, 1) if way == "kernel" else (1, CHUNK)
    N = 1 + B * W_M
    k, v = (jnp.asarray(rng.standard_normal((W_LAYERS, N, W_BS, kv, hd)), jnp.float32) for _ in range(2))
    tables = jnp.asarray(1 + rng.permutation(N - 1).reshape(B, W_M), jnp.int32)
    ctx = np.asarray([W_KEYS - 1, 37, W_BS][:B] if way == "kernel" else [W_KEYS - CHUNK - 24])
    true_len = C if way == "kernel" else 100
    pos = jnp.asarray(ctx[:, None] + np.arange(C), jnp.int32)
    valid = jnp.arange(C)[None] < true_len
    q = jnp.asarray(rng.standard_normal((B, C, heads, hd)), jnp.float32)
    monkeypatch.setattr(latent_flash, "_QUERY_TILE", TILE)
    monkeypatch.setattr(latent_flash, "_KEY_TILE", TILE)
    monkeypatch.setattr(paged_kv, "way", lambda *a, **kw: way)
    have = paged_kv.attention(
        q, _stored(k, form), _stored(v, form), 1, tables, pos, valid, n_kv=kv, head_dim=hd, keeps=keeps)
    want = paged_kv.attend_gathered(q, k, v, 1, tables, pos, kv, W_KEYS, keeps)
    assert have.shape == want.shape == (B, C, heads, hd)
    have, want = (np.asarray(a)[:, :true_len] for a in (have, want))
    np.testing.assert_allclose(have, want, rtol=0, atol=2e-5 * np.abs(want).max())
    if not keeps:  # ``attention_counted`` (the two state models' door): the same rows, counted
        counted = paged_kv.attention_counted(
            q, _stored(k, form), _stored(v, form), 1, tables, pos, valid.sum(axis=1, dtype=jnp.int32),
            n_kv=kv, head_dim=hd)
        np.testing.assert_allclose(np.asarray(counted)[:, :true_len], want, rtol=0, atol=2e-5 * np.abs(want).max())


#: the same shapes at a deployment's sizes: ``(the cache's block shape, slots of a decode batch)``
WAYS_DEPLOYED = {
    "lfm2_heads_of_64_in_lanes": ((16, 512), 64),
    "lfm2_heads_of_64_five_d": ((16, 8, 64), 64),
    "jamba_20_over_1_flat": ((16, 128), 256),
    "mistral_32_over_8": ((16, 8, 128), 32),
    "mellum_4_kv_flat_window": ((64, 128), 32),
    "heads_of_64_window": ((16, 4, 64), 32),
}


@pytest.mark.parametrize("shape", list(WAYS_SHAPES))
def test_the_shapes_choose_the_way_and_the_host_is_told_the_same(shape):
    """``paged_kv.way`` on a TPU: decode the kernel wherever a block is whole
    tiles as it is stored (5-d heads of 64 are not: the gather, as ever), a
    chunk in whole tiles the flash kernel (64-wide heads in pairs), a batch of
    chunks, a ragged chunk and the CPU the gather; and ``program_path`` says
    that way, what it reads and the chunk's key tile."""
    heads, kv, hd, form, keeps = WAYS_SHAPES[shape]
    block, slots = WAYS_DEPLOYED[shape]
    k_cache = jax.ShapeDtypeStruct((6, 4096, *block), jnp.bfloat16)
    said = dict(n_kv=kv, head_dim=hd, keeps=keeps)
    assert paged_kv.block_size(k_cache, kv, hd) == 16
    decode = "gather" if (hd == 64 and form == "five_d") else "kernel"
    assert paged_kv.way(1, slots, heads, k_cache, 8192, backend="tpu", **said) == decode
    assert decode == ("kernel" if paged_attn.kernel_serves(1, heads, k_cache, "tpu", n_kv=kv, head_dim=hd) else "gather")
    assert paged_kv.way(1024, 1, heads, k_cache, 8192, backend="tpu", **said) == "flash"
    assert paged_kv.way(1024, 2, heads, k_cache, 8192, backend="tpu", **said) == "gather"
    assert paged_kv.way(1000, 1, heads, k_cache, 8192, backend="tpu", **said) == "gather"
    assert {paged_kv.way(w, 1, heads, k_cache, 8192, backend="cpu", **said) for w in (1, 1024)} == {"gather"}
    told = dict(n_kv=kv, head_dim=hd, backend="tpu")
    assert paged_kv.program_path(1, k_cache, 8192, (heads,), **told) == (decode, {"kernel": "blocks", "gather": "table"}[decode], 1)
    assert paged_kv.program_path(1024, k_cache, 8192, (heads,), **told) == ("flash", "live", 1024)
    # where a model's kinds of layer differ in their query heads, the way furthest from the kernel
    assert paged_kv.program_path(8, k_cache, 8192, (heads, 64 * heads), **told)[0] == "gather"
