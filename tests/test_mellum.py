"""Mellum2's block through ``ray_tpu.models.llama`` (an explicit head width,
window layers beside full ones, a rope table a kind, a held range of experts)
and the paged cache of layer GROUPS under it (``models/interface.py::
LayerGroup``, ``inference/kv_cache.py``: a pool of blocks a kind, a window
pool's blocks given back while a sequence runs), against the plain reference
``perfbench/families/mellum/reference.py`` at a toy size: two periods (W W W F
W W W F), a window of 8, blocks of 4, 8 experts of which a range is held, 2 a
token. Float32 on both sides. The wrong models a limit has to tell are
``tests/perfbench/mellum_controls.py``.

The two kernels' new forms run in Pallas' interpreters: the decode kernel over
a cache stored flat at ``n_kv`` 4 with a first live block, the chunk kernel
with grouped heads and a window, each against the materialised softmax over a
clean cache while the kernel's own is poisoned wherever it must not read."""

import dataclasses
import os
import sys
import threading
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests", "perfbench"))

import mellum_controls as controls  # noqa: E402
from perfbench.families import mellum  # noqa: E402
from perfbench.families.mellum import reference, server  # noqa: E402
from ray_tpu.inference.engine import EngineConfig, InferenceEngine  # noqa: E402
from ray_tpu.inference.kv_cache import PagedBlockManager  # noqa: E402
from ray_tpu.inference.model_runner import PagedModelRunner  # noqa: E402
from ray_tpu.models import llama as L  # noqa: E402
from ray_tpu.models import paged_kv  # noqa: E402
from ray_tpu.models.interface import LayerGroup  # noqa: E402
from ray_tpu.ops import latent_flash, paged_attention as PA  # noqa: E402
from ray_tpu.ops.layers import rms_norm  # noqa: E402

REL_TOL = 2e-4
W, BS = 8, 4
MODEL = {
    **mellum.TOY_SIZES, "family": "mellum", "num_hidden_layers": 8, "sliding_window": W,
    "layer_types": mellum.TOY_SIZES["layer_types"] * 2, "mlp_layer_types": ["sparse"] * 8,
    "num_experts": 8, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
    "deployment": {"num_experts_total": 8, "held_experts": [0, 8]},
}


def _model(lo=0, hi=8):
    return {**MODEL, "num_experts": hi - lo, "deployment": {"num_experts_total": 8, "held_experts": [lo, hi]}}


def _cfg(model=MODEL, **overrides):
    return mellum.model_config(model, max_seq_len=64, **overrides)


def _params(cfg, seed=0):
    """Seeded weights; the norm vectors are drawn too (``init_params`` sets
    them to 1, under which a forgotten norm WEIGHT would pass)."""
    params = L.init_params(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 4 * cfg.n_layers + 1))
    for p in params["layers"]:
        for name in [n for n in p if n.endswith("norm")]:
            p[name] = 1.0 + 0.3 * jax.random.normal(next(keys), p[name].shape, jnp.float32)
    return params


def _rel(have, want):
    return float(np.max(np.abs(np.asarray(have) - np.asarray(want))) / np.max(np.abs(want)))


def _tokens(seed, shape):
    return np.random.default_rng(seed).integers(1, 256, size=shape).astype(np.int32)


def _runner(cfg, params, full=40, window=24):
    return PagedModelRunner(
        cfg, params, num_blocks=(full, window), block_size=BS, prefill_buckets=(8, 16), decode_buckets=(4,),
    )


@pytest.fixture(scope="module")
def served():
    cfg = _cfg(_model(0, 4))
    params = _params(cfg)
    runner = _runner(cfg, params)
    runner.warmup()
    return cfg, params, runner


# -- the configuration and forward ---------------------------------------------------

def test_the_adapter_sets_the_kinds_the_head_width_and_the_held_range():
    cfg = _cfg(_model(2, 6))
    assert cfg.head_dim == 24 != cfg.dim // cfg.n_heads
    assert cfg.layer_windows == (W, W, W, 0) * 2 and cfg.moe_held == (2, 6) and cfg.moe_experts == 8
    assert L._layer_shapes(cfg)["w_gate"] == (4, 64, 32) and L._layer_shapes(cfg)["router"] == (64, 8)
    assert L._layer_shapes(cfg)["wq"] == (64, 4, 24)
    layout = L.cache_layout(cfg, BS)
    assert layout.groups == (LayerGroup("full", (3, 7), 0), LayerGroup("window", (0, 1, 2, 4, 5, 6), W))
    said = layout.describe()["groups"]
    assert said["full"] == {"layers": 2, "keeps": "all", "bytes_per_token": 2 * 2 * 2 * 24 * 4}
    assert said["window"]["keeps"] == W and said["window"]["layers"] == 6
    cache = layout.init((9, 5))
    assert {k: v.shape for k, v in cache.items()} == {
        "k": (2, 9, BS, 2, 24), "v": (2, 9, BS, 2, 24),
        "k.window": (6, 5, BS, 2, 24), "v.window": (6, 5, BS, 2, 24),
    }
    # a configuration without kinds keeps the one group it had, by its plain names
    plain = L.cache_layout(L.LlamaConfig.tiny(), 8)
    assert plain.groups == (LayerGroup("all", (0, 1), 0),)
    assert set(plain.init(3)) == {"k", "v"} and "groups" not in plain.describe()


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_matches_the_plain_reference(seed):
    cfg = _cfg()
    params = _params(cfg, seed)
    tokens = _tokens(seed, (2, 30))  # past the window (8) and past YaRN's original context (32: at 30, nearly)
    have = L.forward(cfg, params, jnp.asarray(tokens))
    picks = [(b, t) for b in range(2) for t in range(30)]
    want = reference.logits_at(MODEL, params, tokens, picks).reshape(2, 30, -1)
    assert _rel(have, want) < REL_TOL


@pytest.mark.parametrize("variant", controls.WRONG_MODELS)
def test_each_control_of_the_reference_is_another_model(variant):
    """Every wrong model is outside the tolerance the model itself is held to,
    by a wide margin: a limit between the two tells them apart."""
    cfg = _cfg()
    params = _params(cfg)
    tokens = _tokens(3, (1, 40))
    have = L.forward(cfg, params, jnp.asarray(tokens))[0]
    picks = [(0, t) for t in range(40)]
    wrong = reference.logits_at(controls.wrong_model(MODEL, variant, BS), params, tokens, picks)
    assert _rel(have, wrong) > 25 * REL_TOL


def test_a_precision_lower_is_another_model():
    cfg = _cfg()
    params = _params(cfg)
    tokens = _tokens(3, (1, 24))
    have = L.forward(cfg, params, jnp.asarray(tokens))[0]
    for variant in controls.LOW_PARAMS:
        low = reference.logits_at(MODEL, controls.low_params(params, variant), tokens, [(0, t) for t in range(24)])
        assert _rel(have, low) > 25 * REL_TOL, variant


def test_the_four_held_ranges_parts_sum_to_the_uncut_layer():
    """Each chip of four computes its own part of a layer's FFN from the same
    router over all experts; the parts add up to the whole (the exchange that
    adds them is the deployment's, not stood in for)."""
    whole_cfg = _cfg()
    params = _params(whole_cfg)
    p = params["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 12, whole_cfg.dim), jnp.float32)
    whole = L._ffn(whole_cfg, p, h)[0]
    parts = []
    for lo in range(0, 8, 2):
        cfg = _cfg(_model(lo, lo + 2))
        held = {**p, **{k: p[k][lo : lo + 2] for k in ("w_gate", "w_up", "w_down")}}
        parts.append(L._ffn(cfg, held, h)[0])
        want, _ = reference.expert_ffn(reference.sizes(_model(lo, lo + 2)), held, h.reshape(-1, whole_cfg.dim))
        assert _rel(parts[-1].reshape(-1, whole_cfg.dim), want) < REL_TOL
    assert _rel(sum(parts), whole) < REL_TOL
    assert all(float(jnp.abs(part).max()) > 0 for part in parts)


def test_forward_refuses_a_mesh_for_a_configuration_with_kinds():
    cfg = _cfg(attention_impl="flash")
    with pytest.raises(ValueError, match="no window mask yet"):
        L.forward(cfg, _params(cfg), jnp.asarray(_tokens(0, (1, 8))))


# -- the paged steps through the grouped cache -----------------------------------------

def test_chunked_prefill_then_decode_through_both_pools_matches_the_reference(served):
    """A prompt of 37 (five chunks: 16, 16, 5; far past the window of 8, so
    its window table has slid and given blocks back) beside one of 6 (inside
    the window), then six decode steps that cross a block boundary (40) and
    release another block."""
    cfg, params, runner = served
    prompt_lens, steps = [37, 6], 6
    tokens = _tokens(7, (2, 37 + steps))
    scheduler = server.check_scheduler(runner, 4)
    manager = scheduler.blocks
    got = server.drive(runner, scheduler, tokens, prompt_lens, steps)
    want = reference.logits_at(_model(0, 4), params, tokens, [(i, p) for i, p, _ in got])
    for (_, _, have), ref in zip(got, want):
        assert _rel(have, ref) < REL_TOL
    pools = manager.pool_stats()
    assert pools["full"]["released_behind"] == 0 and pools["full"]["in_use"] == 11 + 3
    # the long one holds the window's 2 or 3 blocks, not its 11
    assert pools["window"]["released_behind"] >= 8 and pools["window"]["in_use"] <= 3 + 3
    row_full, row_window = manager.table_row("check-0", runner.max_blocks_per_seq)
    assert all(row_full[:11]) and not any(row_window[:8]) and all(row_window[9:11])
    assert runner.recompiles_after_warmup() == 0


@pytest.mark.parametrize("variant", ["no_window", "window_plus_block", "window_minus_one", "no_yarn",
                                     "yarn_everywhere", "not_renormalised"])
def test_the_paged_path_is_told_from_each_wrong_model(served, variant):
    cfg, params, runner = served
    tokens = _tokens(9, (1, 40))
    got = server.drive(runner, server.check_scheduler(runner, 4), tokens, [36], 4)
    wrong = reference.logits_at(
        controls.wrong_model(_model(0, 4), variant, BS), params, tokens, [(i, p) for i, p, _ in got]
    )
    assert max(_rel(have, ref) for (_, _, have), ref in zip(got, wrong)) > 25 * REL_TOL


def test_the_familys_check_reads_all_four_readings_and_tells_the_wrong_layers(served, monkeypatch):
    cfg, params, runner = served
    model = {**_model(0, 4), "correctness": {
        "logit_rel_tol": 1e-3, "expert_ffn_rel_tol": 1e-3, "window_attn_rel_tol": 1e-3, "full_attn_rel_tol": 1e-3}}

    class Replica(server.BenchMellumServer):
        def __init__(self):  # the check reads the engine's runner and nothing else
            self.engine = type("E", (), {"runner": runner, "scheduler": server.check_scheduler(runner, 4)})()

    got = Replica().bench_check(model, 2**31 + 5, [37, 6], 2)
    assert got["finite"] and max(got["rel_err"]) < 1e-3
    names = [tuple(p) for p in got["positions"] if isinstance(p[0], str)]
    assert names == [("expert_ffn", "16"), ("expert_ffn", "4"), ("window_attn", "chunks"),
                     ("window_attn", "decode"), ("full_attn", "chunks"), ("full_attn", "decode")]
    assert got["window_attn"]["released_behind"] > 0 and got["full_attn"]["released_behind"] == 0
    assert got["pools"]["window"]["released_behind"] > 0
    # one layer alone tells the wrong masks and the wrong ropes, each in its kind
    for variant, kind in (("window_plus_block", "sliding_attention"), ("window_minus_one", "sliding_attention"),
                          ("yarn_everywhere", "sliding_attention"), ("no_yarn", "full_attention"),
                          ("no_attention_factor", "full_attention")):
        wrong = controls.wrong_model(model, variant, BS)
        ref = lambda m, p, h, k: mellum.reference_attention(wrong, p, h, k)  # noqa: E731
        alone = server.attention_alone(runner, model, 7, kind, ref)
        assert min(alone["worst"].values()) > 25 * REL_TOL, (variant, alone)
    ref = lambda m, p, h: mellum.reference_expert_ffn(controls.wrong_model(model, "not_renormalised"), p, h)  # noqa: E731
    assert min(server.expert_ffn_alone(runner, model, 7, ref)["worst"].values()) > 25 * REL_TOL


def test_what_shares_a_decode_batch_cannot_change_a_slot(served):
    cfg, params, runner = served
    tokens = _tokens(13, (3, 30))
    alone = server.drive(runner, server.check_scheduler(runner, 4), tokens[:1], [20], 3)
    among = server.drive(runner, server.check_scheduler(runner, 4), tokens, [20, 27, 5], 3)
    mine = [g for g in among if g[0] == 0]
    for (_, p, a), (_, q, b) in zip(alone, mine):
        assert p == q and _rel(a, b) < 1e-5


# -- the engine ---------------------------------------------------------------------------

def _engine(cfg, params, **kw):
    base = dict(num_blocks=40, block_size=BS, prefill_buckets=(8, 16),
                decode_buckets=(4,), max_decode_batch=4, warmup=False)
    base.update(kw)
    return InferenceEngine(cfg, params, EngineConfig(**base))


def _is_greedy(fwd, prompt, out):
    """Whether ``out`` is what greedy decoding gives after ``prompt``: one
    causal pass over both (padded to the one compiled length), the argmax before
    each emitted token."""
    seq = np.zeros((1, 64), np.int32)
    seq[0, : len(prompt) + len(out)] = list(prompt) + list(out)
    picks = np.asarray(jnp.argmax(fwd(jnp.asarray(seq))[0], axis=-1))
    return list(picks[len(prompt) - 1 : len(prompt) + len(out) - 1]) == list(out)


def test_the_engine_serves_through_both_pools_and_gives_every_block_back(served):
    cfg, params, _ = served
    engine = _engine(cfg, params).start()
    try:
        prompts = [list(map(int, _tokens(20 + i, (n,)))) for i, n in enumerate((30, 5, 19, 12, 41, 9))]
        results = [None] * len(prompts)

        def run(i):
            results[i] = list(engine.generate(prompts[i], max_new_tokens=7))

        threads = [threading.Thread(target=run, args=(i,)) for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        fwd = jax.jit(lambda t: L.forward(cfg, params, t))
        for prompt, out in zip(prompts, results):
            assert len(out) == 7 and _is_greedy(fwd, prompt, out)
        s = engine.stats()
        assert s["kv_layout"]["groups"]["window"]["keeps"] == W
        pools = s["kv_pools"]
        assert set(pools) == {"full", "window"}
        assert pools["full"]["in_use"] == pools["window"]["in_use"] == 0  # nothing leaks
        assert pools["window"]["released_behind"] > 0 and pools["full"]["released_behind"] == 0
        assert pools["window"]["peak_in_use"] <= 4 * (W // BS + 1 + 16 // BS)
        held = s["kv_held"]
        assert 0 < held["held_block_layers"] < held["one_table_block_layers"] and held["launches"] > 0
        assert s["prefix_cache"]["enabled"] is False  # switched off: a hit would skip released rows
        assert s["decode_width"]["live_tokens"] < s["decode_width"]["needed_tokens"] * 4
    finally:
        engine.stop()


@pytest.mark.parametrize("field, value, says", [
    ("kv_transfer_enabled", True, "digest says nothing of the window rows"),
    ("kv_tier_enabled", True, "whatever was released"),
    ("speculative_k", 2, "rejected tail"),
])
def test_what_cannot_run_over_a_window_group_is_refused_where_the_engine_is_made(served, field, value, says):
    cfg, params, _ = served
    with pytest.raises(ValueError, match=says):
        _engine(cfg, params, **{field: value})


def test_the_manager_refuses_prefix_reuse_beside_a_window_pool_and_the_engine_names_its_groups(served):
    with pytest.raises(ValueError, match="prefix reuse cannot run over a layer group that keeps a window"):
        PagedBlockManager(16, BS, prefix_cache_enabled=True, windows=[("window", 8, W)])
    cfg, params, _ = served
    # the window pool's size is no option: the null block, a window (and a
    # block's slack) a decode slot, a largest chunk for the request that is
    # being prefilled and one for the request whose last chunk has just run
    engine = _engine(cfg, params, max_decode_batch=3, decode_buckets=(3,))
    assert engine.runner.num_blocks == (40, 1 + 3 * (W // BS + 1) + 2 * (16 // BS))
    assert engine.stats()["kv_pools"]["window"]["blocks"] == 18
    assert not hasattr(EngineConfig(), "group_num_blocks")


# -- the block manager ------------------------------------------------------------------------

def _manager(full=32, window=12, keeps=W):
    return PagedBlockManager(full, BS, group="full", windows=[("window", window, keeps)])


def test_blocks_behind_the_window_are_released_and_only_whole_ones():
    m = _manager()
    assert m.grow_to("r", 6)  # decode-shaped: the query at 5 sees 0..5
    assert m.held_blocks("r") == [2, 2]
    for n in range(7, 30):
        assert m.grow_to("r", n)
        query = n - 1
        first_seen = max(0, query - W + 1)
        full_row, window_row = m.table_row("r", 16)
        held = [i for i, blk in enumerate(window_row) if blk]
        # exactly the blocks from the one that holds the first visible key to the query's
        assert held == list(range(first_seen // BS, query // BS + 1)), n
        assert all(full_row[: -(-n // BS)]) and not any(full_row[-(-n // BS):])
    pools = m.pool_stats()
    assert pools["window"]["released_behind"] == pools["window"]["taken"] - pools["window"]["in_use"] > 0
    assert pools["window"]["in_use"] == len(held) <= W // BS + 1
    assert pools["full"]["in_use"] == 8 and pools["full"]["released_behind"] == 0
    m.free("r")
    assert m.pool_stats()["window"]["in_use"] == 0 and m.pool_stats()["full"]["in_use"] == 0


def test_a_prefill_chunk_holds_the_window_and_the_chunk():
    m = _manager(full=64, window=12)
    total = 50
    peak = 0
    for start in range(0, 48, 16):
        assert m.grow_to("r", total, (start, start + 16))
        _, window_row = m.table_row("r", 16)
        held = [i for i, blk in enumerate(window_row) if blk]
        assert held == list(range(max(0, start - W + 1) // BS, (start + 16) // BS)), start
        peak = max(peak, len(held))
    assert peak == (W + 16) // BS and m.pool_stats()["window"]["peak_in_use"] == peak


def test_admission_waits_on_either_pool():
    from ray_tpu.inference.scheduler import ContinuousBatchingScheduler, Request

    def scheduler(full, window):
        m = _manager(full=full, window=window)
        return m, ContinuousBatchingScheduler(m, max_decode_batch=4, max_prefill_chunk=16, max_prefills_per_step=2)

    def req(rid, n):
        return Request(request_id=rid, prompt=list(range(1, n + 1)), max_new_tokens=4)

    # the window pool is dry: 5 usable blocks, the first request's chunk of 16 takes 4
    m, s = scheduler(full=64, window=6)
    s.add(req("a", 20))
    s.add(req("b", 20))
    plan = s.schedule()
    assert [r.request_id for r, _, _ in plan.prefills] == ["a"] and len(s.waiting) == 1
    # the full pool is dry: 6 usable blocks hold one prompt of 20 (+1), not two
    m, s = scheduler(full=7, window=32)
    s.add(req("a", 20))
    s.add(req("b", 20))
    plan = s.schedule()
    assert [r.request_id for r, _, _ in plan.prefills] == ["a"] and len(s.waiting) == 1
    assert m.held_blocks("b") == [0, 0]  # a queued request holds nothing in either pool


@pytest.mark.parametrize("seed", range(4))
def test_nothing_leaks_over_a_random_walk_of_finishes_cancels_and_preemptions(seed):
    """Grow (by chunks, then by decode steps), trim, evict and free requests
    in a seeded order over pools that run dry: the two free lists and the
    tables always account for every block, and at the end both pools are whole."""
    rng = np.random.default_rng(seed)
    m = _manager(full=40, window=14)
    live = {}
    for step in range(400):
        roll = rng.random()
        if roll < 0.25 and len(live) < 6:
            rid = f"r{step}"
            total = int(rng.integers(2, 60))
            if m.grow_to(rid, total + 1, (0, min(total + 1, 16))):
                live[rid] = [total, min(total, 16)]  # prompt length, prefilled so far
        elif live:
            rid = list(live)[int(rng.integers(len(live)))]
            total, done = live[rid]
            if roll < 0.35:
                m.free(rid)  # finished or cancelled
                del live[rid]
            elif roll < 0.42:
                m.evict(rid)  # preempted: everything back, to be replayed from 0
                del live[rid]
            elif done < total:
                end = min(total, done + 16)
                if m.grow_to(rid, total + 1, (done, total + 1 if end == total else end)):
                    live[rid][1] = end
            elif m.grow_to(rid, total + 2):
                live[rid] = [total + 1, total + 1]  # a decode step committed a token
        pools = m.pool_stats()
        held = [m.held_blocks(rid) for rid in live]
        assert pools["full"]["in_use"] == sum(h[0] for h in held)
        assert pools["window"]["in_use"] == sum(h[1] for h in held)
        tables = [blk for rid in live for blk in m.table_row(rid, 64)[1] if blk]
        assert len(tables) == len(set(tables)) and 0 not in tables  # no block in two tables
    for rid in list(live):
        m.free(rid)
    pools = m.pool_stats()
    assert pools["full"]["in_use"] == pools["window"]["in_use"] == 0
    assert len(m.windows[0].free) == 13 and pools["window"]["taken"] >= pools["window"]["released_behind"]


def test_one_group_is_the_manager_it_was():
    m = PagedBlockManager(16, BS)
    assert m.windows == () and m.grow_to("r", 9)
    assert m.table_row("r", 6) == [m.owned("r")[0], m.owned("r")[1], m.owned("r")[2], 0, 0, 0]
    assert set(m.pool_stats()) == {"all"} and m.pool_stats()["all"]["in_use"] == 3
    assert m.held_blocks("r") == [3]
    m.free("r")
    assert m.stats()["free_blocks"] == 15


# -- the decode kernel: a flat cache of 4 KV heads, a first live block --------------------------------

HD, N_KV, REP, M = 128, 4, 8, 12
FULL = M * BS


def _decode_case(contexts, keeps, window=1, seed=0, flat=True):
    """Slots at ``contexts`` (0: a padding slot) over a shuffled pool. The
    clean cache for the materialised softmax; the kernel's is NaN / inf
    wherever it must not read: past a slot's last position, every block no
    table holds, and (a window) every block wholly behind the first query's
    window, whose table entries are the null block as the manager leaves them."""
    rng = np.random.default_rng(seed)
    B = len(contexts)
    N = 1 + B * M
    k, v = rng.standard_normal((2, 2, N, BS, N_KV, HD)).astype(np.float32)
    shuffled = rng.permutation(np.arange(1, N))
    tables = np.zeros((B, M), np.int32)
    pos = np.zeros((B, window), np.int32)
    live = np.zeros((N, BS), bool)
    for b, ctx in enumerate(contexts):
        if not ctx:
            continue
        tables[b] = shuffled[b * M:(b + 1) * M]
        pos[b] = np.minimum(ctx - 1 + np.arange(window), FULL - 1)
        first = max(0, pos[b].min() - keeps + 1) // BS if keeps else 0
        for p in range(first * BS, pos[b].max() + 1):
            live[tables[b, p // BS], p % BS] = True
        tables[b, :first] = 0  # given back: the entries read the null block
    kp, vp = k.copy(), v.copy()
    kp[:, ~live], vp[:, ~live] = np.nan, np.inf
    shape = (2, N, BS * N_KV, HD) if flat else (2, N, BS, N_KV, HD)
    q = rng.standard_normal((B, window, N_KV * REP, HD)).astype(np.float32)
    to = lambda a: jnp.asarray(a.reshape(shape))  # noqa: E731
    return jnp.asarray(q), (to(k), to(v)), (to(kp), to(vp)), jnp.asarray(tables), jnp.asarray(pos)


def _softmax_over(q, k, v, tables, pos, keeps):
    """The materialised softmax over each slot's whole table, masked by
    position: what the kernel has to equal for the real slots."""
    B, C, H, hd = q.shape
    ks = np.asarray(k).reshape(2, -1, BS, N_KV, hd)[1][np.asarray(tables)].reshape(B, FULL, N_KV, hd)
    vs = np.asarray(v).reshape(2, -1, BS, N_KV, hd)[1][np.asarray(tables)].reshape(B, FULL, N_KV, hd)
    qg = np.asarray(q).reshape(B, C, N_KV, H // N_KV, hd)
    s = np.einsum("bcgrh,bsgh->bcgrs", qg, ks) / np.sqrt(hd)
    j = np.arange(FULL)[None, None, :]
    i = np.asarray(pos)[:, :, None]
    seen = (j <= i) & ((j > i - keeps) if keeps else True)
    s = np.where(seen[:, :, None, None, :], s, -1e30)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    return np.einsum("bcgrs,bsgh->bcgrh", p, np.where(seen[:, :, None, None, :].any(axis=(1, 2, 3))[..., None, None], vs, 0)).reshape(B, C, H, hd)


@pytest.mark.parametrize("keeps", [0, 8, 10], ids=["keeps_all", "window_8", "window_10_not_whole_blocks"])
@pytest.mark.parametrize("window", [1, 2], ids=["decode", "two_rows"])
def test_the_decode_kernel_over_a_flat_cache_of_four_kv_heads(keeps, window):
    contexts = (1, BS, BS + 1, 3 * BS - 1, 29, FULL - 1, 0, 17, 0)
    q, clean, poisoned, tables, pos = _decode_case(contexts, keeps, window)
    have = PA.paged_attention(q, *poisoned, 1, tables, pos, interpret=True, n_kv=N_KV, keeps=keeps, wave_blocks=3)
    have = np.asarray(have, np.float32)
    assert np.isfinite(have).all()
    padding = [b for b, c in enumerate(contexts) if not c]
    assert (have[padding] == 0).all()  # a padding slot reads nothing
    want = _softmax_over(q, *clean, tables, pos, keeps)
    real = [b for b, c in enumerate(contexts) if c]
    np.testing.assert_allclose(have[real], want[real], atol=2e-5 * np.abs(want[real]).max(), rtol=0)


def test_the_flat_cache_is_the_five_d_cache_in_the_same_bytes():
    q, clean, _, tables, pos = _decode_case((5, 29, 0, 13), 0, flat=False)
    five = PA.paged_attention(q, *clean, 1, tables, pos, interpret=True)
    flat = PA.paged_attention(q, *(a.reshape(2, -1, BS * N_KV, HD) for a in clean), 1, tables, pos,
                              interpret=True, n_kv=N_KV)
    np.testing.assert_array_equal(np.asarray(five), np.asarray(flat))


@pytest.mark.parametrize("backend, shape, n_kv, serves", [
    ("tpu", (7, 100, 16 * 4, 128), 4, True),      # Mellum2's: stored flat, n_kv said beside it
    ("tpu", (7, 100, 16, 4, 128), None, False),   # 4 heads as a dimension of their own: padded tiles
    ("tpu", (7, 100, 16, 8, 128), None, True),    # Mistral's, as ever
    ("tpu", (7, 100, 16 * 4, 128), None, False),  # flat and nobody says the heads
    ("tpu", (7, 100, 4 * 2, 128), 2, False),      # a block under a (16, 128) tile
    ("cpu", (7, 100, 16 * 4, 128), 4, False),
])
def test_which_caches_the_decode_kernel_serves(backend, shape, n_kv, serves):
    cache = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert PA.kernel_serves(1, 32, cache, backend=backend, n_kv=n_kv) is serves


def test_the_layout_stores_four_kv_heads_flat_and_eight_as_they_were():
    mellum2 = dataclasses.replace(L.LlamaConfig.tiny(), n_heads=32, n_kv_heads=4, attn_head_dim=128)
    assert L.cache_layout(mellum2, 16).init(3)["k"].shape == (2, 3, 64, 128)
    mistral = dataclasses.replace(L.LlamaConfig.tiny(), n_heads=32, n_kv_heads=8, attn_head_dim=128)
    assert L.cache_layout(mistral, 16).init(3)["k"].shape == (2, 3, 16, 8, 128)
    assert L.cache_layout(L.LlamaConfig.tiny(), 8).init(3)["k"].shape[2:] == (8, 2, 16)


# -- the chunk kernel: grouped heads, a window ---------------------------------------------------------

TILE, KEYS = 16, 96


def _materialised(q, k, v, ctx_len, n, group, window):
    H, C, d = q.shape
    kk, vv = np.repeat(np.asarray(k), group, axis=0), np.repeat(np.asarray(v), group, axis=0)
    s = np.einsum("hcd,hsd->hcs", np.asarray(q), kk) / np.sqrt(d)
    i = ctx_len + np.arange(C)[:, None]
    j = np.arange(kk.shape[1])[None, :]
    seen = (j <= i) & ((j > i - window) if window else True)
    s = np.where(seen[None], s, -1e30)
    p = np.exp(s - s.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    return np.einsum("hcs,hsd->hcd", p, vv)[:, :n]


@pytest.mark.parametrize("window", [0, 16, 24, 40], ids=["all", "a_tile", "a_tile_and_a_half", "two_and_a_half"])
@pytest.mark.parametrize("ctx_len, n", [(0, 32), (1, 32), (15, 20), (16, 32), (41, 32), (64, 32), (50, 7)])
@pytest.mark.parametrize("group", [1, 4], ids=["a_key_head_a_query_head", "four_query_heads_a_key_head"])
def test_the_chunk_kernel_with_grouped_heads_and_a_window(monkeypatch, group, ctx_len, n, window):
    """Six key tiles of 16, two query tiles. K and V past the live context are
    NaN, and under a window so is every key tile WHOLLY behind the window of
    the chunk's first query: one fetched tile too many and the output is not
    finite (a masked score cannot hide a NaN in V: V is zeroed only past the
    live context)."""
    monkeypatch.setattr(latent_flash, "_QUERY_TILE", TILE)
    monkeypatch.setattr(latent_flash, "_KEY_TILE", TILE)
    rng = np.random.default_rng(ctx_len + window)
    H, C, d = 8, 32, 32
    q = rng.standard_normal((H, C, d)).astype(np.float32)
    k, v = rng.standard_normal((2, H // group, KEYS, d)).astype(np.float32)
    kp, vp = k.copy(), v.copy()
    kp[:, ctx_len + n:], vp[:, ctx_len + n:] = np.nan, np.nan
    if window:
        behind = max(0, ctx_len - window + 1) // TILE * TILE
        kp[:, :behind], vp[:, :behind] = np.nan, np.nan
    have = latent_flash.flash_attention(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp), ctx_len, n, scale=1 / np.sqrt(d),
        group=group, window=window or None, interpret=True,
    )
    have = np.asarray(have, np.float32)
    assert np.isfinite(have).all()
    want = _materialised(q, k, v, ctx_len, n, group, window)
    np.testing.assert_allclose(have[:, :n], want, atol=2e-5 * np.abs(want).max(), rtol=0)
    assert (have[:, -(-n // TILE) * TILE:] == 0).all()  # a query tile with no real query ran nothing


# -- nothing that runs today changes ---------------------------------------------------------------------

def _tpu_text(f, *args):
    return jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",)).as_text()


def test_absent_arguments_lower_to_the_programs_that_were_there():
    """The decode kernel without ``keeps`` has the five scalar operands it had
    and says nothing of a first block; the chunk kernel without ``group`` and
    ``window`` has the index maps it had; saying the defaults aloud is the
    same program."""
    q = jnp.zeros((4, 1, 16, 128), jnp.bfloat16)
    cache = jnp.zeros((2, 9, 16, 8, 128), jnp.bfloat16)
    tables, pos = jnp.zeros((4, 6), jnp.int32), jnp.zeros((4, 1), jnp.int32)
    absent = _tpu_text(lambda q, k, v, t, p: PA.paged_attention(q, k, v, 1, t, p, interpret=False), q, cache, cache, tables, pos)
    said = _tpu_text(lambda q, k, v, t, p: PA.paged_attention(q, k, v, 1, t, p, interpret=False, n_kv=8, keeps=0),
                     q, cache, cache, tables, pos)
    kept = _tpu_text(lambda q, k, v, t, p: PA.paged_attention(q, k, v, 1, t, p, interpret=False, keeps=32),
                     q, cache, cache, tables, pos)
    assert absent == said != kept
    qf, kf = jnp.zeros((8, 256, 128), jnp.bfloat16), jnp.zeros((8, 512, 128), jnp.bfloat16)
    flash = lambda **kw: _tpu_text(  # noqa: E731
        lambda q, k, v: latent_flash.flash_attention(q, k, v, 0, 256, scale=0.1, interpret=False, **kw), qf, kf, kf)
    assert flash() == flash(group=1, window=None) != flash(window=128)


def _paged_layers_as_they_were(cfg, params, cache, x, pos, valid, block_tables):
    """``llama._paged_layers`` of the parent of PR 44, word for word: one
    table, one rope table, every layer in the arrays ``k`` and ``v``."""
    bs = cache["k"].shape[2]
    blk = jnp.where(valid, paged_kv.block_at(block_tables, pos, bs), 0)
    off = pos % bs
    cos, sin = L._rope_at(cfg, pos)
    loads = []
    for layer, p in enumerate(params["layers"]):
        q, k, v = L._qkv(cfg, p, rms_norm(x, p["attn_norm"], cfg.norm_eps))
        q = L._apply_rope_flat(q, cos, sin)
        k = L._apply_rope_flat(k, cos, sin)
        cache = paged_kv.scatter_kv(cache, layer, blk, off, k, v)
        o = paged_kv.attention(
            q, cache["k"], cache["v"], layer, block_tables, pos, n_kv=cfg.n_kv_heads, head_dim=cfg.head_dim)
        x = x + jnp.einsum("bchk,hkd->bcd", o.astype(x.dtype), p["wo"])
        x = L._ffn_residual(cfg, p, x, valid, loads)
    return cache, x, loads


@pytest.mark.parametrize("cfg", [
    L.LlamaConfig.tiny(),
    dataclasses.replace(L.LlamaConfig.tiny(), moe_experts=4, moe_top_k=2),
    dataclasses.replace(L.LlamaConfig.tiny(), dim=1024, n_heads=8, n_kv_heads=8, max_seq_len=512, dtype=jnp.bfloat16),
], ids=["dense", "experts", "eight_kv_heads_of_128"])
def test_a_configuration_without_kinds_lowers_to_the_three_programs_it_had(monkeypatch, cfg):
    """There is ONE paged body, over the layout's groups. For a configuration
    of one group it lowers to the text the body of one table lowered to: the
    three entry points, for the chip and for the CPU."""
    params = jax.eval_shape(lambda: L.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: L.init_paged_kv_cache(cfg, 24, 8))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    B, C, M = 2, 12, 8  # a chunk that is no whole number of blocks: written by rows, as every window was (PR 66)

    def texts():
        steps = (
            (L.paged_prefill_step, (i32(C), i32(M), i32(), i32())),
            (L.paged_verify_step, (i32(B, 4), i32(B, M), i32(B), i32(B))),
            (L.paged_decode_step, (i32(B), i32(B), i32(B, M), i32(B))),
        )
        return [
            jax.jit(partial(step, cfg)).trace(params, cache, *args).lower(lowering_platforms=(platform,)).as_text()
            for step, args in steps for platform in ("cpu", "tpu")
        ]

    now = texts()
    monkeypatch.setattr(L, "_paged_layers", _paged_layers_as_they_were)
    assert now == texts()


def test_a_plain_configuration_of_four_wide_kv_heads_is_served_from_its_flat_cache():
    """Four KV heads of 128 are stored joined to the tokens whatever the
    configuration's kinds (``cache_layout`` goes by the shapes): a plain GQA
    model of that shape writes and reads such a cache through the same body.
    Chunked prefill over several blocks, then decode, against ``forward``."""
    cfg = dataclasses.replace(L.LlamaConfig.tiny(), n_heads=8, n_kv_heads=4, attn_head_dim=128, max_seq_len=64)
    params = L.init_params(cfg, jax.random.PRNGKey(3))
    runner = PagedModelRunner(cfg, params, num_blocks=20, block_size=4, prefill_buckets=(8,), decode_buckets=(2,))
    assert runner.cache["k"].shape == (2, 20, 4 * 4, 128) and runner._tables((), 2).shape == (2, 16)
    tokens = _tokens(5, (1, 27))
    want = L.forward(cfg, params, jnp.asarray(tokens))[0]
    row = np.zeros(runner.max_blocks_per_seq, np.int32)
    row[:7] = np.arange(7) + 3
    for start in (0, 8, 16):  # positions past the first block of 4, and past 16 = 4 tokens x 4 heads
        logits = runner.prefill_chunk(tokens[0, start : start + 8], row, start)
    assert _rel(logits, want[23]) < REL_TOL
    for p in (24, 25, 26):
        logits = runner.decode([int(tokens[0, p])], [p], [row], [p + 1])
        assert _rel(logits[0], want[p]) < REL_TOL
    assert runner._path_name(8) == "gather"
