"""Laguna-XS.2's block through ``ray_tpu.models.llama`` (layer KINDS that
differ in more than their window: query heads, the rope's base, its rotated
width, YaRN; a sigmoid gate a head; a dense layer 0 beside sigmoid-routed
layers with a shared expert and a held range) and the paged cache of two layer
groups under it with a window NARROWER than a prefill chunk, against the plain
reference ``perfbench/families/laguna/reference.py`` at a toy size: the dense
layer and two periods (F W W W F W W W F), 6 / 8 query heads over 2 KV heads
of 16, a window of 8 under chunks of 16, blocks of 4, 8 experts of which a
range is held, 2 a token. Float32 on both sides. The wrong models a limit has
to tell are ``tests/perfbench/laguna_controls.py``."""

import dataclasses
import os
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests", "perfbench"))

import laguna_controls as controls  # noqa: E402
from perfbench.families import laguna  # noqa: E402
from perfbench.families.laguna import reference, server  # noqa: E402
from ray_tpu.inference.engine import EngineConfig, InferenceEngine  # noqa: E402
from ray_tpu.inference.model_runner import PagedModelRunner  # noqa: E402
from ray_tpu.models import llama as L  # noqa: E402
from ray_tpu.models.interface import LayerGroup, model_of  # noqa: E402

REL_TOL = 2e-4
W, BS = 8, 4
_PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
MODEL = {
    **laguna.TOY_SIZES, "family": "laguna", "num_hidden_layers": 9, "sliding_window": W,
    "layer_types": ["full_attention"] + _PERIOD * 2, "num_attention_heads_per_layer": [6] + [8, 8, 8, 6] * 2,
    "mlp_layer_types": ["dense"] + ["sparse"] * 8, "num_experts": 8, "rms_norm_eps": 1e-6,
    "gating": True, "scoring_func": "sigmoid", "norm_topk_prob": True, "moe_routed_scaling_factor": 2.5,
    "moe_apply_router_weight_on_input": False, "attention_bias": False, "tie_word_embeddings": False,
    "deployment": {"num_experts_total": 8, "held_experts": [0, 8]},
}


def _model(lo=0, hi=8):
    return {**MODEL, "num_experts": hi - lo, "deployment": {"num_experts_total": 8, "held_experts": [lo, hi]}}


def _cfg(model=MODEL, **overrides):
    return laguna.model_config(model, max_seq_len=64, **overrides)


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


@pytest.fixture(scope="module")
def served():
    cfg = _cfg(_model(0, 4))
    params = _params(cfg)
    runner = PagedModelRunner(
        cfg, params, num_blocks=(40, 24), block_size=BS, prefill_buckets=(8, 16), decode_buckets=(4,),
    )
    runner.warmup()
    return cfg, params, runner


# -- the configuration and forward ---------------------------------------------------

def test_the_adapter_names_a_kind_a_layer_and_the_model_is_llamas():
    cfg = _cfg(_model(2, 6))
    assert model_of(cfg) is L.MODEL  # found by its config's type: the one body
    full, window = cfg.kind_of(0), cfg.kind_of(1)
    assert cfg.kinds == (full, window) and cfg.kind_of(8) == full and cfg.kind_of(7) == window
    assert (full.window, full.n_heads, full.rope_theta, full.rotary_dim) == (0, 6, 500000.0, 8)
    assert full.rope_scaling == L.RopeScaling(4.0, 32, 8.0, 1.0, 1.1386294361119891)
    assert (window.window, window.n_heads, window.rope_theta, window.rotary_dim, window.rope_scaling) == (
        W, 8, 10000.0, 0, None)
    assert cfg.layer_windows == (0, W, W, W, 0, W, W, W, 0)  # read from the kinds: what the cache groups by
    assert cfg.kind_of_window(W) == window and cfg.kind_of_window(0) == full
    assert (cfg.attn_gate, cfg.dense_layers, cfg.dense_mlp_hidden, cfg.moe_shared_hidden) == (True, (0,), 96, 32)
    assert (cfg.moe_scoring, cfg.moe_scale, cfg.moe_renormalize, cfg.moe_held, cfg.moe_experts) == (
        "sigmoid", 2.5, True, (2, 6), 8)
    dense, sparse, last = (L._layer_shapes(cfg, l) for l in (0, 1, 8))
    assert (dense["wq"], dense["wo"], dense["wg"], dense["w_gate"]) == ((64, 6, 16), (6, 16, 64), (64, 6), (64, 96))
    assert "router" not in dense and "shared_gate" not in dense
    assert (sparse["wq"], sparse["wg"], sparse["router"], sparse["w_gate"], sparse["shared_down"]) == (
        (64, 8, 16), (64, 8), (64, 8), (4, 64, 32), (32, 64))
    assert last["wq"] == (64, 6, 16) and last["w_down"] == (4, 32, 64)
    params = _params(cfg)
    assert L.param_count(cfg) == sum(x.size for x in jax.tree_util.tree_leaves(params))
    axes = L.logical_axes(cfg)
    assert [set(a) for a in axes["layers"]] == [set(p) for p in params["layers"]]
    assert axes["layers"][0]["w_gate"] == ("embed", "mlp") and axes["layers"][1]["w_gate"] == ("expert", "embed", "mlp")
    layout = L.cache_layout(cfg, BS)
    assert layout.groups == (LayerGroup("full", (0, 4, 8), 0), LayerGroup("window", (1, 2, 3, 5, 6, 7), W))
    with pytest.raises(ValueError, match="layer_kinds names"):
        dataclasses.replace(cfg, n_layers=8)
    with pytest.raises(ValueError, match="kinds of layer keep a window of 3"):
        cfg.kind_of_window(3)
    with pytest.raises(ValueError, match="one rank"):
        L.partition_rules(cfg, None)
    with pytest.raises(ValueError, match="held"):
        laguna.model_config({**_model(0, 4), "num_experts": 8}, max_seq_len=64)
    with pytest.raises(ValueError, match="gating=True"):
        laguna.model_config({**MODEL, "gating": "per-channel"}, max_seq_len=64)
    with pytest.raises(SystemExit, match="served only"):
        laguna.train_program()


def test_a_configuration_without_kinds_has_the_kinds_its_plain_fields_spell():
    plain = L.LlamaConfig.tiny()
    assert plain.kinds == (L.LayerKind(0, 4, 10000.0, 0, None),) and plain.layer_windows == ()
    yarn = L.RopeScaling(4.0, 32)
    mellum_like = L.LlamaConfig.tiny(n_layers=4, layer_windows=(8, 8, 8, 0), rope_scaling=yarn, rope_theta=5e5)
    assert mellum_like.kinds == (L.LayerKind(8, 4, 5e5, 0, None), L.LayerKind(0, 4, 5e5, 0, yarn))
    assert mellum_like.kind_of_window(8) == mellum_like.kind_of(0)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_matches_the_plain_reference(seed):
    cfg = _cfg()
    params = _params(cfg, seed)
    tokens = _tokens(seed, (2, 40))  # past the window (8) and past YaRN's original context (32)
    have = L.forward(cfg, params, jnp.asarray(tokens))
    picks = [(b, t) for b in range(2) for t in range(40)]
    want = reference.logits_at(MODEL, params, tokens, picks).reshape(2, 40, -1)
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
    wrong = reference.logits_at(
        controls.wrong_model(MODEL, variant, BS), controls.wrong_params(MODEL, params, variant), tokens, picks
    )
    assert _rel(have, wrong) > 25 * REL_TOL


def test_a_precision_lower_is_another_model():
    cfg = _cfg()
    params = _params(cfg)
    tokens = _tokens(3, (1, 24))
    have = L.forward(cfg, params, jnp.asarray(tokens))[0]
    for variant in controls.LOW_PARAMS:
        low = reference.logits_at(MODEL, controls.low_params(params, variant), tokens, [(0, t) for t in range(24)])
        assert _rel(have, low) > 25 * REL_TOL, variant


def test_the_shares_of_all_ranges_and_the_shared_expert_once_sum_to_the_uncut_layer():
    """Each chip computes its own part of a layer's FFN from the same router
    over all experts, and the shared expert whole; the routed parts and the
    shared expert ONCE add up to the uncut reference's layer (the exchange that
    adds them is the deployment's, not stood in for)."""
    whole_cfg = _cfg()
    params = _params(whole_cfg)
    p = params["layers"][2]
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 12, whole_cfg.dim), jnp.float32)
    rows = h.reshape(-1, whole_cfg.dim)
    want_whole, _ = reference.expert_ffn(reference.sizes(MODEL), p, rows)
    shared = reference.mlp(p["shared_gate"], p["shared_up"], p["shared_down"], rows)
    parts = []
    for lo in range(0, 8, 2):
        cfg = _cfg(_model(lo, lo + 2))
        held = {**p, **{k: p[k][lo : lo + 2] for k in ("w_gate", "w_up", "w_down")}}
        part = L._ffn(cfg, held, h)[0].reshape(-1, whole_cfg.dim)
        want, _ = reference.expert_ffn(reference.sizes(_model(lo, lo + 2)), held, rows)
        assert _rel(part, want) < REL_TOL
        parts.append(part - shared)  # what every chip computes alike is counted once
    assert _rel(sum(parts) + shared, want_whole) < REL_TOL
    assert _rel(L._ffn(whole_cfg, p, h)[0].reshape(-1, whole_cfg.dim), want_whole) < REL_TOL
    assert all(float(jnp.abs(part).max()) > 0 for part in parts)
    # the dense layer's FFN is the plain MLP of intermediate_size, no load counted
    out, aux = L._ffn(whole_cfg, params["layers"][0], h)
    p0 = params["layers"][0]
    assert aux is None and _rel(out.reshape(-1, 64), reference.mlp(p0["w_gate"], p0["w_up"], p0["w_down"], rows)) < REL_TOL


# -- the paged steps through the grouped cache -----------------------------------------

def test_chunked_prefill_wider_than_the_window_then_decode_matches_the_reference(served):
    """A prompt of 37 (chunks of 16, 16, 5 under a window of 8: every whole
    chunk is TWO windows wide, so the window table slides inside the prefill)
    beside one of 6 (inside the window), then six decode steps that cross a
    block boundary (40) and release another block."""
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
    assert pools["window"]["released_behind"] >= 8 and pools["window"]["in_use"] <= 3 + 3
    # a chunk of 16 under a window of 8 in blocks of 4: window 2 + chunk 4 + 1 blocks at most while it runs
    assert pools["window"]["peak_in_use"] <= 7 + 2
    row_full, row_window = manager.table_row("check-0", runner.max_blocks_per_seq)
    assert all(row_full[:11]) and not any(row_window[:8]) and all(row_window[9:11])
    assert runner.recompiles_after_warmup() == 0
    # of what the decode launches read, the window groups' part, by layers
    dw = runner.decode_width
    assert 0 < dw["window_read_tokens"] < dw["gathered_tokens"]
    assert dw["window_read_tokens"] == pytest.approx(dw["gathered_tokens"] * 6 / 9)  # the gather: every layer the table whole


@pytest.mark.parametrize("variant", [v for v in controls.WRONG_MODELS if v != "layer0_routed"])
def test_the_paged_path_is_told_from_each_wrong_model(served, variant):
    cfg, params, runner = served
    tokens = _tokens(9, (1, 40))
    model = _model(0, 4)
    got = server.drive(runner, server.check_scheduler(runner, 4), tokens, [36], 4)
    wrong = reference.logits_at(
        controls.wrong_model(model, variant, BS), controls.wrong_params(model, params, variant), tokens,
        [(i, p) for i, p, _ in got],
    )
    assert max(_rel(have, ref) for (_, _, have), ref in zip(got, wrong)) > 25 * REL_TOL


def test_the_familys_check_reads_all_four_readings_and_tells_the_wrong_layers(served):
    cfg, params, runner = served
    model = {**_model(0, 4), "correctness": {
        "logit_rel_tol": 1e-3, "expert_ffn_rel_tol": 1e-3, "window_attn_rel_tol": 1e-3, "full_attn_rel_tol": 1e-3}}

    class Replica(server.BenchLagunaServer):
        def __init__(self):  # the check reads the engine's runner and nothing else
            self.engine = type("E", (), {"runner": runner, "scheduler": server.check_scheduler(runner, 4)})()

    got = Replica().bench_check(model, 2**31 + 5, [37, 6], 2)
    assert got["finite"] and max(got["rel_err"]) < 1e-3
    names = [tuple(p) for p in got["positions"] if isinstance(p[0], str)]
    assert names == [("expert_ffn", "16"), ("expert_ffn", "4"), ("window_attn", "chunks"),
                     ("window_attn", "decode"), ("full_attn", "chunks"), ("full_attn", "decode")]
    assert got["window_attn"]["released_behind"] > 0 and got["full_attn"]["released_behind"] == 0
    assert got["pools"]["window"]["released_behind"] > 0
    # one layer alone tells what is wrong in its kind (and the gate in both)
    for variants, kind in ((controls.OF_A_WINDOW_LAYER + controls.OF_A_LAYER, "sliding_attention"),
                           (controls.OF_A_FULL_LAYER + controls.OF_A_LAYER, "full_attention")):
        for variant in variants:
            wrong = controls.wrong_model(model, variant, BS)
            ref = lambda m, p, h, k: laguna.reference_attention(  # noqa: E731
                wrong, controls.wrong_layer_params(model, p, variant), h, k)
            alone = server.attention_alone(runner, model, 7, kind, ref)
            assert min(alone["worst"].values()) > 25 * REL_TOL, (variant, alone)
    sparse = type("R", (), {"cfg": cfg, "params": {"layers": params["layers"][1:]},
                            "prefill_buckets": runner.prefill_buckets, "decode_buckets": runner.decode_buckets})()
    for variant in controls.OF_THE_FFN:
        ref = lambda m, p, h: laguna.reference_expert_ffn(controls.wrong_model(model, variant), p, h)  # noqa: E731
        assert min(server.expert_ffn_alone(sparse, model, 7, ref)["worst"].values()) > 25 * REL_TOL, variant


def test_what_shares_a_decode_batch_cannot_change_a_slot(served):
    cfg, params, runner = served
    tokens = _tokens(13, (3, 30))
    alone = server.drive(runner, server.check_scheduler(runner, 4), tokens[:1], [20], 3)
    among = server.drive(runner, server.check_scheduler(runner, 4), tokens, [20, 27, 5], 3)
    mine = [g for g in among if g[0] == 0]
    for (_, p, a), (_, q, b) in zip(alone, mine):
        assert p == q and _rel(a, b) < 1e-5


# -- the engine ---------------------------------------------------------------------------

def _is_greedy(fwd, prompt, out):
    seq = np.zeros((1, 64), np.int32)
    seq[0, : len(prompt) + len(out)] = list(prompt) + list(out)
    picks = np.asarray(jnp.argmax(fwd(jnp.asarray(seq))[0], axis=-1))
    return list(picks[len(prompt) - 1 : len(prompt) + len(out) - 1]) == list(out)


def test_the_engine_serves_it_on_the_normal_path_and_says_the_heads_a_kind(served):
    cfg, params, _ = served
    engine = InferenceEngine(cfg, params, EngineConfig(
        num_blocks=64, block_size=BS, prefill_buckets=(8, 16), decode_buckets=(4,), max_decode_batch=4, warmup=False,
    )).start()
    try:
        # as many requests as decode slots, as in the cell: the window pool is sized for a full batch
        prompts = [list(map(int, _tokens(20 + i, (n,)))) for i, n in enumerate((30, 19, 41, 9))]
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
        # the window pool as the engine sizes it: a window and a block a slot, two largest chunks beside
        assert engine.runner.num_blocks == (64, 1 + 4 * (W // BS + 1) + 2 * (16 // BS))
        pools = s["kv_pools"]
        assert pools["full"]["in_use"] == pools["window"]["in_use"] == 0  # nothing leaks
        assert pools["window"]["released_behind"] > 0 and s["scheduler"]["total_preempted"] == 0
        assert 0 < s["decode_width"]["window_read_tokens"] < s["decode_width"]["gathered_tokens"]
        assert s["moe"]["decode"]["expert_layers"] > 0  # 8 sparse layers' loads a launch, none of the dense one
        path = engine.runner.attention_paths[1].name
        assert path == "gather+window[full:6h,window:8h]"
    finally:
        engine.stop()
