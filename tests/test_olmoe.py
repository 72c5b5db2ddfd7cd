"""The OLMoE block on the normal path, at toy sizes on the CPU in float32
with seeded weights: QK-norm and the dropless top-k experts in ``forward``
and in the three paged steps, against the plain reference of the benchmark's
``olmoe`` family (``perfbench/families/olmoe/reference.py``: a loop over the
experts with a mask each, no sort, no cache, nothing of the program).

Tolerances. Both sides compute in float32 from the same weights; they differ
in the order of summation (grouped by expert and summed over k here, summed
over experts there; paged attention over a padded table here, a full causal
softmax there). Logits are O(1); 2e-4 of the largest reference logit is some
hundred float32 roundings through two layers and a thousand times tighter
than what leaving out a gate's renormalisation, a norm or an expert moves
(the last three tests of this file read 1e-2 and more)."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests", "perfbench"))

import olmoe_controls as controls  # noqa: E402 - the reference's twin with the wrong models
from perfbench.families.olmoe import reference  # noqa: E402
from ray_tpu.inference.model_runner import PagedModelRunner  # noqa: E402
from ray_tpu.models import llama as L  # noqa: E402
from ray_tpu.models import paged_kv  # noqa: E402
from ray_tpu.ops.layers import rms_norm  # noqa: E402
from ray_tpu.ops.moe import dropless_moe_ffn, init_moe_params, moe_ffn, route  # noqa: E402

REL_TOL = 2e-4

MODEL = {  # the reference reads these keys of a configuration file
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "num_experts_per_tok": 2, "vocab_size": 256,
}


def _cfg(**overrides):
    base = dict(
        mlp_hidden=32, max_seq_len=128, qk_norm=True, moe_experts=4, moe_top_k=2,
        moe_renormalize=False,
    )
    base.update(overrides)
    return L.LlamaConfig.tiny(**base)


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
    """One toy OLMoE runner with its weights, warmed."""
    cfg = _cfg()
    params = _params(cfg)
    runner = PagedModelRunner(
        cfg, params, num_blocks=64, block_size=8, prefill_buckets=(16, 32),
        decode_buckets=(4,), verify_buckets=(4,),
    )
    runner.warmup()
    return cfg, params, runner


def _row(runner, first, n_tokens):
    row = np.zeros(runner.max_blocks_per_seq, np.int32)
    need = -(-n_tokens // runner.block_size)
    row[:need] = np.arange(first, first + need)
    return row


# -- forward ------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_forward_matches_the_plain_reference(seed):
    cfg = _cfg()
    params = _params(cfg, seed)
    tokens = _tokens(seed, (2, 24))
    have = L.forward(cfg, params, jnp.asarray(tokens))
    picks = [(b, t) for b in range(2) for t in range(24)]
    want = reference.logits_at(MODEL, params, tokens, picks).reshape(2, 24, -1)
    assert _rel(have, want) < REL_TOL


@pytest.mark.parametrize("variant", controls.VARIANTS, ids=lambda v: v or "the_model_itself")
def test_each_control_of_the_reference_is_another_model(variant):
    """What the chip's check must read as NOT correct is far from the model
    at float32 too: the limit is not what tells them apart here. The
    controls are a twin of the reference kept by the tests; without a
    variant the twin IS the reference."""
    cfg = _cfg()
    params = _params(cfg)
    tokens = _tokens(3, (2, 24))
    picks = [(b, t) for b in range(2) for t in range(24)]
    twin = controls.logits_at(MODEL, params, tokens, picks, variant=variant)
    if variant is None:
        assert np.array_equal(twin, reference.logits_at(MODEL, params, tokens, picks))
        return
    have = L.forward(cfg, params, jnp.asarray(tokens)).reshape(48, -1)
    assert _rel(have, twin) > 1e-2  # 50 times the tolerance


@pytest.mark.parametrize("variant", controls.VARIANTS[1:-1])
def test_the_expert_ffn_alone_tells_each_control_of_the_experts(variant):
    """The second reading of the chip's check (``perfbench/families/olmoe/
    server.py``): the program's FFN of a block and the reference's on the
    same activations, so that no expert can flip. At float32 the model
    reads under 1e-5 and every control that touches the FFN 1e-2 or more."""
    cfg = _cfg()
    p = _params(cfg)["layers"][0]
    h = jnp.asarray(np.random.default_rng(5).standard_normal((1, 24, cfg.dim)), jnp.float32)
    have = L._ffn(cfg, p, h[0])[0]
    want, margin = reference.expert_ffn(p, h, top_k=cfg.moe_top_k)
    assert float(margin.min()) > 1e-4 and _rel(have, want[0]) < 1e-5
    wrong, _ = controls.expert_ffn(p, h, top_k=cfg.moe_top_k, variant=variant)
    assert _rel(have, wrong[0]) > 1e-2


def test_qk_norm_on_a_dense_config():
    """QK-norm is a property of the attention alone: a dense MLP with it
    trains a norm's worth of new weights and moves the logits."""
    cfg = L.LlamaConfig.tiny(qk_norm=True)
    plain = L.LlamaConfig.tiny()
    params = _params(cfg)
    assert params["layers"][0]["q_norm"].shape == (cfg.n_heads * cfg.head_dim,)
    assert params["layers"][0]["k_norm"].shape == (cfg.n_kv_heads * cfg.head_dim,)
    assert L.param_count(cfg) == L.param_count(plain) + cfg.n_layers * (
        cfg.n_heads + cfg.n_kv_heads) * cfg.head_dim
    assert set(L.logical_axes(cfg)["layers"][0]) == set(params["layers"][0])
    tokens = jnp.asarray(_tokens(5, (2, 16)))
    with_norm = L.forward(cfg, params, tokens)
    without = L.forward(plain, params, tokens)  # the same weights, the two norms not applied
    assert _rel(with_norm, without) > 1e-2
    # by hand, one layer's q: the norm runs over all heads together
    p = params["layers"][0]
    h = rms_norm(params["embed"][tokens], p["attn_norm"], cfg.norm_eps)
    q, k, _ = L._qkv(cfg, p, h)
    flat = jnp.einsum("bsd,dhk->bshk", h, p["wq"]).reshape(2, 16, -1)
    want = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True) + cfg.norm_eps) * p["q_norm"]
    np.testing.assert_allclose(np.asarray(q).reshape(2, 16, -1), np.asarray(want), atol=1e-5)
    # and the paged steps take the same path: prefill's logits are forward's
    runner = PagedModelRunner(cfg, params, num_blocks=16, block_size=8, prefill_buckets=(16,),
                              decode_buckets=(2,))
    logits = runner.prefill_chunk(np.asarray(tokens[0]), _row(runner, 1, 16), 0)
    assert _rel(logits, with_norm[0, -1]) < REL_TOL
    assert runner.moe is None  # a dense model keeps no expert account


# -- the paged steps ------------------------------------------------------------

def test_chunked_prefill_then_decode_matches_the_reference_full_pass(served):
    cfg, params, runner = served
    prompt_lens, steps = [40, 12], 2
    totals = [n + steps for n in prompt_lens]
    tokens = _tokens(7, (2, max(totals)))
    rows = [_row(runner, 1, totals[0]), _row(runner, 10, totals[1])]
    got = []
    for i, n in enumerate(prompt_lens):
        start = 0
        while start < n:  # chunks of the largest bucket: 32 then 8
            c = min(32, n - start)
            logits = runner.prefill_chunk(tokens[i, start : start + c], rows[i], start)
            start += c
        got.append((i, n - 1, logits))
    for d in range(steps):
        poss = [n + d for n in prompt_lens]
        logits = runner.decode(
            [int(tokens[i, p]) for i, p in enumerate(poss)], poss, rows, [p + 1 for p in poss]
        )
        got += [(i, p, logits[i]) for i, p in enumerate(poss)]
    want = reference.logits_at(MODEL, params, tokens, [(i, p) for i, p, _ in got])
    for (_, _, have), ref in zip(got, want):
        assert _rel(have, ref) < REL_TOL
    assert runner.recompiles_after_warmup() == 0


def test_verify_step_matches_decode_repeated(served):
    cfg, params, runner = served
    tokens = _tokens(11, (2, 20))
    rows = [_row(runner, 20, 20), _row(runner, 30, 20)]
    for i in range(2):
        runner.prefill_chunk(tokens[i, :16], rows[i], 0)
    # windows of 3 and 2 positions in one verify launch ...
    windows = [list(tokens[0, 16:19]), list(tokens[1, 16:18])]
    verified = runner.verify_batch(windows, rows, [16, 16])
    # ... against the same positions one decode step at a time
    for i, window in enumerate(windows):
        for j, tok in enumerate(window):
            one = runner.decode([int(tok)], [16 + j], [rows[i]], [17 + j])
            assert _rel(verified[i][j], one[0]) < REL_TOL


def test_loads_come_back_with_the_logits_and_are_accounted(served):
    cfg, params, runner = served
    before = {k: dict(v) for k, v in runner.moe.items()}
    tokens = _tokens(13, (1, 24))[0]
    row = _row(runner, 40, 24)
    runner.prefill_chunk(tokens[:20], row, 0)  # 20 real rows in a bucket of 32
    runner.decode([int(tokens[20])], [20], [row], [21])  # 1 real slot of 4
    pre = {k: runner.moe["prefill"][k] - before["prefill"][k] for k in before["prefill"]}
    dec = {k: runner.moe["decode"][k] - before["decode"][k] for k in before["decode"]}
    layers, E, k = cfg.n_layers, cfg.moe_experts, cfg.moe_top_k
    assert (pre["launches"], pre["assignments"], pre["expert_slots"]) == (1, 20 * k * layers, layers * E)
    assert (dec["launches"], dec["assignments"], dec["expert_slots"]) == (1, 1 * k * layers, layers * E)
    assert dec["experts_touched"] == k * layers and dec["max_load"] == layers
    assert pre["mean_load"] == pytest.approx(layers * 20 * k / E)
    assert pre["mean_load"] <= pre["max_load"] <= 20 * layers
    # the step itself: a third output, [layers, E] int32, counting real rows only
    padded = np.zeros(32, np.int32)
    padded[:20] = tokens[:20]
    runner.cache, _, loads = runner._prefill_jit(
        params, runner.cache, padded, row, np.int32(0), np.int32(20)
    )
    assert loads.shape == (layers, E) and loads.dtype == jnp.int32
    assert np.asarray(loads).sum(axis=1).tolist() == [20 * k] * layers


def test_paged_steps_of_a_dense_config_have_two_outputs():
    cfg = L.LlamaConfig.tiny()
    params = L.init_params(cfg, jax.random.PRNGKey(0))
    cache = L.init_paged_kv_cache(cfg, 8, 8)
    z = np.zeros
    assert len(L.paged_prefill_step(cfg, params, cache, z(8, np.int32), z(8, np.int32),
                                    np.int32(0), np.int32(4))) == 2
    assert len(L.paged_decode_step(cfg, params, cache, z(2, np.int32), z(2, np.int32),
                                   z((2, 8), np.int32), np.ones(2, np.int32))) == 2
    assert len(L.paged_verify_step(cfg, params, cache, z((2, 4), np.int32), z((2, 8), np.int32),
                                   z(2, np.int32), z(2, np.int32))) == 2


def test_what_shares_a_decode_batch_cannot_change_a_slot(served):
    """Dropless: a request's logits do not depend on who shares its batch."""
    cfg, params, runner = served
    tokens = _tokens(17, (4, 12))
    rows = [_row(runner, 45 + 2 * i, 12) for i in range(4)]
    for i in range(4):
        runner.prefill_chunk(tokens[i, :11], rows[i], 0)
    alone = runner.decode([int(tokens[0, 11])], [11], [rows[0]], [12])
    crowd = runner.decode([int(t) for t in tokens[:, 11]], [11] * 4, rows, [12] * 4)
    assert _rel(crowd[0], alone[0]) < 1e-6


# -- the dropless FFN on hand-made routings ----------------------------------------

def _loop_ffn(params, x, gates, experts):
    """Per-expert loop with a mask: float64, the routing handed in."""
    x = np.asarray(x, np.float64)
    out = np.zeros_like(x)
    for e in range(params["router"].shape[1]):
        wg, wu, wd = (np.asarray(params[n][e], np.float64) for n in ("w_gate", "w_up", "w_down"))
        g = np.where(np.asarray(experts) == e, np.asarray(gates, np.float64), 0.0).sum(-1)
        h = x @ wg
        out += g[:, None] * (((h / (1 + np.exp(-h))) * (x @ wu)) @ wd)
    return out


def _forced(E, d, scores):
    """A router under which token t's softmax is ``softmax(scores[t])``:
    the tokens are one-hot rows, so x @ router picks a row of it."""
    T = len(scores)
    assert T <= d
    router = np.zeros((d, E), np.float32)
    router[:T] = scores
    return jnp.asarray(router), jnp.eye(T, d, dtype=jnp.float32)


@pytest.mark.parametrize("case", ["every_token_to_one_expert", "an_expert_with_no_token",
                                  "more_padding_than_real_rows", "random_routing"])
def test_dropless_ffn_against_the_per_expert_loop(case):
    E, d, hidden, k = 4, 16, 32, 2
    params = init_moe_params(jax.random.PRNGKey(0), d, hidden, E)
    rng = np.random.default_rng(1)
    T, valid = 12, None
    if case == "every_token_to_one_expert":
        k = 1
        params["router"], x = _forced(E, d, np.tile([0.0, 9.0, 0.0, 0.0], (T, 1)))
        want_load = [0, T, 0, 0]
    elif case == "an_expert_with_no_token":
        scores = rng.normal(size=(T, E))
        scores[:, 2] = -30.0  # never among the top 2 of 4
        params["router"], x = _forced(E, d, scores)
        want_load = None
    elif case == "more_padding_than_real_rows":
        x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
        valid = jnp.asarray([True, False, False, True, False, False, False, True,
                             False, False, False, False])
        want_load = None
    else:
        x = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
        want_load = None
    out, aux = jax.jit(
        lambda p, x, v: dropless_moe_ffn(p, x, top_k=k, renormalize=False, valid=v)
    )(params, x, valid)
    gates, experts, _ = route(params["router"], x, top_k=k, renormalize=False)
    keep = np.ones(T, bool) if valid is None else np.asarray(valid)
    want = _loop_ffn(params, x, gates, experts) * keep[:, None]
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5, rtol=2e-5)
    # the loads are a bincount of the valid rows' choices
    counted = np.bincount(np.asarray(experts)[keep].reshape(-1), minlength=E)
    assert np.asarray(aux["load"]).tolist() == counted.tolist()
    assert int(aux["load"].sum()) == int(keep.sum()) * k
    if want_load is not None:
        assert counted.tolist() == want_load
    if case == "an_expert_with_no_token":
        assert counted[2] == 0 and (counted[[0, 1, 3]] > 0).all()


def test_a_real_row_does_not_change_when_the_padding_rows_do():
    E, d, hidden, k, T = 4, 16, 32, 2, 10
    params = init_moe_params(jax.random.PRNGKey(2), d, hidden, E)
    rng = np.random.default_rng(3)
    valid = jnp.asarray([True] * 3 + [False] * 7)
    x1 = rng.normal(size=(T, d)).astype(np.float32)
    x2 = x1.copy()
    x2[3:] = 50.0 * rng.normal(size=(T - 3, d))  # other routing, other magnitudes
    fn = jax.jit(lambda x: dropless_moe_ffn(params, x, top_k=k, renormalize=False, valid=valid))
    (o1, a1), (o2, a2) = fn(jnp.asarray(x1)), fn(jnp.asarray(x2))
    assert np.array_equal(np.asarray(o1[:3]), np.asarray(o2[:3]))  # bit for bit
    assert np.array_equal(np.asarray(a1["load"]), np.asarray(a2["load"]))
    assert not np.asarray(o1[3:]).any() and not np.asarray(o2[3:]).any()


@pytest.mark.parametrize("renormalize", [False, True])
def test_one_routing_for_both_expert_paths(renormalize):
    """``moe_ffn`` (expert-parallel, capacity) with room for everything and
    the dropless path compute the same FFN from the same ``route``."""
    params = init_moe_params(jax.random.PRNGKey(4), 16, 32, 4)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 8, 16), jnp.float32)
    capped, aux = moe_ffn(params, x, top_k=2, renormalize=renormalize, capacity_factor=8.0)
    assert float(aux["dropped_fraction"]) == 0.0
    free, aux2 = dropless_moe_ffn(params, x.reshape(16, 16), top_k=2, renormalize=renormalize)
    np.testing.assert_allclose(np.asarray(capped).reshape(16, 16), np.asarray(free), atol=2e-5)
    assert float(aux["aux_loss"]) == pytest.approx(float(aux2["aux_loss"]), rel=1e-6)
    gates, _, probs = route(params["router"], x.reshape(16, 16), top_k=2, renormalize=renormalize)
    sums = np.asarray(gates.sum(-1))
    assert np.allclose(sums, 1.0) if renormalize else (sums < 1.0 - 1e-3).all()
    assert gates.dtype == jnp.float32 and probs.dtype == jnp.float32


def test_forward_without_an_expert_axis_drops_nothing_and_trains():
    """``forward`` on one device runs the dropless path: a capacity factor
    that would drop most assignments changes nothing, and gradients reach
    the router and every expert that received a token."""
    cfg = _cfg()
    tight = _cfg(moe_capacity_factor=0.01)
    params = _params(cfg)
    tokens = jnp.asarray(_tokens(19, (2, 16)))
    assert np.array_equal(np.asarray(L.forward(cfg, params, tokens)),
                          np.asarray(L.forward(tight, params, tokens)))
    grads = jax.grad(lambda p: L.next_token_loss(cfg, p, tokens, tokens))(params)
    g0 = grads["layers"][0]
    assert float(jnp.abs(g0["router"]).max()) > 0 and float(jnp.abs(g0["q_norm"]).max()) > 0
    assert all(float(jnp.abs(g0["w_down"][e]).max()) > 0 for e in range(cfg.moe_experts))


# -- the chip's branch of the grouped matmul, compiled for a described v5e -------------

@pytest.fixture(scope="module")
def one_chip():
    """A described (not attached) v5e chip: the TPU compiler is installed
    here. Made inside a fixture, never at import (only one process may hold
    libtpu; under xdist every worker imports this file)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows, tile", [(32 * 8, 128), (1024 * 8, 256), (8 * 8, 128)],
                         ids=["decode_4_rows_an_expert", "prefill_128_rows_an_expert",
                              "a_bucket_under_a_tile_is_padded_to_one"])
def test_the_grouped_matmul_compiles_for_the_chip_at_olmoe_widths(one_chip, monkeypatch, rows, tile):
    """On a TPU ``grouped_matmul`` is the Pallas kernel ``megablox.gmm``; the
    CPU tests above never reach that branch. Compile it at the published
    widths for the real chip (nothing runs): Mosaic refuses here what it
    would refuse there (tiling, VMEM)."""
    from ray_tpu.ops import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # steer the branch; the test's business
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)  # such a compile cannot be read back without a chip
    try:
        shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
        for k, n in ((2048, 1024), (1024, 2048)):  # gate/up, then down
            compiled = jax.jit(moe.grouped_matmul).lower(
                shape((rows, k), jnp.bfloat16), shape((64, k, n), jnp.bfloat16), shape((64,), jnp.int32)
            ).compile()
            text = compiled.as_text()
            assert "tpu_custom_call" in text and "gmm" in text and "ragged-dot" not in text
            assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20
            assert compiled.out_info.shape == (rows, n)
        assert rows % tile == 0 or rows < tile
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.mark.parametrize("L, g, d, f, rows", [(5, 16, 7168, 2048, 128 * 8), (5, 16, 7168, 2048, 1024 * 8),
                                              (1, 16, 7168, 2048, 128 * 8), (38, 8, 3584, 1024, 32 * 4),
                                              (38, 8, 3584, 1024, 1024 * 4)],
                         ids=["gigachat_step", "gigachat_chunk", "gigachat_mtp_module_a_stack_of_one",
                              "xing4_decode", "xing4_chunk"])
def test_the_grouped_matmul_reads_a_layer_in_the_stack_at_the_latent_cells_widths(one_chip, monkeypatch, L, g, d, f, rows):
    """A scanned model's expert stack ``[L, g, k, n]`` with a TRACED layer
    (``ops/moe.py::grouped_matmul``, PR 43), compiled for the real chip at
    both latent cells' widths: the kernel is still ``gmm``, the stack goes in
    as it lies (a bitcast to ``[L g, k, n]``), and nothing the size of a
    layer's matrices (0.47 GB on GigaChat3.1, 59 MB on Xing4) is among the
    temporaries, where the parent's scan copied each slice out."""
    from ray_tpu.ops import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        shape = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
        for k, n in ((d, f), (f, d)):  # gate/up, then down
            compiled = jax.jit(moe.grouped_matmul).lower(
                shape((rows, k)), shape((L, g, k, n)), shape((g,), jnp.int32), shape((), jnp.int32)
            ).compile()
            text = compiled.as_text()
            assert "tpu_custom_call" in text and "gmm" in text and "ragged-dot" not in text
            assert f"bf16[{L * g},{k},{n}]" in text and "bitcast" in text
            layer_bytes = g * k * n * 2
            assert compiled.memory_analysis().temp_size_in_bytes < min(layer_bytes // 4, 64 * 2**20)
            assert compiled.out_info.shape == (rows, n)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.mark.parametrize(
    "layers, blocks, n_kv, heads, window",
    [(16, 6144, 8, 32, 1), (16, 6144, 8, 32, 8), (12, 2240, 16, 16, 1), (12, 2240, 16, 16, 4)],
    ids=["mistral_decode", "mistral_verify_8", "olmoe_decode", "olmoe_verify_4"],
)
def test_the_paged_attention_kernel_compiles_for_the_chip_at_the_benchmark_widths(
    one_chip, layers, blocks, n_kv, heads, window
):
    """The other kernel of the serving path (``ops/paged_attention.py``; here
    because a second file of such compiles would go to another xdist worker
    and skip): both configurations' whole caches as the benchmark sizes them,
    32 slots, the full-width table. The cache goes in as it lies (no copy of
    it among the temporaries) and there is one Mosaic call."""
    from ray_tpu.ops import paged_attention as PA

    cache_like = jax.ShapeDtypeStruct((layers, blocks, 16, n_kv, 128), jnp.bfloat16)
    assert PA.kernel_serves(window, heads, cache_like, backend="tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
        cache = shape((layers, blocks, 16, n_kv, 128), jnp.bfloat16)
        compiled = jax.jit(
            lambda q, k, v, tables, pos: PA.paged_attention(q, k, v, layers - 1, tables, pos, interpret=False)
        ).lower(
            shape((32, window, heads, 128), jnp.bfloat16), cache, cache,
            shape((32, 256), jnp.int32), shape((32, window), jnp.int32),
        ).compile()
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1 and "paged_attn" in text
        assert compiled.memory_analysis().temp_size_in_bytes < 2**20
        assert compiled.out_info.shape == (32, window, heads, 128)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.mark.parametrize("window", [1024, 256], ids=["chunk_1024", "chunk_256"])
def test_the_latent_flash_kernel_compiles_for_the_chip_at_xing4_widths(one_chip, window):
    """The kernel of Xing4's prefill chunk (``ops/latent_flash.py``; here for
    the same reason as the one above): 32 heads, keys 128 + 64 shared, values
    128, a table of 8192 positions, at the tiles the module fixes. One Mosaic
    call; the scores are nobody's temporary."""
    from ray_tpu.ops import latent_flash as LF

    H, S, dk, ds, dv = 32, 8192, 128, 64, 128
    assert LF.kernel_serves(window, S, dk, dv, ds, jnp.bfloat16, backend="tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        shape = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
        compiled = jax.jit(
            lambda q, k, v, qs, ks, ctx, n: LF.flash_attention(
                q, k, v, ctx, n, scale=0.1, q_shared=qs, k_shared=ks, interpret=False
            )
        ).lower(
            shape((H, window, dk)), shape((H, S, dk)), shape((H, S, dv)), shape((H, window, ds)),
            shape((S, ds)), shape((), jnp.int32), shape((), jnp.int32),
        ).compile()
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1 and "latent_flash" in text
        assert compiled.memory_analysis().temp_size_in_bytes < 2**20
        assert compiled.out_info.shape == (H, window, dv)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.mark.parametrize("window", [1, 1024, 256], ids=["decode_128_slots", "chunk_1024", "chunk_256"])
def test_both_attention_kernels_compile_for_the_chip_at_heads_of_64(one_chip, window):
    """LFM2-8B-A1B's attention (32 query heads of 64 over 8 KV heads, a cache
    of ``[16, 512]`` blocks: a token's heads in one row) through the two
    kernels that were there, at the benchmark's sizes: the decode kernel with
    the heads in LANES (128 slots, the full-width table, the whole cache as it
    lies), the chunk's flash kernel with the heads in PAIRS (a table of 8192).
    One Mosaic call each; neither the cache nor the scores is a temporary."""
    from ray_tpu.ops import latent_flash as LF
    from ray_tpu.ops import paged_attention as PA

    L, N, B, H, KV, hd, S = 6, 30160, 128, 32, 8, 64, 8192
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        shape = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
        if window == 1:
            cache = shape((L, N, 16, KV * hd))
            assert PA.kernel_serves(1, H, cache, backend="tpu", n_kv=KV, head_dim=hd)
            compiled = jax.jit(
                lambda q, k, v, tables, pos: PA.paged_attention(q, k, v, L - 1, tables, pos, interpret=False, n_kv=KV)
            ).lower(shape((B, 1, H, hd)), cache, cache, shape((B, S // 16), jnp.int32), shape((B, 1), jnp.int32)).compile()
            name, out = "paged_attn", (B, 1, H, hd)
            temporaries = 4 * B * H * KV * hd * 2  # the queries and the outputs laid out in lanes
        else:
            assert LF.kernel_serves(window, S, hd, hd, 0, jnp.bfloat16, backend="tpu", kv_heads=KV)
            compiled = jax.jit(
                lambda q, k, v, ctx, n: LF.flash_attention(q, k, v, ctx, n, scale=hd ** -0.5, group=H // KV, interpret=False)
            ).lower(shape((H, window, hd)), shape((KV, S, hd)), shape((KV, S, hd)), shape((), jnp.int32),
                    shape((), jnp.int32)).compile()
            name, out = "latent_flash", (H, window, hd)
            temporaries = 2 * KV * S * hd * 2 + 4 * H * window * 2 * hd * 2  # K and V in pairs; q and o in halves
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1 and name in text
        assert compiled.memory_analysis().temp_size_in_bytes <= temporaries + 2**20
        assert compiled.out_info.shape == out
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.mark.parametrize("kernel", ["paged_attn", "latent_flash_1024", "latent_flash_256", "ssm_scan_1024",
                                    "ssm_scan_256", "ssm_update"])
def test_the_kernels_of_the_jamba_cell_compile_for_the_chip_at_its_widths(one_chip, kernel):
    """AI21-Jamba2-3B's kernels at the benchmark's sizes (here for the same
    reason as the ones above): the two attention kernels at ONE KV head under
    20 query heads (a cache of flat ``[16 x 1, 128]`` blocks, 256 slots, the
    full-width table of 512 blocks; 20 query heads a key head over a table of
    8192) and ``ops/selective_scan.py``'s two over the pool of 257 slots of
    ``[16, 40, 128]`` float32: one Mosaic call each, the cache and the pool go
    in as they lie (the pool aliased to its output), and no ``[chunk, 16,
    5120]`` array is among the temporaries."""
    from ray_tpu.ops import latent_flash as LF
    from ray_tpu.ops import paged_attention as PA
    from ray_tpu.ops import selective_scan as SS

    B, H, hd, S, N, D = 256, 20, 128, 8192, 16, 5120
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        shape = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
        f32 = lambda s: shape(s, jnp.float32)  # noqa: E731
        pool = f32((26, B + 1, *SS.state_shape(N, D)))
        scalar = shape((), jnp.int32)
        if kernel == "paged_attn":
            cache = shape((2, 131073, 16, hd))
            assert PA.kernel_serves(1, H, cache, backend="tpu", n_kv=1)
            compiled = jax.jit(
                lambda q, k, v, tables, pos: PA.paged_attention(q, k, v, 1, tables, pos, interpret=False, n_kv=1)
            ).lower(shape((B, 1, H, hd)), cache, cache, shape((B, S // 16), jnp.int32), shape((B, 1), jnp.int32)).compile()
            name, out, temporaries = "paged_attn", (B, 1, H, hd), 0
        elif kernel.startswith("latent_flash"):
            C = int(kernel.rsplit("_", 1)[1])
            assert LF.kernel_serves(C, S, hd, hd, 0, jnp.bfloat16, backend="tpu")
            compiled = jax.jit(
                lambda q, k, v, ctx, n: LF.flash_attention(q, k, v, ctx, n, scale=hd ** -0.5, group=H, interpret=False)
            ).lower(shape((H, C, hd)), shape((1, S, hd)), shape((1, S, hd)), scalar, scalar).compile()
            name, out, temporaries = "latent_flash", (H, C, hd), 0
        elif kernel.startswith("ssm_scan"):
            C = int(kernel.rsplit("_", 1)[1])
            assert SS.kernel_serves(pool, "tpu")
            compiled = jax.jit(
                lambda pool, layer, slot, *a: SS.chunk(pool, layer, slot, False, *a, kernel=True, interpret=False),
                donate_argnums=0,
            ).lower(pool, scalar, scalar, f32((C, D)), f32((C, D)), f32((C, N)), f32((C, N)), f32((N, D))).compile()
            name, out, temporaries = "ssm_scan", (C, D), 3 * C * D * 4  # dt, x and y laid out a register a state index
        else:
            compiled = jax.jit(
                lambda pool, layer, slots, *a: SS.step(pool, layer, slots, jnp.zeros((B,), bool), *a, kernel=True,
                                                       interpret=False),
                donate_argnums=0,
            ).lower(pool, scalar, shape((B,), jnp.int32), f32((B, D)), f32((B, D)), f32((B, N)), f32((B, N)),
                    f32((N, D))).compile()
            name, out, temporaries = "ssm_update", (B, D), 3 * B * D * 4
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1 and name in text
        memory = compiled.memory_analysis()
        assert memory.temp_size_in_bytes <= temporaries + 2**20 < 1024 * N * D * 4
        if name.startswith("ssm"):
            assert memory.alias_size_in_bytes >= 26 * (B + 1) * N * D * 4  # the pool is updated where it lies
            assert compiled.out_info[0].shape == out
        else:
            assert compiled.out_info.shape == out
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.mark.parametrize("window", [1024, 256], ids=["chunk_1024", "chunk_256"])
def test_the_latent_flash_kernel_takes_a_192_wide_value_at_gigachat_widths(one_chip, window):
    """GigaChat3.1's prefill chunk through the same kernel: 64 heads, keys
    128 + 64 shared, VALUES 192 (one and a half lane tiles: a value tile as
    wide as its array), a table of 8192. One Mosaic call; what XLA adds
    around it here is V re-laid onto 256 lanes (in the model's program the
    expansion writes it so), never the scores."""
    from ray_tpu.ops import latent_flash as LF

    H, S, dk, ds, dv = 64, 8192, 128, 64, 192
    assert LF.kernel_serves(window, S, dk, dv, ds, jnp.bfloat16, backend="tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        shape = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
        compiled = jax.jit(
            lambda q, k, v, qs, ks, ctx, n: LF.flash_attention(
                q, k, v, ctx, n, scale=0.1, q_shared=qs, k_shared=ks, interpret=False
            )
        ).lower(
            shape((H, window, dk)), shape((H, S, dk)), shape((H, S, dv)), shape((H, window, ds)),
            shape((S, ds)), shape((), jnp.int32), shape((), jnp.int32),
        ).compile()
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1 and "latent_flash" in text
        assert compiled.memory_analysis().temp_size_in_bytes <= H * S * 256 * 2 + 2**20
        assert compiled.out_info.shape == (H, window, dv)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.mark.parametrize(
    "layers, blocks, slots, window, heads",
    [(7, 12664, 64, 1, 32), (40, 2824, 32, 1, 32), (40, 2824, 32, 4, 32), (40, 2824, 32, 8, 32),
     (7, 16168, 64, 2, 64)],
    ids=["kimi_linear_decode", "xing4_decode", "xing4_verify_4", "xing4_verify_8", "gigachat_mtp_window_2"],
)
def test_the_latent_rows_kernel_compiles_for_the_chip_at_the_benchmark_widths(one_chip, layers, blocks, slots, window, heads):
    """The kernel of both latent models' decode (``ops/latent_paged.py``;
    here for the same reason as the ones above): 32 heads (GigaChat3.1: 64, a window of two), rows of 512 + 64,
    both configurations' whole caches as the layout stores them (a block of
    16 as ``[8, 1152]``), the full-width table. The cache goes in as it lies
    and there is one Mosaic call (Mosaic refused a DMA of one row of ``[blocks,
    9216]``, the form until PR 36: no test can compile that)."""
    from ray_tpu.models.interface import CacheLayout
    from ray_tpu.ops import latent_paged as LP

    layout = CacheLayout("latent", layers, 16, (("latent", (576,)),), jnp.bfloat16, flat_blocks=True)
    cache_like = jax.eval_shape(lambda: layout.init(blocks))["latent"]
    assert cache_like.shape == (layers, blocks, 8, 1152)
    assert LP.kernel_serves(window, heads, 576, 512, cache_like, backend="tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
        compiled = jax.jit(
            lambda q, cache, tables, ctx: LP.attend_paged(
                q, cache, layers - 1, tables, ctx, kv_lora_rank=512, scale=0.07, interpret=False
            )
        ).lower(
            shape((slots, window, heads, 576), jnp.bfloat16), shape(cache_like.shape, jnp.bfloat16),
            shape((slots, 512), jnp.int32), shape((slots,), jnp.int32),
        ).compile()
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1 and "latent_rows" in text
        # the queries laid out twice are the one temporary: slots x 2 x window x heads x 1152 bf16
        assert compiled.memory_analysis().temp_size_in_bytes < 2 * slots * 2 * window * heads * 1152 * 2 + 2**20
        assert [o.shape for o in compiled.out_info] == [(slots, window, heads, 512)] + [(slots, window, heads)] * 2
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.mark.parametrize("kernel", ["index_rows", "latent_rows_selected"])
def test_a_selecting_windows_paged_kernels_compile_for_the_chip_at_glm5s_widths(one_chip, kernel):
    """GLM-5's verify window (``dsa-longctx-batch``: 8 slots x 2 under a table
    of 32,768, both arrays of the cell's pool of 12,288 blocks as the layout
    stores them, 7 rows a token): the indexer's scores from the slots' live
    blocks (``ops/index_paged.py``: 32 heads of 128, a block ``[16, 128]``) and
    ``ops/latent_paged.py`` with the selection as ONE more operand (64 heads,
    256 laid-out query rows against ``[8, 1152]`` blocks). Here for the same
    reason as the ones above: Mosaic refuses shapes the interpreter takes."""
    from ray_tpu.models import glm_dsa, latent
    from ray_tpu.ops import index_paged as IP, latent_paged as LP

    cfg = glm_dsa.GlmDsaConfig(dtype=jnp.bfloat16, max_seq_len=32768)
    cache_like = jax.eval_shape(lambda: latent.cache_layout(cfg, 16, n_layers=7).init(12289))
    assert latent.sparse_paged_serves(cfg, 2, cache_like, backend="tpu")
    B, C, M = 8, 2, 2048
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
        tables, ctx = shape((B, M), jnp.int32), shape((B,), jnp.int32)
        if kernel == "index_rows":
            compiled = jax.jit(
                lambda q, w, own, cache, tables, ctx: IP.index_scores(q, w, own, cache, 6, tables, ctx, interpret=False)
            ).lower(
                shape((B, C, 32, 128), jnp.bfloat16), shape((B, C, 32), jnp.float32), shape((B, C, C), jnp.float32),
                shape(cache_like["index"].shape, jnp.bfloat16), tables, ctx,
            ).compile()
            assert (compiled.out_info.shape, compiled.out_info.dtype) == ((B, C, M * 16), jnp.float32)
            room = 2 * B * C * M * 16 * 4  # the scores a wave at a time, and as the caller takes them
        else:
            compiled = jax.jit(
                lambda q, cache, tables, ctx, chosen: LP.attend_paged(
                    q, cache, 6, tables, ctx, kv_lora_rank=512, scale=0.07, chosen=chosen, interpret=False
                )
            ).lower(
                shape((B, C, 64, 576), jnp.bfloat16), shape(cache_like["latent"].shape, jnp.bfloat16), tables, ctx,
                shape((B, C, M * 16), jnp.bool_),
            ).compile()
            assert [o.shape for o in compiled.out_info] == [(B, C, 64, 512)] + [(B, C, 64)] * 2
            room = 2 * B * 2 * C * 64 * 1152 * 2 + 2 * B * C * M * 16 * 4  # the queries laid out twice, the mask as int32
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1 and kernel in text
        assert compiled.memory_analysis().temp_size_in_bytes < room + 2**20
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.mark.parametrize("group, blocks, keeps", [("full", 17408, 0), ("window", 4480, 1024)],
                         ids=["mellum2_full_layers", "mellum2_window_layers"])
def test_the_paged_attention_kernel_compiles_over_a_flat_cache_of_four_kv_heads(one_chip, group, blocks, keeps):
    """Mellum2's decode (``ops/paged_attention.py``; here for the same reason
    as the ones above): 4 KV heads of 128, eight query heads each, a block
    stored flat as ``[64, 128]`` (as ``[16, 4, 128]`` the device pads every
    token's heads to a tile), 64 slots, the 16 k table; a window layer is told
    each slot's first live block. Both groups' whole pools as the
    configuration sizes them go in as they lie: one Mosaic call, no copy."""
    from ray_tpu.ops import paged_attention as PA

    layers = {"full": 7, "window": 21}[group]
    cfg = dataclasses.replace(L.LlamaConfig.tiny(), n_heads=32, n_kv_heads=4, attn_head_dim=128, dtype=jnp.bfloat16)
    layout = L.cache_layout(cfg, 16)
    assert layout.flat_blocks and layout.block_shape((4, 128)) == (64, 128)
    cache_like = jax.ShapeDtypeStruct((layers, blocks, 64, 128), jnp.bfloat16)
    assert PA.kernel_serves(1, 32, cache_like, backend="tpu", n_kv=4)
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
        cache = shape(cache_like.shape, jnp.bfloat16)
        compiled = jax.jit(
            lambda q, k, v, tables, pos: PA.paged_attention(
                q, k, v, layers - 1, tables, pos, interpret=False, n_kv=4, keeps=keeps
            )
        ).lower(
            shape((64, 1, 32, 128), jnp.bfloat16), cache, cache, shape((64, 1024), jnp.int32), shape((64, 1), jnp.int32),
        ).compile()
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1 and "paged_attn" in text
        assert compiled.memory_analysis().temp_size_in_bytes < 2**20
        assert compiled.out_info.shape == (64, 1, 32, 128)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.mark.parametrize("chunk, keys, window", [(1024, 16384, 0), (256, 16384, 0), (1024, 3072, 1024), (256, 2048, 1024)],
                         ids=["full_chunk_1024", "full_chunk_256", "window_chunk_1024", "window_chunk_256"])
def test_the_flash_kernel_compiles_with_grouped_heads_and_a_window_at_mellum2_widths(one_chip, chunk, keys, window):
    """Mellum2's prefill chunk through ``ops/latent_flash.py``: 32 query heads
    over 4 key heads (the index map, nothing repeated), keys and values 128
    wide, no shared part; a full layer over the 16 k table, a window layer over
    the window, the chunk and a block's slack in whole key tiles
    (``paged_kv.chunk_keys``). One Mosaic call; the scores are nobody's temporary."""
    from ray_tpu.ops import latent_flash as LF

    cfg = dataclasses.replace(L.LlamaConfig.tiny(), n_heads=32, n_kv_heads=4, attn_head_dim=128, layer_windows=(1024, 0))
    assert paged_kv.chunk_keys(window, chunk, 16384, 16) == keys
    assert LF.kernel_serves(chunk, keys, 128, 128, 0, jnp.bfloat16, backend="tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        shape = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
        compiled = jax.jit(
            lambda q, k, v, ctx, n: LF.flash_attention(
                q, k, v, ctx, n, scale=0.1, interpret=False, group=8, window=window or None
            )
        ).lower(
            shape((32, chunk, 128)), shape((4, keys, 128)), shape((4, keys, 128)), shape((), jnp.int32), shape((), jnp.int32),
        ).compile()
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1 and "latent_flash" in text
        assert compiled.memory_analysis().temp_size_in_bytes < 2**20
        assert compiled.out_info.shape == (32, chunk, 128)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


def test_the_kda_update_kernel_compiles_for_the_chip_in_place_in_kimi_linears_pool(one_chip):
    """A decode step's update of one KDA layer's slab (``ops/kda.py``; here
    for the same reason as the ones above) over ``kda-reason-offline``'s whole
    state pool, 20 layers x 65 slots x 32 heads x 128 x 128 float32: one
    Mosaic call, the pool aliased in and out (2.7 GB: a copy would not fit
    beside the weights) and nothing large beside it."""
    from ray_tpu.ops import kda

    slots, heads, d = 65, 32, 128
    pool = (20, slots, heads, d, d)
    assert kda.kernel_serves(jax.ShapeDtypeStruct(pool, jnp.float32), backend="tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        shape = lambda s, dt=jnp.float32: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
        compiled = jax.jit(
            lambda state, *a: kda.update(state, 19, *a, interpret=False), donate_argnums=0
        ).lower(
            shape(pool), shape((slots, heads, d)), shape((slots, heads, d)), shape((slots, heads, d)),
            shape((slots, heads, d)), shape((slots, heads)), shape((slots,), jnp.bool_),
        ).compile()
        text, memory = compiled.as_text(), compiled.memory_analysis()
        assert text.count('custom_call_target="tpu_custom_call"') == 1 and "kda_update" in text
        assert memory.alias_size_in_bytes == 20 * slots * heads * d * d * 4
        assert memory.temp_size_in_bytes < 2**20
        assert [o.shape for o in compiled.out_info] == [pool, (slots, heads, d)]
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.mark.parametrize("chunk", [1024, 256], ids=["chunk_1024", "chunk_256"])
@pytest.mark.parametrize("name", ["mistral-7b-v0.3-16l", "olmoe-1b-7b-0125-12l"])
def test_a_plain_configurations_prefill_chunk_compiles_for_the_chip_through_the_flash_kernel(
    one_chip, monkeypatch, name, chunk
):
    """Both prefill programs of the benchmark's two plain configurations
    (Mistral: 32 heads over 8 KV heads of 128; OLMoE: 16 over 16, experts
    through ``gmm``), ONE layer of each at the file's widths over its pool and
    its 4096-key table, compiled for the real chip (nothing runs): the chunk's
    attention is ONE ``latent_flash`` call over K and V gathered through the
    table (``paged_kv.way`` from shapes, PR 51), and no program holds
    the materialised way's float32 scores ``[chunk, heads, 4096]`` (537 MB a
    layer at Mistral's 1024-chunk) among its temporaries."""
    from perfbench import families
    from perfbench.harness import cells

    model = cells.config_of(cells.benchmark(), name)
    cfg = families.of(model).model_config(model, max_seq_len=int(model["max_position_embeddings"]))
    cfg = dataclasses.replace(cfg, n_layers=1)
    engine = model["serving"]["engine"]
    assert chunk in engine["prefill_buckets"] and not cfg.layer_windows
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # steer the branch; the test's business
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        on_chip = lambda tree: jax.tree.map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), tree)
        params = on_chip(jax.eval_shape(lambda: L.init_params(cfg, jax.random.PRNGKey(0))))
        cache = on_chip(jax.eval_shape(lambda: L.init_paged_kv_cache(cfg, engine["num_blocks"], engine["block_size"])))
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)  # noqa: E731
        assert L._attention_path(cfg, chunk, cache) == ("flash", "live")
        compiled = jax.jit(lambda *args: L.paged_prefill_step(cfg, *args), donate_argnums=(1,)).lower(
            params, cache, i32(chunk), i32(cfg.max_seq_len // engine["block_size"]), i32(), i32(),
        ).compile()
        text = compiled.as_text()
        assert text.count("latent_flash") >= 1 and text.count('custom_call_target="tpu_custom_call"') == (
            1 + (3 if cfg.moe_experts else 0)  # the experts' three grouped matmuls
        )
        scores = chunk * cfg.n_heads * cfg.max_seq_len * 4
        assert compiled.memory_analysis().temp_size_in_bytes < scores // 2
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


def test_the_selecting_chunks_kernel_compiles_for_the_chip_at_glm5_widths(one_chip):
    """GLM-5's chunk under its selection (``ops/latent_flash.py::attend_selected``;
    here for the same reason as the ones above): 64 heads, latent rows of 512 +
    64, keys 192 + 64 and values 256 expanded in VMEM, 1024 queries under an
    int8 mask over a table of 32,768 positions, at the tiles the module fixes.
    One Mosaic call at the TABLE's width whatever the context; K, V and the
    scores are nobody's temporary (all that XLA adds is the head-major weights
    and ``[[W_k, 0], [0, I]]``: 64 x 576 x 256)."""
    from ray_tpu.ops import latent_flash as LF

    H, C, S, kr, dn, dr, dv = 64, 1024, 32768, 512, 192, 64, 256
    assert LF.selected_serves(C, S, kr, dn, dr, dv, jnp.bfloat16, backend="tpu")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        shape = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
        compiled = jax.jit(
            lambda q, rows, w_k, w_v, mask, ctx, n: LF.attend_selected(
                q, rows, w_k, w_v, mask, ctx, n, scale=0.0625, interpret=False
            )
        ).lower(
            shape((H, C, dn + dr)), shape((S, kr + dr)), shape((H, kr, dn)), shape((H, kr, dv)),
            shape((C, S), jnp.int8), shape((), jnp.int32), shape((), jnp.int32),
        ).compile()
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1 and "latent_flash_selected" in text
        assert compiled.memory_analysis().temp_size_in_bytes < 2 * H * (kr + dr) * (dn + dr) * 2
        assert compiled.out_info.shape == (H, C, dv)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.mark.parametrize("kernel", ["state_update", "decode_attention", "chunk_1024", "chunk_256",
                                    "recurrence_1024", "recurrence_256"])
def test_olmo_hybrid_s_kernels_compile_for_the_chip_at_the_published_widths(one_chip, kernel):
    """Olmo-Hybrid-7B's four kernels at the benchmark's sizes (here for the
    same reason as the ones above): the decode update of a Gated DeltaNet
    layer's slab whose heads are JOINED along the lanes (``ops/kda.py``: 65
    slots x 30 heads of 96 x 192 float32 as ``[96, 5760]``, ten heads a grid
    step, the pool aliased in and out); the decode attention over 30 KV heads of
    128 stored flat under ONE query row each (64 slots, a table of 4096, the
    whole cache as it lies); the chunk's flash kernel over 30 heads; the chunk's
    Gated DeltaNet recurrence (``ops/gdn_chunk.py``: one slot's 30 heads of 96 x
    192 over 16 or 4 sub-chunks of 64, the operands laid heads first around
    it). One Mosaic call each; neither pool nor the scores is a temporary."""
    from ray_tpu.ops import gdn_chunk, kda
    from ray_tpu.ops import latent_flash as LF
    from ray_tpu.ops import paged_attention as PA

    cache_was, temporaries = jax.config.jax_enable_compilation_cache, 2 * 2**20
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        shape = lambda s, dt=jnp.bfloat16: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)  # noqa: E731
        if kernel == "state_update":
            n, slots, H, dk, dv = 12, 65, 30, 96, 192
            pool = jax.ShapeDtypeStruct((n, slots, dk, H * dv), jnp.float32)
            assert kda.kernel_serves(pool, "tpu", heads=H) and kda._joined_block(H, dk, dv) == 10
            f32 = lambda *s: shape(s, jnp.float32)  # noqa: E731
            compiled = jax.jit(
                lambda pool, layer, q, k, v, g, beta, fresh: kda.update(pool, layer, q, k, v, g, beta, fresh, interpret=False),
                donate_argnums=0,
            ).lower(
                f32(n, slots, dk, H * dv), shape((), jnp.int32), f32(slots, H, dk), f32(slots, H, dk), f32(slots, H, dv),
                f32(slots, H), f32(slots, H), shape((slots,), jnp.bool_),
            ).compile()
            text, name, out = compiled.as_text(), "kda_update", (slots, H, dv)
            assert compiled.memory_analysis().alias_size_in_bytes == n * slots * dk * H * dv * 4  # in place
            assert compiled.out_info[1].shape == out
        elif kernel == "decode_attention":
            L, N, B, H, hd = 4, 5001, 64, 30, 128
            cache = shape((L, N, 16 * H, hd))
            assert PA.kernel_serves(1, H, cache, "tpu", n_kv=H, head_dim=hd)
            compiled = jax.jit(
                lambda q, k, v, tables, pos: PA.paged_attention(q, k, v, L - 1, tables, pos, interpret=False, n_kv=H)
            ).lower(shape((B, 1, H, hd)), cache, cache, shape((B, 256), jnp.int32), shape((B, 1), jnp.int32)).compile()
            text, name = compiled.as_text(), "paged_attn"
            assert compiled.out_info.shape == (B, 1, H, hd)
        elif kernel.startswith("recurrence"):
            T, H, dk, dv = int(kernel.split("_")[1]), 30, 96, 192
            f32 = lambda *s: shape(s, jnp.float32)  # noqa: E731
            operands = f32(1, H, dk, dv), f32(1, T, H, dk), f32(1, T, H, dk), f32(1, T, H, dv), f32(1, T, H), f32(1, T, H)
            assert gdn_chunk.kernel_serves(operands[0], operands[1], operands[3], 64, "tpu")
            compiled = jax.jit(lambda *a: gdn_chunk.chunked(*a, 64, interpret=False)).lower(*operands).compile()
            text, name = compiled.as_text(), "gdn_chunk"
            assert [o.shape for o in compiled.out_info] == [(1, H, dk, dv), (1, T, H, dv)]
            # the operands heads first and the output back: five arrays of the chunk's size, nothing of a sub-chunk's
            temporaries = 5 * T * H * 256 * 4
        else:
            window, H, S, hd = int(kernel.split("_")[1]), 30, 4096, 128
            assert LF.kernel_serves(window, S, hd, hd, 0, jnp.bfloat16, backend="tpu", kv_heads=H)
            compiled = jax.jit(
                lambda q, k, v, ctx, n: LF.flash_attention(q, k, v, ctx, n, scale=hd ** -0.5, group=1, interpret=False)
            ).lower(shape((H, window, hd)), shape((H, S, hd)), shape((H, S, hd)), shape((), jnp.int32),
                    shape((), jnp.int32)).compile()
            text, name = compiled.as_text(), "latent_flash"
            assert compiled.out_info.shape == (H, window, hd)
        assert text.count('custom_call_target="tpu_custom_call"') == 1 and name in text
        assert compiled.memory_analysis().temp_size_in_bytes < temporaries
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
