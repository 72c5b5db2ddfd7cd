"""The grouped expert matmul over a scanned model's STACKED expert weights
(``ops/moe.py::grouped_matmul`` told a ``layer``; ``models/xing4.py::
_scan_layers(experts_in_place=True)``, which ``models/deepseek_v3.py``
reuses), on the CPU: the stack read in place equals the slice, for the
matmul alone (``jax.lax.ragged_dot``, and ``megablox.gmm`` in Pallas'
interpreter, since the CPU never runs the kernel) and for every paged step of
both latent models against the PARENT's scan (a copy kept here: each layer's
expert matrices among the scan's ``xs``); a rank-3 call lowers to the text
it lowered to before, so OLMoE's and Kimi-Linear's programs and the training
step are what they were; and the serving body, lowered for a TPU, slices no
layer's expert matrices out of the stack (the stand-in for the trace's
witness: no ``dynamic-slice_bitcast_fusion`` of 0.47 GB a matrix a layer)."""

import functools
import os
import re
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models import deepseek_v3 as dsv3, kimi_linear, llama, xing4  # noqa: E402
from ray_tpu.models.interface import model_of  # noqa: E402
from ray_tpu.ops import moe  # noqa: E402

BS = 8


# -- the parent's code, kept here as the other side ---------------------------------------------

def parents_grouped_matmul(xs, w, group_sizes, layer=None):
    """``ops/moe.py::grouped_matmul`` as it stood before it took a stack."""
    assert layer is None and w.ndim == 3
    m, k = xs.shape
    n = w.shape[2]
    if jax.default_backend() == "tpu" and k % 128 == 0 and n % 128 == 0:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        tm = 256 if m >= 4096 else 128
        out = gmm(
            jnp.pad(xs, ((0, -m % tm), (0, 0))), w, group_sizes,
            preferred_element_type=xs.dtype, tiling=(tm, min(k, 1024), min(n, 1024)),
        )
        return out[:m]
    return jax.lax.ragged_dot(xs, w, group_sizes)


def parents_scan_layers(cfg, params, X, attention, valid, wrap=None, layer0=0, experts_in_place=False):
    """``models/xing4.py::_scan_layers`` as it stood: every weight of a layer,
    its expert matrices too, a slice of the scan's ``xs``."""
    del experts_in_place
    rows, aux = [], {}
    for name, count, is_moe in xing4._groups(cfg):
        if name not in params:
            continue

        def body(carry, p, is_moe=is_moe):
            X, layer = carry
            X, layer_rows, layer_aux = xing4._layer(
                cfg, p, X, lambda p, h: attention(p, h, layer), valid, is_moe
            )
            return (X, layer + 1), (layer_rows, layer_aux)

        if wrap is not None:
            body = wrap(body)
        (X, _), (group_rows, group_aux) = jax.lax.scan(body, (X, jnp.int32(layer0)), params[name])
        layer0 += count
        rows.append(group_rows)
        if is_moe:
            aux = group_aux
    rows = None if rows[0] is None else jnp.concatenate(rows)
    return X, rows, aux


def _text(lowered):
    """A lowered program without what names the place it was traced from."""
    return [line.split(" loc(")[0] for line in lowered.as_text().splitlines()
            if "module @" not in line and not line.startswith("#loc")]


@pytest.fixture
def on_a_tpu(monkeypatch):
    """Steer the branch a TPU takes (nothing runs there; the test's business)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _gmm_calls(text):
    return sum("call @gmm" in line for line in text)


def _for_a_tpu(f, *args):
    """``f`` traced and lowered FOR a TPU, without one: StableHLO with the
    Pallas calls as ``tpu_custom_call``s."""
    return jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))


# -- (i) the matmul alone -------------------------------------------------------------------------

L, G, K, N, M = 3, 4, 128, 256, 200
SIZES = {"all_rows": [60, 0, 99, 41], "held_style_fewer_than_m": [30, 0, 100, 41], "one_group": [0, 0, 77, 0]}


@pytest.fixture(scope="module")
def operands():
    rng = np.random.default_rng(3)
    return (jnp.asarray(rng.standard_normal((M, K)), jnp.float32),
            jnp.asarray(rng.standard_normal((L, G, K, N)), jnp.float32))


@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
@pytest.mark.parametrize("layer", range(L))
def test_the_stack_with_a_traced_layer_equals_the_slice(operands, layer, sizes):
    xs, stack = operands
    sizes = jnp.asarray(sizes, jnp.int32)
    have = jax.jit(moe.grouped_matmul)(xs, stack, sizes, jnp.int32(layer))
    want = jax.jit(moe.grouped_matmul)(xs, stack[layer], sizes)
    rows = int(sizes.sum())  # behind the last group: unspecified
    assert np.array_equal(np.asarray(have)[:rows], np.asarray(want)[:rows])


@pytest.mark.parametrize("sizes", SIZES.values(), ids=SIZES.keys())
@pytest.mark.parametrize("layer", range(L))
def test_the_chips_kernel_reads_the_layers_groups_in_the_merged_stack(operands, layer, sizes, on_a_tpu, monkeypatch):
    """The TPU branch in Pallas' interpreter: ``gmm`` over ``[L g, k, n]``
    under sizes that are zero outside the layer's ``g`` gives the layer's
    product, and no other layer's matrix reaches a row (each layer's are
    different numbers)."""
    from jax.experimental.pallas.ops.tpu import megablox

    monkeypatch.setattr(megablox, "gmm", functools.partial(megablox.gmm, interpret=True))
    xs, stack = operands
    sizes = jnp.asarray(sizes, jnp.int32)
    have = jax.jit(moe.grouped_matmul)(xs, stack, sizes, jnp.int32(layer))
    assert have.shape == (M, N)
    rows = int(sizes.sum())
    want = jax.lax.ragged_dot(xs, stack[layer], sizes)
    np.testing.assert_allclose(np.asarray(have)[:rows], np.asarray(want)[:rows], rtol=1e-5, atol=1e-4)


def test_dropless_ffn_over_a_stack_equals_the_layers_own():
    """``dropless_moe_ffn`` told ``layer``: the routed output and every
    counter of a layer's slice, under a held range and padding rows."""
    rng = np.random.default_rng(5)
    D, F, E, held = 32, 16, 8, (2, 6)
    f = lambda *s: jnp.asarray(rng.standard_normal(s) / np.sqrt(s[-2]), jnp.float32)  # noqa: E731
    stacks = {"w_gate": f(L, 4, D, F), "w_up": f(L, 4, D, F), "w_down": f(L, 4, F, D)}
    own = {"router": f(D, E), "router_bias": 0.1 * f(1, E)[0]}
    x, valid = f(24, D) * 4, jnp.arange(24) < 19
    kw = dict(top_k=2, renormalize=True, valid=valid, scoring="sigmoid", scale=2.0, held=held, n_group=4, topk_group=2)
    for layer in range(L):
        have = jax.jit(lambda s, l: moe.dropless_moe_ffn({**own, **s}, x, layer=l, **kw))(stacks, jnp.int32(layer))
        want = jax.jit(lambda s: moe.dropless_moe_ffn({**own, **s}, x, **kw))({k: v[layer] for k, v in stacks.items()})
        jax.tree_util.tree_map(lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)), have, want)


# -- (ii) every paged step of both latent models against the parent's scan ----------------------

def _toy(family):
    if family == "xing4":
        cfg = xing4.Xing4Config.tiny(held_experts=(2, 6), n_layers=5, n_dense_layers=2)
        return cfg, xing4.init_params(cfg, jax.random.PRNGKey(2))
    cfg = dsv3.DeepseekV3Config.tiny(held_experts=(2, 6), n_layers=4, n_dense_layers=1)
    return cfg, dsv3.init_params(cfg, jax.random.PRNGKey(2))


def _steps(family, step):
    """``step`` of the toy ``family`` after a prefill: everything it returns
    (cache, logits or tokens, counters), as numpy."""
    cfg, params = _toy(family)
    mod = model_of(cfg)
    tokens = np.random.default_rng(11).integers(1, 256, size=(3, 40)).astype(np.int32)
    cache = model_of(cfg).cache_layout(cfg, BS).init(40)
    tables = np.arange(1, 25, dtype=np.int32).reshape(3, 8)
    lens = (21, 13, 30)
    out = {}
    for slot, n in enumerate(lens):
        chunk = np.zeros(32, np.int32)
        chunk[:n] = tokens[slot, :n]
        extra = (np.int32(-1),) if family == "deepseek_v3" else ()
        cache, *rest = jax.jit(lambda p, c, *a: mod.paged_prefill_step(cfg, p, c, *a))(
            params, cache, chunk, tables[slot], np.int32(0), np.int32(n), *extra)
        out[f"prefill{slot}"] = rest
    if step == "prefill":
        return {"cache": cache, **out}
    ctx = np.asarray(lens, np.int32)
    if step == "decode":
        got = jax.jit(lambda p, c, *a: mod.paged_decode_step(cfg, p, c, *a))(
            params, cache, tokens[:, 39], ctx, tables, ctx + 1)
    elif step == "verify":
        got = jax.jit(lambda p, c, *a: mod.paged_verify_step(cfg, p, c, *a))(
            params, cache, tokens[:, 36:40], tables, ctx, np.asarray([4, 2, 0], np.int32))
    else:
        got = jax.jit(lambda p, c, *a: dsv3.paged_mtp_step(cfg, p, c, *a))(
            params, cache, tokens[:, 38:40], tables, ctx - 1, np.asarray([2, 2, 0], np.int32),
            np.asarray([2, 1, 1], np.int32))
    return {step: got}


STEPS = [("xing4", "prefill"), ("xing4", "decode"), ("xing4", "verify"),
         ("deepseek_v3", "prefill"), ("deepseek_v3", "decode"), ("deepseek_v3", "verify"), ("deepseek_v3", "mtp_step")]


@pytest.mark.parametrize("family, step", STEPS, ids=[f"{f}.{s}" for f, s in STEPS])
def test_a_paged_step_over_the_stack_equals_the_parents_scan_of_slices(family, step, monkeypatch):
    """Logits (or the one-program step's tokens), every cache row and every
    counter (``load``, ``bias_changed``, ``group_changed``, ``routed_rows``),
    exactly: on the CPU both sides are ``ragged_dot`` on the same numbers.
    DeepSeek-V3's module runs as a stack of ONE through the same path."""
    have = _steps(family, step)
    counters = jax.tree_util.tree_leaves(have)[-1]
    with monkeypatch.context() as m:
        m.setattr(xing4, "_scan_layers", parents_scan_layers)
        want = _steps(family, step)
    assert jax.tree_util.tree_structure(have) == jax.tree_util.tree_structure(want)
    for a, b in zip(jax.tree_util.tree_leaves(have), jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert np.asarray(counters).size and len(jax.tree_util.tree_leaves(have)) > 3


@pytest.mark.parametrize("family, layers, says", [
    ("xing4", 38, 38), ("deepseek_v3", 6, 6), ("deepseek_v3", 1, 1), ("kimi_linear", 26, 0), ("olmoe", 12, 0),
])
def test_the_model_says_which_expert_layers_read_the_stack(family, layers, says):
    cfg = {"xing4": xing4.Xing4Config.tiny, "deepseek_v3": dsv3.DeepseekV3Config.tiny,
           "kimi_linear": kimi_linear.KimiLinearConfig.tiny,
           "olmoe": lambda: llama.LlamaConfig.tiny(moe_experts=4)}[family]()
    assert model_of(cfg).experts_in_place(cfg, layers) == says


# -- (iii) a rank-3 call is the parent's program ------------------------------------------------

@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("m", [64, 256, 4096])
def test_a_rank_3_call_lowers_to_the_parents_text(backend, m, monkeypatch):
    if backend == "tpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = (jnp.zeros((m, 128), jnp.bfloat16), jnp.zeros((8, 128, 256), jnp.bfloat16), jnp.zeros((8,), jnp.int32))
    lower = lambda f: _for_a_tpu(f, *args) if backend == "tpu" else jax.jit(f).lower(*args)  # noqa: E731
    have, want = _text(lower(lambda *a: moe.grouped_matmul(*a))), _text(lower(lambda *a: parents_grouped_matmul(*a)))
    assert have == want and _gmm_calls(have) == (backend == "tpu")


def _olmoe_decode():
    cfg = llama.LlamaConfig.tiny(moe_experts=4, moe_top_k=2, dim=128, mlp_hidden=128, n_heads=2, n_kv_heads=2)
    params = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: model_of(cfg).cache_layout(cfg, BS).init(16))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    return (lambda p, c, *a: llama.paged_decode_step(cfg, p, c, *a)), (params, cache, i32(4), i32(4), i32(4, 8), i32(4))


def _kimi_decode():
    cfg = kimi_linear.KimiLinearConfig.tiny(dim=128, moe_hidden=128)
    model = model_of(cfg)
    params = jax.eval_shape(lambda: kimi_linear.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: model.cache_layout(cfg, BS).init(16))
    state = jax.eval_shape(lambda: model.state_layout(cfg).init(5))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    return (lambda p, c, s, *a: kimi_linear.paged_decode_step(cfg, p, c, s, *a)), (
        params, cache, state, i32(4), i32(4), i32(4, 8), i32(4), i32(4))


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
@pytest.mark.parametrize("program", [_olmoe_decode, _kimi_decode], ids=["olmoe", "kimi_linear"])
def test_the_unscanned_models_decode_programs_are_the_parents(program, backend, monkeypatch):
    """OLMoE (layers unrolled in ``models/llama.py``) and Kimi-Linear (a
    Python loop over 27 layers) call with rank 3: their decode programs,
    with the parent's ``grouped_matmul`` put back, are the same text."""
    if backend == "tpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    def lower():
        f, args = program()  # a function of its own each time: a trace is cached by the function
        return _text(_for_a_tpu(f, *args) if backend == "tpu" else jax.jit(f).lower(*args))

    have = lower()
    with monkeypatch.context() as m:
        m.setattr(moe, "grouped_matmul", parents_grouped_matmul)
        want = lower()
    assert have == want
    assert _gmm_calls(have) >= (3 if backend == "tpu" else 0) and (_gmm_calls(have) > 0) == (backend == "tpu")


# -- (iv) the serving body slices no layer's expert matrices ------------------------------------

def _wide(family):
    """Toy models at whole lanes (128), so that a TPU's branch is ``gmm``."""
    kw = dict(dim=128, moe_hidden=128, held_experts=(2, 6), dtype=jnp.bfloat16)
    if family == "xing4":
        cfg = xing4.Xing4Config.tiny(n_layers=5, n_dense_layers=2, **kw)
        return cfg, jax.eval_shape(lambda: xing4.init_params(cfg, jax.random.PRNGKey(0)))
    cfg = dsv3.DeepseekV3Config.tiny(n_layers=4, n_dense_layers=1, **kw)
    return cfg, jax.eval_shape(lambda: dsv3.init_params(cfg, jax.random.PRNGKey(0)))


def _serving_program(family, step):
    cfg, params = _wide(family)
    mod = model_of(cfg)
    cache = jax.eval_shape(lambda: model_of(cfg).cache_layout(cfg, BS).init(16))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    if step == "prefill":
        extra = (i32(),) if family == "deepseek_v3" else ()
        return cfg, (lambda p, c, *a: mod.paged_prefill_step(cfg, p, c, *a)), (params, cache, i32(32), i32(8), i32(), i32(), *extra)
    if step == "decode":
        return cfg, (lambda p, c, *a: mod.paged_decode_step(cfg, p, c, *a)), (params, cache, i32(4), i32(4), i32(4, 8), i32(4))
    if step == "verify":
        return cfg, (lambda p, c, *a: mod.paged_verify_step(cfg, p, c, *a)), (params, cache, i32(4, 4), i32(4, 8), i32(4), i32(4))
    return cfg, (lambda p, c, *a: dsv3.paged_mtp_step(cfg, p, c, *a)), (params, cache, i32(4, 2), i32(4, 8), i32(4), i32(4), i32(4))


def _slices_of_a_layers_experts(cfg, text):
    """The ``dynamic_slice``s of the lowered text whose result is one layer's
    ``[n_held, k, n]`` (with or without the leading 1 a scan leaves)."""
    g, D, F = cfg.n_held, cfg.dim, cfg.moe_hidden
    shapes = [f"{lead}{g}x{a}x{b}x" for a, b in ((D, F), (F, D)) for lead in ("<", "<1x")]
    return [line for line in text if "dynamic_slice" in line and "dynamic_update_slice" not in line
            and any(s in line.split("->")[-1] for s in shapes)]


@pytest.mark.parametrize("family, step", STEPS, ids=[f"{f}.{s}" for f, s in STEPS])
def test_the_serving_body_for_a_tpu_slices_no_layers_expert_matrices(family, step, on_a_tpu, monkeypatch):
    cfg, f, args = _serving_program(family, step)
    text = _text(_for_a_tpu(f, *args))
    assert _gmm_calls(text) >= 3 and any("tpu_custom_call" in line for line in text)  # the grouped matmuls are the kernel
    assert _slices_of_a_layers_experts(cfg, text) == []
    # the stack goes in whole: [L, g, k, n] seen as [L g, k, n]
    merged = re.compile(rf"reshape .*<(\d+)x{cfg.n_held}x{cfg.dim}x{cfg.moe_hidden}x\w+>\) -> tensor<(\d+)x{cfg.dim}x{cfg.moe_hidden}x")
    found = [m for m in map(merged.search, text) if m]
    assert found and all(int(m.group(2)) == int(m.group(1)) * cfg.n_held for m in found)
    # and the parent's scan, lowered the same way, does slice them: the check can see what it looks for
    with monkeypatch.context() as m:
        m.setattr(xing4, "_scan_layers", parents_scan_layers)
        _, f, args = _serving_program(family, step)  # a function of its own: a trace is cached by the function
        assert len(_slices_of_a_layers_experts(cfg, _text(_for_a_tpu(f, *args)))) >= 3


# -- (v) training is what it was ----------------------------------------------------------------

@pytest.mark.parametrize("family", ["xing4", "deepseek_v3"])
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_the_training_step_and_its_gradients_are_the_parents(family, remat, monkeypatch):
    """``forward`` keeps scanning slices: the loss, every gradient and the
    lowered text of the differentiated program equal the parent's scan's."""
    cfg, params = _toy(family)
    tokens = jnp.asarray(np.random.default_rng(4).integers(1, 256, size=(2, 16)), jnp.int32)
    loss = lambda p: xing4.next_token_loss(cfg, p, tokens, jnp.roll(tokens, -1, axis=1), remat=remat)  # noqa: E731
    have = jax.jit(jax.value_and_grad(loss))(params)
    have_text = _text(jax.jit(jax.value_and_grad(loss)).lower(params))
    with monkeypatch.context() as m:
        m.setattr(xing4, "_scan_layers", parents_scan_layers)
        want = jax.jit(jax.value_and_grad(loss))(params)
        want_text = _text(jax.jit(jax.value_and_grad(loss)).lower(params))
    for a, b in zip(jax.tree_util.tree_leaves(have), jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert have_text == want_text
    grads = have[1]["moe"]
    assert all(float(jnp.abs(grads[k]).max()) > 0 for k in ("w_gate", "w_up", "w_down", "router"))


def test_one_optimizer_step_leaves_the_parents_parameters(monkeypatch):
    import optax

    cfg, params = _toy("xing4")
    tokens = jnp.asarray(np.random.default_rng(4).integers(1, 256, size=(2, 16)), jnp.int32)
    batch = {"tokens": tokens, "targets": jnp.roll(tokens, -1, axis=1)}
    opt = optax.adamw(1e-3)

    def one_step():
        step = xing4.make_train_step(cfg, opt, donate=False)
        return step((params, opt.init(params)), batch)

    (have, _), have_loss = one_step()
    with monkeypatch.context() as m:
        m.setattr(xing4, "_scan_layers", parents_scan_layers)
        (want, _), want_loss = one_step()
    assert float(have_loss) == float(want_loss)
    for a, b in zip(jax.tree_util.tree_leaves(have), jax.tree_util.tree_leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
