"""Gated DeltaNet's chunked form as a kernel (``ops/gdn_chunk.py``) on the CPU
in Pallas' TPU interpreter, against the two things it has to be: the plain
chunked form it replaces on a TPU (``ops/delta_rule.py::gdn_chunked``) and the
recurrence a position (``kda_update`` with the gate broadcast), at the published
widths (30 heads of 96 x 192, sub-chunks of 64) and at the tests' toy widths
(3 heads of 8 x 16, sub-chunks of 8); and the choice between kernel and plain
form, which shapes, dtypes and the backend make at trace time."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import olmo_hybrid as oh
from ray_tpu.ops import delta_rule, gdn_chunk

F32 = jnp.float32


def _rel(have, want):
    return float(np.abs(np.asarray(have) - np.asarray(want)).max() / np.abs(np.asarray(want)).max())


def _unit(a):
    return a / np.linalg.norm(a, axis=-1, keepdims=True)


def _window(seed, T, H, dk, dv, betas=(0.0, 2.0), rho=0.0, real=None):
    """``(q, k, v, g, beta) [1, T, ...]`` as a Gated DeltaNet mixer hands them
    over: q and k normalised a head, ONE gate a head from heads that forget in
    a position to heads that outlive the window, ``beta`` in ``betas``, a head's
    keys with a common part ``rho`` (pairwise cosine ``rho^2``). Behind the first
    ``real`` positions the window's padding: ``beta = 0`` and ``g = 0`` under q, k
    and v that are NOT zero (nothing of them may move the state)."""
    rng = np.random.default_rng(seed)
    common = _unit(rng.standard_normal((1, 1, H, dk)))
    k = _unit(rho * common + np.sqrt(1 - rho ** 2) * _unit(rng.standard_normal((1, T, H, dk))))
    q = _unit(rng.standard_normal((1, T, H, dk))) * dk ** -0.5
    v = rng.standard_normal((1, T, H, dv))
    g = -np.exp(rng.uniform(np.log(1e-3), np.log(3.0), (1, 1, H))) * rng.uniform(0.5, 1.5, (1, T, H))
    beta = rng.uniform(*betas, (1, T, H))
    if real is not None:
        g[:, real:], beta[:, real:] = 0.0, 0.0
    return tuple(jnp.asarray(a, F32) for a in (q, k, v, g, beta))


@jax.jit
def _a_position_at_a_time(S, q, k, v, g, beta):
    def step(S, xs):
        q, k, v, g, beta = xs
        return delta_rule.kda_update(S, q, k, v, g[..., None], beta)

    S, o = jax.lax.scan(step, S, tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v, g, beta)))
    return S, jnp.swapaxes(o, 0, 1)


PUBLISHED, TOY = (96, 192, 64), (8, 16, 8)  # dk, dv, positions a sub-chunk

#: id -> (heads, (dk, dv, chunk), T, real positions, carried state, betas, rho, heads a grid step,
#: calls the window is cut into, tolerance against the recurrence)
CASES = {
    "published.256.fresh": (30, PUBLISHED, 256, 256, False, (0.0, 2.0), 0.0, None, 1, 1e-5),
    "published.256.carried.heads-of-ten": (30, PUBLISHED, 256, 256, True, (0.0, 2.0), 0.0, 10, 1, 1e-5),
    "published.1024.carried": (2, PUBLISHED, 1024, 1024, True, (0.0, 2.0), 0.0, None, 1, 1e-5),
    "published.1024.fresh.a-head-a-step": (3, PUBLISHED, 1024, 1024, False, (0.0, 2.0), 0.0, 1, 1, 1e-5),
    "published.padded-tail": (2, PUBLISHED, 256, 137, True, (0.0, 2.0), 0.0, None, 1, 1e-5),
    "published.one-real-row": (2, PUBLISHED, 256, 1, True, (0.0, 2.0), 0.0, None, 1, 1e-5),
    "published.over-a-chunks-edge": (2, PUBLISHED, 512, 400, True, (0.0, 2.0), 0.0, None, 2, 1e-5),
    "published.beta-under-one": (2, PUBLISHED, 256, 256, False, (0.0, 1.0), 0.0, None, 1, 1e-5),
    "published.beta-near-two": (2, PUBLISHED, 256, 256, False, (1.8, 2.0), 0.0, None, 1, 1e-5),
    "published.keys-alike": (2, PUBLISHED, 128, 128, False, (0.0, 2.0), 0.8, None, 1, 1e-4),
    "published.keys-very-alike": (2, PUBLISHED, 128, 128, False, (0.0, 2.0), 0.95, None, 1, 1e-3),
    "published.keys-alike.beta-near-two": (2, PUBLISHED, 128, 128, False, (1.8, 2.0), 0.8, None, 1, 1e-2),
    "toy.fresh": (3, TOY, 48, 48, False, (0.0, 2.0), 0.0, None, 1, 1e-5),
    "toy.carried.padded-tail": (3, TOY, 48, 29, True, (0.0, 2.0), 0.0, None, 1, 1e-5),
    "toy.over-a-chunks-edge": (3, TOY, 64, 64, True, (0.0, 2.0), 0.0, 1, 4, 1e-5),
    "sub-chunks-of-32.one-merge": (2, (16, 8, 32), 96, 96, True, (0.0, 2.0), 0.5, 2, 1, 1e-5),
}


@pytest.mark.parametrize("case", CASES)
def test_the_kernel_is_the_chunked_form_and_the_recurrence(case):
    """Every case both ways: against ``gdn_chunked`` on the same operands and
    against the recurrence over the real positions, as far as the float32 WY
    form holds there (``tests/test_delta_rule.py`` has the plain form's own
    reach: with keys alike the two chunked forms stand as far from each other
    as each from the recurrence, the kernel no further than the plain form). A
    window cut into several calls hands the state from one to the next, as a
    prompt's chunks do."""
    H, (dk, dv, chunk), T, real, carried, betas, rho, hb, calls, tol = CASES[case]
    window = _window(len(case), T, H, dk, dv, betas, rho, real)
    S0 = jnp.asarray(np.random.default_rng(5).standard_normal((1, H, dk, dv)), F32) * carried
    S, outs = S0, []
    for part in range(calls):
        cut = slice(part * T // calls, (part + 1) * T // calls)
        S, o = gdn_chunk.chunked(S, *(a[:, cut] for a in window), chunk, head_block=hb, interpret=True)
        outs.append(o)
    o = jnp.concatenate(outs, axis=1)
    assert bool(jnp.all(jnp.isfinite(o))) and bool(jnp.all(jnp.isfinite(S)))
    S_plain, o_plain = delta_rule.gdn_chunked(S0, *window, chunk)
    assert _rel(S, S_plain) < tol and _rel(o[:, :real], o_plain[:, :real]) < tol
    S_want, o_want = _a_position_at_a_time(S0, *(a[:, :real] for a in window))
    assert _rel(S, S_want) < tol and _rel(o[:, :real], o_want) < tol


def test_a_batch_of_sequences_is_its_sequences_and_the_gradient_is_the_plain_forms():
    q, k, v, g, beta = (jnp.concatenate([a, a[:, ::-1]]) for a in _window(3, 16, 3, 8, 16))
    S0 = jnp.asarray(np.random.default_rng(6).standard_normal((2, 3, 8, 16)), F32)
    S, o = gdn_chunk.chunked(S0, q, k, v, g, beta, 8, interpret=True)
    S_plain, o_plain = delta_rule.gdn_chunked(S0, q, k, v, g, beta, 8)
    assert _rel(S, S_plain) < 1e-5 and _rel(o, o_plain) < 1e-5

    def loss(chunked, S0, q, g):
        S, o = chunked(S0, q, k, v, g, beta)
        return (o ** 2).sum() + S.sum()

    have = jax.grad(functools.partial(loss, functools.partial(gdn_chunk.chunked, chunk=8, interpret=True)), (0, 1, 2))(S0, q, g)
    want = jax.grad(functools.partial(loss, functools.partial(delta_rule.gdn_chunked, chunk=8)), (0, 1, 2))(S0, q, g)
    for a, b in zip(have, want):
        assert _rel(a, b) < 1e-5


def test_which_chunks_the_kernel_serves():
    """A TPU, float32, whole sub-chunks of 64 x a power of two, widths of whole
    sublanes: the published model's two prefill buckets. Not the CPU (these
    tests), not the toy's sub-chunks of 8, not a bfloat16 state."""
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, F32)  # noqa: E731
    S, q, v = f32(1, 30, 96, 192), f32(1, 1024, 30, 96), f32(1, 1024, 30, 192)
    assert gdn_chunk.kernel_serves(S, q, v, 64, "tpu") and gdn_chunk.kernel_serves(S, f32(1, 256, 30, 96), f32(1, 256, 30, 192), 64, "tpu")
    assert gdn_chunk.kernel_serves(f32(4, 30, 96, 192), f32(4, 128, 30, 96), f32(4, 128, 30, 192), 128, "tpu")
    assert not gdn_chunk.kernel_serves(S, q, v, 64, "cpu") and not gdn_chunk.kernel_serves(S, q, v, 64)
    assert not gdn_chunk.kernel_serves(S, q, v, 96, "tpu") and not gdn_chunk.kernel_serves(S, q, v, 32, "tpu")
    assert not gdn_chunk.kernel_serves(S, f32(1, 1000, 30, 96), f32(1, 1000, 30, 192), 64, "tpu")
    assert not gdn_chunk.kernel_serves(f32(1, 3, 8, 16), f32(1, 48, 3, 8), f32(1, 48, 3, 16), 8, "tpu")  # the toy
    assert not gdn_chunk.kernel_serves(f32(1, 30, 100, 192), f32(1, 256, 30, 100), f32(1, 256, 30, 192), 64, "tpu")
    assert not gdn_chunk.kernel_serves(jax.ShapeDtypeStruct((1, 30, 96, 192), jnp.bfloat16), q, v, 64, "tpu")
    with pytest.raises(ValueError, match="whole sub-chunks"):
        gdn_chunk.chunked(jnp.zeros((1, 3, 8, 16)), *(jnp.zeros(s) for s in ((1, 20, 3, 8), (1, 20, 3, 8), (1, 20, 3, 16), (1, 20, 3), (1, 20, 3))), 8)
    with pytest.raises(ValueError, match="a block of heads that divides"):
        gdn_chunk.chunked(jnp.zeros((1, 3, 8, 16)), *(jnp.zeros(s) for s in ((1, 16, 3, 8), (1, 16, 3, 8), (1, 16, 3, 16), (1, 16, 3), (1, 16, 3))), 8, head_block=2)


@pytest.mark.parametrize("serves", [False, True], ids=["the-plain-form", "the-kernel"])
def test_a_prefill_chunk_runs_what_the_predicate_says(serves, monkeypatch):
    """The toy model's prefill program over two chunks of one prompt (a carried
    state, a padded tail): on the CPU the predicate says no and the kernel is
    never called; told yes (the question patched, the kernel in the interpreter)
    every Gated DeltaNet layer of every chunk goes through it, and the logits
    and both pools are the plain form's."""
    cfg = oh.OlmoHybridConfig.tiny()
    params = oh.init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (32,), 0, cfg.vocab_size, jnp.int32)
    table = jnp.arange(1, 9, dtype=jnp.int32)

    def prefill():
        cache, state = oh.cache_layout(cfg, 8).init(9), oh.state_layout(cfg).init(3)
        for ctx, true_len in ((0, 16), (16, 11)):
            cache, state, logits = oh.paged_prefill_step(
                cfg, params, cache, state, tokens[ctx : ctx + 16], table, jnp.int32(ctx), jnp.int32(true_len), jnp.int32(2)
            )
        return logits, state

    want_logits, want_state = prefill()
    calls, chunked = [], gdn_chunk.chunked

    def kernel(*args):
        calls.append(args[1].shape)
        return chunked(*args, interpret=True)

    monkeypatch.setattr(oh.gdn_chunk, "chunked", kernel)
    if serves:
        monkeypatch.setattr(oh.gdn_chunk, "kernel_serves", lambda *a, **k: True)
    logits, state = prefill()
    assert len(calls) == (2 * cfg.n_gdn_layers if serves else 0)
    assert _rel(logits, want_logits) < 1e-5
    for name in want_state:
        assert _rel(state[name].astype(F32), want_state[name].astype(F32)) < 1e-5
