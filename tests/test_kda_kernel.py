"""The decode update of a KDA layer's slab in the pool (``ops/kda.py``) against
the four lines it replaces, ``models/kimi_linear.py::kda_update``, on the CPU
in Pallas' TPU interpreter at the published head width (``dk = dv = 128``:
whole lanes) over a small pool: 3 layers x 5 slots x 4 heads, the kernel on
the MIDDLE layer's slab. Slot 0 is the null slot; slot 2 is held by nobody
(``beta = 0``, ``g = 0``: nothing of it moves); slot 3's sequence starts here
(``fresh``: its state reads as zeros whatever bytes lie there, NaN in these
cases)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import kimi_linear as kl
from ray_tpu.ops import kda

LAYERS, LAYER, SLOTS, H, D = 3, 1, 5, 4, 128
IDLE, FRESH = (0, 2), 3
TOL = 1e-6


def _rel(have, want):
    return float(np.abs(np.asarray(have) - np.asarray(want)).max() / np.abs(np.asarray(want)).max())


def _inputs(seed, steps=1, rho=0.0, beta_near_one=False):
    """``(q, k, v, g, beta)`` of ``steps`` decode steps, ``[steps, SLOTS, H,
    .]`` as the mixer hands them over: q and k normalised a head, ``g <= 0``,
    and on the slots nobody holds ``beta = 0`` and ``g = 0``. ``rho``: the
    cosine between the keys of a head over the steps."""
    rng = np.random.default_rng(seed)

    def unit(a):
        return a / np.linalg.norm(a, axis=-1, keepdims=True)

    common = unit(rng.standard_normal((1, SLOTS, H, D)))
    k = unit(np.sqrt(rho) * common + np.sqrt(1 - rho) * unit(rng.standard_normal((steps, SLOTS, H, D))))
    q = unit(rng.standard_normal((steps, SLOTS, H, D))) * D ** -0.5
    v = rng.standard_normal((steps, SLOTS, H, D))
    g = -np.exp(rng.normal(-2.0, 1.5, (steps, SLOTS, H, D)))
    beta = rng.uniform(0.9, 1.0, (steps, SLOTS, H)) if beta_near_one else rng.uniform(0.05, 0.95, (steps, SLOTS, H))
    g[:, IDLE], beta[:, IDLE] = 0.0, 0.0
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


def _pool(seed):
    pool = np.random.default_rng([seed, 1]).standard_normal((LAYERS, SLOTS, H, D, D)).astype(np.float32)
    pool[:, FRESH] = np.nan  # what the slot's last holder left: never read
    return jnp.asarray(pool)


def _fresh():
    return jnp.zeros((SLOTS,), bool).at[FRESH].set(True)


def _reference(slab, fresh, *step):
    return kl.kda_update(jnp.where(fresh[:, None, None, None], 0.0, slab), *step)


def test_the_kernel_serves_whole_lanes_in_float32_on_a_tpu_and_nothing_else():
    pool = jax.ShapeDtypeStruct((20, 65, 32, 128, 128), jnp.float32)
    assert kda.kernel_serves(pool, backend="tpu")
    assert not kda.kernel_serves(pool, backend="cpu") and not kda.kernel_serves(pool)  # the tests run on the CPU
    for shape, dtype in (((5, 4, 4, 16, 16), jnp.float32), ((20, 65, 32, 128, 64), jnp.float32),
                         ((20, 65, 32, 128, 128), jnp.bfloat16), ((65, 32, 128, 128), jnp.float32)):
        assert not kda.kernel_serves(jax.ShapeDtypeStruct(shape, dtype), backend="tpu")
    assert [kda._head_block(*a) for a in ((32, 128, 128), (4, 128, 128), (32, 256, 256), (12, 128, 128), (64, 128, 128))] == [16, 4, 4, 12, 16]
    with pytest.raises(ValueError, match="does not serve"):
        kda.update(_pool(0), LAYER, *(a[0] for a in _inputs(0)), _fresh(), head_block=3)


def test_one_step_is_kda_update_on_the_layers_slab_and_touches_nothing_else():
    pool, fresh = _pool(0), _fresh()
    q, k, v, g, beta = (a[0] for a in _inputs(0))
    out, o = kda.update(pool, LAYER, q, k, v, g, beta, fresh)
    S_want, o_want = _reference(pool[LAYER], fresh, q, k, v, g, beta)
    assert np.isfinite(np.asarray(out[LAYER])).all() and np.isfinite(np.asarray(o)).all()
    assert _rel(out[LAYER], S_want) < TOL and _rel(o, o_want) < TOL
    # a fresh slot: zeros decayed and updated, whatever lay there
    np.testing.assert_allclose(
        np.asarray(out[LAYER, FRESH]), np.asarray(beta[FRESH, :, None, None] * k[FRESH, :, :, None] * v[FRESH, :, None, :]),
        rtol=1e-6, atol=1e-7,
    )
    # a slot nobody holds and the null slot come back bit for bit, and so do the other layers' slabs
    have, was = np.asarray(out).view(np.uint32), np.asarray(pool).view(np.uint32)
    for slot in IDLE:
        assert (have[LAYER, slot] == was[LAYER, slot]).all()
    for layer in (0, 2):
        assert (have[layer] == was[layer]).all()


@pytest.mark.parametrize("hb", [1, 2, 4])
def test_24_steps_in_a_row_are_the_recurrence(hb):
    steps, fresh = 24, _fresh()
    pool, window = _pool(1), _inputs(1, steps)
    S = pool[LAYER]
    for t in range(steps):
        step = tuple(a[t] for a in window)
        pool, o = kda.update(pool, LAYER, *step, fresh if t == 0 else jnp.zeros_like(fresh), head_block=hb)
        S, o_want = _reference(S, fresh if t == 0 else jnp.zeros_like(fresh), *step)
        assert _rel(o, o_want) < 4 * TOL, t
    assert _rel(pool[LAYER], S) < 4 * TOL
    assert (np.asarray(pool[LAYER, 0]) == np.asarray(_pool(1)[LAYER, 0])).all()


@pytest.mark.parametrize("rho", [0.8, 0.95])
def test_keys_of_a_head_alike_and_beta_near_one(rho):
    """The case that broke the chunked form (PR 35: a float32 inverse that
    cancelled to nothing): the recurrence has no inverse, and the kernel is
    the recurrence."""
    steps, none = 24, jnp.zeros((SLOTS,), bool)
    window = _inputs(2, steps, rho=rho, beta_near_one=True)
    pool = jnp.zeros((LAYERS, SLOTS, H, D, D), jnp.float32)
    S = pool[LAYER]
    for t in range(steps):
        step = tuple(a[t] for a in window)
        pool, o = kda.update(pool, LAYER, *step, none)
        S, o_want = kl.kda_update(S, *step)
        assert _rel(o, o_want) < 4 * TOL, t
    assert _rel(pool[LAYER], S) < 4 * TOL and float(jnp.abs(S).max()) < 10.0


@pytest.mark.parametrize("dk, dv", [(256, 128), (128, 256)])
def test_other_widths_of_whole_lanes(dk, dv):
    rng = np.random.default_rng(4)
    f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)  # noqa: E731
    pool, fresh = f32(2, 3, 2, dk, dv), jnp.asarray([False, True, False])
    q, k, v, g, beta = f32(3, 2, dk) * dk ** -0.5, f32(3, 2, dk) * dk ** -0.5, f32(3, 2, dv), -jnp.abs(f32(3, 2, dk)), jax.nn.sigmoid(f32(3, 2))
    out, o = kda.update(pool, 0, q, k, v, g, beta, fresh)
    S_want, o_want = _reference(pool[0], fresh, q, k, v, g, beta)
    assert _rel(out[0], S_want) < TOL and _rel(o, o_want) < TOL and (np.asarray(out[1]) == np.asarray(pool[1])).all()
