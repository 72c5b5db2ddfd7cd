"""The decode update of a KDA layer's slab of the state pool, in ONE pass: a
Pallas TPU kernel that reads each slot's matrix state, decays it, applies the
rank-one update, reads the output off it and writes it back where it lies.

``models/kimi_linear.py::kda_update`` is the same four lines in ``jnp``: ``u``
needs the whole decayed state of a head before the update can start and ``o``
the updated one, so XLA makes three passes over the layer's slab where the
bytes that must move are one read and one write. A head's state is 64 KB: it
fits VMEM many times over, and all four lines run on one read.

The pool ``[n_kda, slots, H, dk, dv]`` float32 is aliased in and out and only
the layer's slab is ever touched: a grid over (slot, block of ``hb`` heads),
the tile ``[hb, dk, dv]`` brought in and taken back by Pallas' own double
buffering. Everything in float32 on the vector unit, as ``kda_update``
promises: nothing of the state goes through a bfloat16 product.

The sums over ``dk`` run along the tile's SUBLANE axis, so ``k``, ``q`` and
``e^g`` of a head must lie along sublanes too, broadcast over the lanes. They
arrive as rows (``[hb, dk]``: lanes); the three of a block of heads are
stacked to ONE ``[128, dk]`` matrix and transposed once a grid step, and a
head's column is a static lane of that.

The layer is an operand (scalar-prefetched: the block index map reads it),
and the call is jitted by itself: a model's 20 calls are one traced and
lowered kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: rows of the stacked matrix that is transposed a grid step: q, k and e^g of
#: a block's heads, so a block is at most 42 heads
_STACK_ROWS = 128

#: bytes of the state a grid step brings in (and takes back): 16 heads of 128 x
#: 128, double-buffered 4 MB of VMEM. On the chip, 65 slots x 32 heads
#: (PERF.md, PR 46): 0.744 ms a layer at 2 heads a step, 0.542 at 4, 0.451 at
#: 8, 0.431 at 16 and at 32
_TILE_BYTES = 1 << 20


def kernel_serves(state, backend: str | None = None) -> bool:
    """Whether :func:`update` runs the kernel over ``state`` (anything with
    the shape and dtype of the pool ``[n_kda, slots, H, dk, dv]``): on a TPU,
    a float32 pool whose ``dk`` and ``dv`` are whole lanes of 128. Everything
    else (the CPU, the tests' toy widths) keeps ``kda_update``. Decided at
    trace time; the model's ``attention_path`` asks the same question to say
    what a launch runs."""
    backend = backend or jax.default_backend()
    if backend != "tpu" or len(state.shape) != 5:
        return False
    dk, dv = state.shape[3:]
    return state.dtype == jnp.float32 and dk % 128 == 0 and dv % 128 == 0


def _head_block(n_heads: int, dk: int, dv: int) -> int:
    """The largest block of heads that divides ``H``, fills no more than
    ``_TILE_BYTES`` and whose columns fit the stacked matrix."""
    most = max(1, min(_STACK_ROWS // 3, _TILE_BYTES // (4 * dk * dv), n_heads))
    return max(hb for hb in range(1, most + 1) if n_heads % hb == 0)


def _kernel(
    layer_ref,  # SMEM [1] int32 (the index maps read it)
    fresh_ref,  # SMEM [slots] int32: the slot's sequence starts here, its state reads as zeros
    beta_ref,  # SMEM [slots * H] float32
    q_ref, k_ref, g_ref,  # VMEM [hb, dk]
    v_ref,  # VMEM [hb, dv]
    s_ref,  # VMEM [hb, dk, dv]: the tile as it lies in the pool
    s_out,  # VMEM [hb, dk, dv]: the same place
    o_ref,  # VMEM [hb, dv]
):
    from jax.experimental import pallas as pl

    del layer_ref
    hb, dk, dv = s_ref.shape
    slot, blk = pl.program_id(0), pl.program_id(1)
    n_heads = hb * pl.num_programs(1)
    # q, k, e^g of the block's heads as COLUMNS: one transpose a grid step
    rows = [q_ref[...], k_ref[...], jnp.exp(g_ref[...])]
    if 3 * hb < _STACK_ROWS:
        rows.append(jnp.zeros((_STACK_ROWS - 3 * hb, dk), jnp.float32))
    cols = jnp.concatenate(rows, axis=0).T  # [dk, 128]
    fresh = jnp.full((dk, dv), fresh_ref[slot], jnp.int32) != 0
    for h in range(hb):
        q, k, e_g = (cols[:, i * hb + h : i * hb + h + 1] for i in range(3))
        beta = beta_ref[slot * n_heads + blk * hb + h]
        S = jnp.where(fresh, 0.0, s_ref[h]) * e_g
        u = jnp.sum(S * k, axis=0, keepdims=True)
        S = S + k * (beta * (v_ref[h : h + 1, :] - u))
        s_out[h] = S
        o_ref[h : h + 1, :] = jnp.sum(S * q, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("head_block", "interpret"))
def _call(state, layer, q, k, v, g, beta, fresh, *, head_block, interpret):
    # imported here, as ops/paged_attention.py does: a second of import that
    # only a process which runs the kernel pays
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, n_slots, H, dk, dv = state.shape
    hb = head_block
    # [slots, H, .] -> [slots, H / hb, hb, .]: a block's last two dimensions are whole
    q, k, g, v = (a.astype(jnp.float32).reshape(n_slots, H // hb, hb, -1) for a in (q, k, g, v))

    def rows(width):
        return pl.BlockSpec((None, None, hb, width), lambda s, j, *_: (s, j, 0, 0))

    tile = pl.BlockSpec((None, None, hb, dk, dv), lambda s, j, layer, *_: (layer[0], s, j, 0, 0))
    state, o = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_slots, H // hb),
            in_specs=[rows(dk), rows(dk), rows(dk), rows(dv), tile],
            out_specs=[tile, rows(dv)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((n_slots, H // hb, hb, dv), jnp.float32),
        ],
        # operand 7 (after the three prefetched scalars and q, k, g, v) is the pool
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        name="kda_update",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(
        layer.reshape(1), fresh.astype(jnp.int32), beta.astype(jnp.float32).reshape(-1),
        q, k, g, v, state,
    )
    return state, o.reshape(n_slots, H, dv)


def update(state, layer, q, k, v, g, beta, fresh, *, head_block=None, interpret=None):
    """One decode step of one KDA layer over the WHOLE pool's slots:
    ``state [n_kda, slots, H, dk, dv]`` float32 (donated: the layer's slab is
    updated where it lies, the other slabs are neither read nor written),
    ``layer`` the slab's index, and for each slot ``q, k, g [slots, H, dk]``,
    ``v [slots, H, dv]``, ``beta [slots, H]``, ``fresh [slots]`` (the slot's
    state reads as zeros whatever bytes lie there). For every slot and head,
    as ``kda_update``: ``S = S e^g; u = sum_k S k; S += (beta k)(v - u)^T; o =
    sum_k S q``. A slot with ``beta = 0`` and ``g = 0`` keeps its state.
    Returns ``(state, o [slots, H, dv])``.

    ``head_block``: heads a grid step (default: the most that divide ``H``
    within a tile of 1 MB, 16 at 128 x 128).
    ``interpret``: run the kernel in Pallas' TPU interpreter (what the CPU
    tests do); by default wherever the backend is not a TPU."""
    H, dk, dv = state.shape[2:]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    hb = head_block or _head_block(H, dk, dv)
    if H % hb or 3 * hb > _STACK_ROWS:
        raise ValueError(f"a block of {hb} heads does not serve {H} heads")
    return _call(
        state, jnp.asarray(layer, jnp.int32), q, k, v, g, beta, fresh,
        head_block=hb, interpret=bool(interpret),
    )
