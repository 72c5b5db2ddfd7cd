"""The decode update of a delta-rule layer's slab of the state pool, in ONE pass:
a Pallas TPU kernel that reads each slot's matrix state, decays it, applies the
rank-one update, reads the output off it and writes it back where it lies. ONE
kernel file for both models that recur by the gated delta rule: Kimi-Linear's
KDA (``models/kimi_linear.py``: a gate a channel, a state of 128 x 128 a head)
and Olmo-Hybrid's Gated DeltaNet (``models/olmo_hybrid.py``: ONE gate a head, a
state of 96 x 192 a head).

``ops/delta_rule.py::kda_update`` (where the recurrence lives since PR 64) is
the same four lines in ``jnp``: ``u`` needs the whole decayed state of a head
before the update can start and ``o`` the updated one, so XLA makes three
passes over the layer's slab where the bytes that must move are one read and
one write. A head's state is 64-72 KB: it fits VMEM many times over, and all
four lines run on one read.

TWO FORMS of the pool, each aliased in and out, only the layer's slab ever
touched, a grid over (slot, block of ``hb`` heads), the tile brought in and
taken back by Pallas' own double buffering, everything in float32 on the vector
unit as ``kda_update`` promises (nothing of the state goes through a bfloat16
product):

* ``[n, slots, H, dk, dv]``, ``dk`` and ``dv`` whole lanes of 128 (KDA): the
  tile ``[hb, dk, dv]``, a head at a time (:func:`_kernel`; its lowered text at
  128 x 128 is what it was before the second form: ``tests/test_delta_rule.py``).
* heads JOINED along the lanes, ``[n, slots, dk, H x dv]`` (Gated DeltaNet: 192
  lanes a head fill a tile and a half; 30 heads side by side are 5760 = 45 x
  128, nothing padded): the tile ``[dk, hb x dv]``, a GROUP of heads that is
  whole lanes at a time (a pair of 192 = 3 tiles), a lane's ``k``, ``q``,
  ``beta`` and ``e^g`` chosen by the lane's head (:func:`_joined_kernel`). The
  gate is ONE number a head and arrives, as ``e^g``, with ``beta`` in SMEM.

The two forms of a 96 x 192 state were timed ON THE CHIP (PERF.md, PR 64: a
layer's call over 65 slots x 30 heads, 32 calls in one device loop as PR 57
timed, a v5e; 287.5 MB must move, 0.351 ms at 819 GB/s): JOINED 0.495 ms at 10
heads a step (71% of the HBM roofline; 0.518 at 6, 0.828 at 2, 0.496 at 30),
the padded 5-d form ``[.., 30, 96, 192]`` through this file's first kernel with
the gate broadcast 0.635 / 0.628 ms at 10 / 15 heads a step (it moves 4 / 3 of
the bytes: 192 lanes are stored as 256), ``kda_update``'s ``jnp`` 1.14 ms over
the padded form and 3.04 over the joined one (two transposes of the slab). The
joined form was kept; the padded one is not in the code.

The sums over ``dk`` run along the tile's SUBLANE axis, so ``k``, ``q`` (and a
channel's ``e^g``) of a head must lie along sublanes too, broadcast over the
lanes. They arrive as rows (``[hb, dk]``: lanes; ``dk`` padded to whole lanes
where it is not); those of a block of heads are stacked to ONE ``[128, dk]``
matrix and transposed once a grid step, and a head's column is a static lane
of that.

The layer is an operand (scalar-prefetched: the block index map reads it),
and the call is jitted by itself: a model's 20 (12) calls are one traced and
lowered kernel. Both forms' device operations are named ``kda_update.N``.
"""

from __future__ import annotations

import functools
import math

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


def kernel_serves(state, backend: str | None = None, heads: int | None = None) -> bool:
    """Whether :func:`update` runs a kernel over ``state`` (anything with the
    shape and dtype of the pool), on a TPU, a float32 pool, in one of the TWO
    forms a state is stored in:

    * ``[n, slots, H, dk, dv]`` whose ``dk`` and ``dv`` are whole lanes of 128
      (KDA's 128 x 128: a head's state is whole tiles as it lies);
    * heads JOINED along the lanes, ``[n, slots, dk, H * dv]`` with ``heads``
      said beside it, for a state whose ``dv`` is no whole lane (Gated
      DeltaNet's 96 x 192: as ``[.., 96, 192]`` the device stores 192 lanes as
      256, a third more bytes to hold, read and write; 30 heads side by side
      are 5760 = 45 x 128 lanes and nothing is padded): ``dk`` whole sublanes of
      8 and a block of heads that is whole lanes (:func:`_joined_block`).

    Everything else (the CPU, the tests' toy widths, a 5-d pool of 16 x 16 or
    128 x 64) keeps ``kda_update``. Decided at trace time; the model's
    ``attention_path`` asks the same question to say what a launch runs."""
    backend = backend or jax.default_backend()
    if backend != "tpu" or state.dtype != jnp.float32:
        return False
    if len(state.shape) == 5:
        dk, dv = state.shape[3:]
        return dk % 128 == 0 and dv % 128 == 0
    if len(state.shape) == 4 and heads:
        dk, width = state.shape[2:]
        return width % heads == 0 and dk % 8 == 0 and _joined_block(heads, dk, width // heads) > 0
    return False


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


def heads_apart(S, heads: int):
    """A state in the joined form ``[.., dk, H x dv]`` seen a head at a time, ``[.., H, dk, dv]``."""
    return jnp.swapaxes(S.reshape(*S.shape[:-1], heads, S.shape[-1] // heads), -3, -2)


def heads_joined(S):
    """``[.., H, dk, dv]`` -> the joined form ``[.., dk, H x dv]``."""
    S = jnp.swapaxes(S, -3, -2)
    return S.reshape(*S.shape[:-2], -1)


def _joined_block(n_heads: int, dk: int, dv: int) -> int:
    """Heads a grid step of the joined form: the largest number that divides
    ``H``, is whole GROUPS (the fewest heads whose ``dv`` fill whole lanes: a
    pair at 192), fills no more than ``_TILE_BYTES`` and whose q and k columns
    fit the stacked matrix; 0 where there is none."""
    group = math.lcm(dv, 128) // dv
    most = min(_STACK_ROWS // 2, _TILE_BYTES // (4 * dk * dv), n_heads)
    return max((hb for hb in range(group, most + 1, group) if n_heads % hb == 0), default=0)


def _joined_kernel(
    layer_ref,  # SMEM [1] int32 (the index maps read it)
    fresh_ref,  # SMEM [slots] int32
    beta_ref, decay_ref,  # SMEM [slots * H] float32: the step's size and e^g, ONE number a head
    q_ref, k_ref,  # VMEM [hb, dk padded to whole lanes]
    v_ref,  # VMEM [1, hb * dv]: the block's heads side by side
    s_ref,  # VMEM [dk, hb * dv]: the tile as it lies in the pool
    s_out,  # VMEM [dk, hb * dv]: the same place
    o_ref,  # VMEM [1, hb * dv]
    *,
    dv: int,
):
    """The four lines of ``kda_update`` over a block of heads that lie side by
    side along the lanes, a GROUP of heads (whole lanes: 384 = 3 tiles for a
    pair of 192) at a time: a lane's ``k``, ``q``, ``beta`` and ``e^g`` are its
    head's, chosen by the lane's index, so every operation runs over whole
    tiles and no slice starts inside one."""
    from jax.experimental import pallas as pl

    del layer_ref
    dk, width = s_ref.shape
    hb, group = width // dv, math.lcm(dv, 128) // dv
    span = group * dv
    slot, blk = pl.program_id(0), pl.program_id(1)
    n_heads = hb * pl.num_programs(1)
    # q and k of the block's heads as COLUMNS: one transpose a grid step
    rows = [q_ref[...], k_ref[...]]
    if 2 * hb < _STACK_ROWS:
        rows.append(jnp.zeros((_STACK_ROWS - 2 * hb, q_ref.shape[1]), jnp.float32))
    cols = jnp.concatenate(rows, axis=0).T[:dk]  # [dk, 128]
    head_of = jax.lax.broadcasted_iota(jnp.int32, (dk, span), 1) // dv
    head_of_row = jax.lax.broadcasted_iota(jnp.int32, (1, span), 1) // dv
    fresh = jnp.full((dk, span), fresh_ref[slot], jnp.int32) != 0
    for p in range(hb // group):
        h0 = p * group
        at = slot * n_heads + blk * hb + h0
        q = jnp.broadcast_to(cols[:, h0 : h0 + 1], (dk, span))
        k = jnp.broadcast_to(cols[:, hb + h0 : hb + h0 + 1], (dk, span))
        beta = jnp.full((1, span), beta_ref[at], jnp.float32)
        decay = jnp.full((1, span), decay_ref[at], jnp.float32)
        for j in range(1, group):
            q = jnp.where(head_of == j, cols[:, h0 + j : h0 + j + 1], q)
            k = jnp.where(head_of == j, cols[:, hb + h0 + j : hb + h0 + j + 1], k)
            beta = jnp.where(head_of_row == j, beta_ref[at + j], beta)
            decay = jnp.where(head_of_row == j, decay_ref[at + j], decay)
        lanes = slice(p * span, (p + 1) * span)
        S = jnp.where(fresh, 0.0, s_ref[:, lanes]) * decay
        u = jnp.sum(S * k, axis=0, keepdims=True)
        S = S + k * (beta * (v_ref[:, lanes] - u))
        s_out[:, lanes] = S
        o_ref[:, lanes] = jnp.sum(S * q, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("heads", "head_block", "interpret"))
def _call_joined(state, layer, q, k, v, g, beta, fresh, *, heads, head_block, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, n_slots, dk, width = state.shape
    H, hb, dv = heads, head_block, width // heads
    lanes_k = -(-dk // 128) * 128  # the stacked matrix is transposed in whole tiles

    def rows(a):  # [slots, H, dk] -> [slots, H / hb, hb, dk padded]
        a = jnp.pad(a.astype(jnp.float32), ((0, 0), (0, 0), (0, lanes_k - dk)))
        return a.reshape(n_slots, H // hb, hb, lanes_k)

    of_heads = pl.BlockSpec((None, None, hb, lanes_k), lambda s, j, *_: (s, j, 0, 0))
    side_by_side = pl.BlockSpec((None, 1, hb * dv), lambda s, j, *_: (s, 0, j))
    tile = pl.BlockSpec((None, None, dk, hb * dv), lambda s, j, layer, *_: (layer[0], s, 0, j))
    state, o = pl.pallas_call(
        functools.partial(_joined_kernel, dv=dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_slots, H // hb),
            in_specs=[of_heads, of_heads, side_by_side, tile],
            out_specs=[tile, side_by_side],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(state.shape, state.dtype),
            jax.ShapeDtypeStruct((n_slots, 1, H * dv), jnp.float32),
        ],
        # operand 7 (after the four prefetched scalars and q, k, v) is the pool
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        name="kda_update",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(
        layer.reshape(1), fresh.astype(jnp.int32), beta.astype(jnp.float32).reshape(-1),
        jnp.exp(g.astype(jnp.float32)).reshape(-1),
        rows(q), rows(k), v.astype(jnp.float32).reshape(n_slots, 1, H * dv), state,
    )
    return state, o.reshape(n_slots, H, dv)


def update(state, layer, q, k, v, g, beta, fresh, *, head_block=None, interpret=None):
    """One decode step of one delta-rule layer over the WHOLE pool's slots:
    ``state`` float32 in one of the two forms :func:`kernel_serves` names, ``[n,
    slots, H, dk, dv]`` or heads joined along the lanes ``[n, slots, dk, H *
    dv]`` (donated: the layer's slab is updated where it lies, the other slabs
    are neither read nor written), ``layer`` the slab's index, and for each slot
    ``q, k [slots, H, dk]``, ``v [slots, H, dv]``, ``beta [slots, H]``, the gate
    ``g [slots, H, dk]`` a channel (the 5-d form) or ``g [slots, H]`` a head (the
    joined form), ``fresh [slots]`` (the slot's state reads as zeros whatever
    bytes lie there). For every slot and head, as ``ops/delta_rule.py::
    kda_update``: ``S = S e^g; u = sum_k S k; S += (beta k)(v - u)^T; o = sum_k
    S q``. A slot with ``beta = 0`` and ``g = 0`` keeps its state. Returns
    ``(state, o [slots, H, dv])``.

    ``head_block``: heads a grid step (default: the most that divide ``H``
    within a tile of 1 MB, 16 at 128 x 128, 10 at 96 x 192).
    ``interpret``: run the kernel in Pallas' TPU interpreter (what the CPU
    tests do); by default wherever the backend is not a TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if state.ndim == 4:
        H, dk = q.shape[1:]
        dv = state.shape[3] // H
        hb = head_block or _joined_block(H, dk, dv)
        if not hb or H % hb or (hb * dv) % 128 or 2 * hb > _STACK_ROWS:
            raise ValueError(f"a block of {hb} heads of {dk} x {dv} does not serve {H} heads joined along the lanes")
        return _call_joined(
            state, jnp.asarray(layer, jnp.int32), q, k, v, g, beta, fresh,
            heads=H, head_block=hb, interpret=bool(interpret),
        )
    H, dk, dv = state.shape[2:]
    hb = head_block or _head_block(H, dk, dv)
    if H % hb or 3 * hb > _STACK_ROWS:
        raise ValueError(f"a block of {hb} heads does not serve {H} heads")
    return _call(
        state, jnp.asarray(layer, jnp.int32), q, k, v, g, beta, fresh,
        head_block=hb, interpret=bool(interpret),
    )
