"""The diagonal SELECTIVE state-space recurrence of a Mamba-1 mixer, served:
one scalar state a (state index ``n``, channel ``d``) pair, whose decay, input
gate and read-out are all functions of the token::

    h_t[n, d] = exp(dt_t[d] * A[n, d]) * h_{t-1}[n, d] + dt_t[d] * x_t[d] * B_t[n]
    y_t[d]    = sum_n h_t[n, d] * C_t[n]

There is no matmul form: every pair decays at its own rate. All of it runs in
float32 on the vector and transcendental units.

What a sequence leaves behind is ``h``, stored in the state pool as ``[N, G,
lanes]`` (:func:`state_shape`): the channels of ONE state index fill whole
``(8, 128)`` registers (``G = d_inner / 128`` sublane rows of 128 lanes), so
that ``B_t[n]`` and ``C_t[n]`` are SCALARS to a register (read from SMEM) and
``dt_t``, ``x_t`` are whole registers of channels: the update is five
multiply-adds and an exponential a register of state, with no broadcast along
sublanes or lanes and no reduction across them (``y`` is a sum of registers).
As published, ``[d_inner, N]``, the device would pad 16 lanes to 128.

Two entry points over the donated pool ``[layers, slots, N, G, lanes]``, each
a Pallas TPU kernel where :func:`kernel_serves` and the same lines in ``jnp``
elsewhere (the CPU, the tests' toy widths):

* :func:`chunk`: a PREFILL CHUNK of ``C`` positions of ONE sequence from its
  slot's state (zeros where ``fresh``), the state after the chunk written back
  in place. Kernel ``ssm_scan``: a grid over (block of 1024 channels, block of
  positions); a block of channels' state, 16 registers, is carried through
  the chunk's positions in registers; ``dt``, ``x`` are read once and ``y``
  written once: no ``[C, N, d_inner]`` array exists anywhere. A padded row
  must not advance the state: the caller hands ``dt = 0`` there
  (``exp(0) = 1`` and nothing added).
* :func:`step`: a DECODE BATCH, one position a slot, ONE read and ONE write of
  each named slot's state where it lies. Kernel ``ssm_update``: a grid over
  the batch's rows, the row's slot scalar-prefetched into the block index map
  (as ``ops/kda.py`` reads its layer), the pool aliased in and out. Padding
  rows name the null slot 0, where colliding writes are trash on trash.

The layer is an operand (scalar-prefetched) and each call is jitted by
itself: a model's 26 calls are one traced and lowered kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
LANES = 128
#: sublane rows of channels a grid step of ``ssm_scan`` carries: one register a state index
_ROWS = 8
#: positions a grid step of ``ssm_scan`` brings in: dt, x, y blocks of 1 MB
_SCAN_POSITIONS = 256


def state_shape(d_state: int, d_inner: int):
    """The shape one sequence's ``h`` of one layer is stored in: ``[N, G,
    lanes]``, ``d_inner = G x lanes`` channels in order (``lanes`` 128, or all
    of a toy width that is no multiple of it)."""
    lanes = LANES if d_inner % LANES == 0 else d_inner
    return (d_state, d_inner // lanes, lanes)


def kernel_serves(pool, backend: str | None = None) -> bool:
    """Whether :func:`chunk` and :func:`step` run the kernels over ``pool``
    (anything with the shape and dtype of ``[layers, slots, N, G, lanes]``): on
    a TPU, a float32 pool whose channels are whole ``(8, 128)`` registers a
    state index (``d_inner`` a multiple of 1024). Everything else keeps the
    ``jnp`` form. Decided at trace time; the model's ``attention_path`` asks
    the same question to say what a launch runs."""
    backend = backend or jax.default_backend()
    if backend != "tpu" or len(pool.shape) != 5:
        return False
    _, _, _, G, lanes = pool.shape
    return pool.dtype == F32 and lanes == LANES and G % _ROWS == 0


# ---------------------------------------------------------------------------
# the plain form


def scan_positions(h, dt, x, Bm, Cm, A):
    """The recurrence over ``T`` positions of one sequence from ``h [N, D]``:
    ``dt``, ``x [T, D]``, ``Bm``, ``Cm [T, N]``, ``A [N, D]``, all float32 ->
    ``(y [T, D], h)``. One ``lax.scan`` step a position."""

    def body(h, at):
        dt_t, x_t, b_t, c_t = at
        h = jnp.exp(dt_t[None] * A) * h + (dt_t * x_t)[None] * b_t[:, None]
        return h, (h * c_t[:, None]).sum(axis=0)

    h, y = jax.lax.scan(body, h, (dt, x, Bm, Cm))
    return y, h


def advance(h, dt, x, Bm, Cm, A):
    """ONE position of a batch: ``h [B, N, D]``, ``dt``, ``x [B, D]``,
    ``Bm``, ``Cm [B, N]`` -> ``(y [B, D], h)``."""
    h = jnp.exp(dt[:, None] * A) * h + (dt * x)[:, None] * Bm[:, :, None]
    return (h * Cm[:, :, None]).sum(axis=1), h


# ---------------------------------------------------------------------------
# the kernels


def _scan_kernel(
    layer_ref, slot_ref,  # SMEM [1] int32 each (the index maps read them)
    fresh_ref,  # SMEM [1] int32: the sequence starts here, its state reads as zeros
    b_ref, c_ref,  # SMEM [C * N] float32: B_t[n], C_t[n] at t * N + n
    dt_ref, x_ref,  # VMEM [T, 8, 128]: a block of positions of a block of channels
    a_ref,  # VMEM [N, 8, 128]
    h_in,  # VMEM [N, 8, 128]: the slot's state as it lies in the pool
    y_ref,  # VMEM [T, 8, 128]
    h_out,  # VMEM [N, 8, 128]: the same place, resident over the chunk's positions
):
    from jax.experimental import pallas as pl

    del layer_ref, slot_ref
    N, T = a_ref.shape[0], dt_ref.shape[0]
    t_blk = pl.program_id(1)

    @pl.when(t_blk == 0)
    def _():
        fresh = jnp.full(h_in.shape, fresh_ref[0], jnp.int32) != 0
        h_out[...] = jnp.where(fresh, 0.0, h_in[...])

    A = [a_ref[n] for n in range(N)]
    base = t_blk * (T * N)

    def position(t, h):
        dt = dt_ref[t]
        dtx = dt * x_ref[t]
        at = base + t * N
        y = jnp.zeros_like(dt)
        new = []
        for n in range(N):
            hn = jnp.exp(dt * A[n]) * h[n] + dtx * b_ref[at + n]
            y = y + hn * c_ref[at + n]
            new.append(hn)
        y_ref[t] = y
        return tuple(new)

    h = jax.lax.fori_loop(0, T, position, tuple(h_out[n] for n in range(N)))
    for n in range(N):
        h_out[n] = h[n]


@functools.partial(jax.jit, static_argnames=("positions", "interpret"))
def _scan_call(pool, layer, slot, fresh, dt, x, Bm, Cm, A, *, positions, interpret):
    # imported here, as ops/paged_attention.py does: a second of import that
    # only a process which runs the kernel pays
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, _, N, G, lanes = pool.shape
    C, T = dt.shape[0], positions
    dt, x = (a.astype(F32).reshape(C, G, lanes) for a in (dt, x))
    rows = pl.BlockSpec((T, _ROWS, lanes), lambda j, t, *_: (t, j, 0))
    tile = pl.BlockSpec(
        (None, None, N, _ROWS, lanes), lambda j, t, layer, slot, *_: (layer[0], slot[0], 0, j, 0)
    )
    y, pool = pl.pallas_call(
        _scan_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(G // _ROWS, C // T),
            in_specs=[rows, rows, pl.BlockSpec((N, _ROWS, lanes), lambda j, t, *_: (0, j, 0)), tile],
            out_specs=[rows, tile],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((C, G, lanes), F32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operand 8 (after the five prefetched scalars and dt, x, A) is the pool
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        name="ssm_scan",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(
        layer.reshape(1), slot.reshape(1), fresh.astype(jnp.int32).reshape(1),
        Bm.astype(F32).reshape(-1), Cm.astype(F32).reshape(-1),
        dt, x, A.astype(F32).reshape(N, G, lanes), pool,
    )
    return y.reshape(C, G * lanes), pool


def _update_kernel(
    layer_ref, slots_ref,  # SMEM [1], [B] int32 (the index maps read them)
    fresh_ref,  # SMEM [B] int32
    b_ref, c_ref,  # SMEM [B * N] float32
    dt_ref, x_ref,  # VMEM [G, 128]: the row's channels
    a_ref,  # VMEM [N, G, 128]
    h_in,  # VMEM [N, G, 128]: the row's slot as it lies in the pool
    y_ref,  # VMEM [G, 128]
    h_out,  # VMEM [N, G, 128]: the same place
):
    from jax.experimental import pallas as pl

    del layer_ref, slots_ref
    N, G, lanes = a_ref.shape
    b = pl.program_id(0)
    fresh = jnp.full((_ROWS, lanes), fresh_ref[b], jnp.int32) != 0
    for g in range(0, G, _ROWS):
        rows = slice(g, g + _ROWS)
        dt = dt_ref[rows, :]
        dtx = dt * x_ref[rows, :]
        y = jnp.zeros_like(dt)
        for n in range(N):
            h = jnp.where(fresh, 0.0, h_in[n, rows, :])
            h = jnp.exp(dt * a_ref[n, rows, :]) * h + dtx * b_ref[b * N + n]
            h_out[n, rows, :] = h
            y = y + h * c_ref[b * N + n]
        y_ref[rows, :] = y


@functools.partial(jax.jit, static_argnames=("interpret",))
def _update_call(pool, layer, slots, fresh, dt, x, Bm, Cm, A, *, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _, _, N, G, lanes = pool.shape
    B = dt.shape[0]
    dt, x = (a.astype(F32).reshape(B, G, lanes) for a in (dt, x))
    row = pl.BlockSpec((None, G, lanes), lambda b, *_: (b, 0, 0))
    tile = pl.BlockSpec(
        (None, None, N, G, lanes), lambda b, layer, slots, *_: (layer[0], slots[b], 0, 0, 0)
    )
    y, pool = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B,),
            in_specs=[row, row, pl.BlockSpec((N, G, lanes), lambda b, *_: (0, 0, 0)), tile],
            out_specs=[row, tile],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, G, lanes), F32),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        input_output_aliases={8: 1},
        # in order: two rows of a batch may name one slot (the null slot's padding)
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="ssm_update",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(
        layer.reshape(1), slots.astype(jnp.int32), fresh.astype(jnp.int32),
        Bm.astype(F32).reshape(-1), Cm.astype(F32).reshape(-1),
        dt, x, A.astype(F32).reshape(N, G, lanes), pool,
    )
    return y.reshape(B, G * lanes), pool


# ---------------------------------------------------------------------------
# the two entry points


def _use_kernel(pool, kernel) -> bool:
    return kernel_serves(pool) if kernel is None else bool(kernel)


def _interpreted(interpret) -> bool:
    return jax.default_backend() != "tpu" if interpret is None else bool(interpret)


def chunk(pool, layer, slot, fresh, dt, x, Bm, Cm, A, *, kernel=None, interpret=None):
    """A prefill chunk of ONE sequence over a layer's slab of the donated
    pool ``[layers, slots, N, G, lanes]`` float32: ``layer``, ``slot`` int32
    scalars, ``fresh`` (the sequence starts here: the slot reads as zeros
    whatever it held), ``dt``, ``x [C, d_inner]``, ``Bm``, ``Cm [C, N]``, ``A
    [N, d_inner]``, float32. A row with ``dt = 0`` leaves the state as it was
    (the caller masks the padded rows so). Returns ``(y [C, d_inner] float32,
    pool)`` with the slot's state after the chunk written where it lay.

    ``kernel``: force (True) or forbid (False) the Pallas kernel (default:
    :func:`kernel_serves`); ``interpret``: run it in Pallas' TPU interpreter
    (what the CPU tests do); by default wherever the backend is not a TPU."""
    _, _, N, G, lanes = pool.shape
    layer, slot = jnp.asarray(layer, jnp.int32), jnp.asarray(slot, jnp.int32)
    if _use_kernel(pool, kernel):
        C = dt.shape[0]
        positions = min(C, _SCAN_POSITIONS)
        if C % positions:
            raise ValueError(f"a chunk of {C} positions is no whole block of {positions}")
        return _scan_call(pool, layer, slot, jnp.asarray(fresh), dt, x, Bm, Cm, A,
                          positions=positions, interpret=_interpreted(interpret))
    at = (layer, slot, jnp.int32(0), jnp.int32(0), jnp.int32(0))
    h = jax.lax.dynamic_slice(pool, at, (1, 1, N, G, lanes)).reshape(N, G * lanes)
    h = jnp.where(fresh, 0.0, h)
    y, h = scan_positions(h, dt.astype(F32), x.astype(F32), Bm.astype(F32), Cm.astype(F32), A.astype(F32))
    return y, jax.lax.dynamic_update_slice(pool, h.reshape(1, 1, N, G, lanes), at)


def step(pool, layer, slots, fresh, dt, x, Bm, Cm, A, *, kernel=None, interpret=None):
    """One position a slot of a decode batch, in place in a layer's slab of
    the donated pool: ``slots [B]`` the rows' slots (padding on the null slot
    0), ``fresh [B]``, ``dt``, ``x [B, d_inner]``, ``Bm``, ``Cm [B, N]``, ``A
    [N, d_inner]``. Returns ``(y [B, d_inner] float32, pool)``; reads and
    writes the ``B`` named slots of the slab and no other. ``kernel``,
    ``interpret``: as :func:`chunk`."""
    if _use_kernel(pool, kernel):
        return _update_call(pool, jnp.asarray(layer, jnp.int32), slots, fresh, dt, x, Bm, Cm, A,
                            interpret=_interpreted(interpret))
    _, _, N, G, lanes = pool.shape
    B = dt.shape[0]
    h = jnp.where(fresh[:, None, None], 0.0, pool[layer, slots].reshape(B, N, G * lanes))
    y, h = advance(h, dt.astype(F32), x.astype(F32), Bm.astype(F32), Cm.astype(F32), A.astype(F32))
    return y, pool.at[layer, slots].set(h.reshape(B, N, G, lanes))
