"""Gated DeltaNet's chunked (WY) form over a prefill chunk as ONE Pallas TPU
kernel: ``ops/delta_rule.py::gdn_chunked``'s mathematics in its precision
(float32 operands, float32 accumulation, the matmul's highest precision),
arranged so that a head's state never leaves VMEM between the sub-chunks.

``gdn_chunked`` is a ``lax.scan`` over the sub-chunks whose body is some 25
dependent XLA operations on ``[H, c, <= dv]`` float32 arrays (the product ``[K;
Q] K^T``, the decay, ``_unit_lower_inverse``'s products and concatenations,
``_wy``'s five products), each a trip through HBM with rows of 96 and 192
lanes that fill no whole tile. What has to be multiplied is 12 MFLOP a head a
sub-chunk and what has to move is the operands once.

The kernel's grid is (a block of ``hb`` heads, the sub-chunks in turn). A grid
step holds one sub-chunk of ``hb`` heads, and for each head:

* what does NOT depend on the state (and so hangs on nothing of the step
  before: the scheduler runs it beside the other heads' dependent products):
  ``Gamma_ts = e^(G_t - G_s)`` for ``s <= t`` from the running sum ``G`` of the
  gate inside the sub-chunk, ``A = ([K; Q] K^T) * Gamma`` in one product, the
  inverse ``T = (I + beta * strict(A^kk))^-1`` BY BLOCKS OF 16 as
  ``delta_rule._unit_lower_inverse`` has it (diagonal blocks by the product
  formula, then neighbours merged; here on whole ``c x c`` matrices under
  block masks, which multiplies the same numbers: a zero adds nothing), ``U =
  T beta (K e^G)`` and ``V~ = T beta V``;
* the three products that do: ``[U; Q e^G] S`` in one, ``W = V~ - U S``, ``o = (Q
  e^G) S + A^qk W``, ``S <- e^(G_last) S + (K e^(G_last - G))^T W``, on the state in
  a VMEM scratch that is read from HBM at the head block's first sub-chunk and
  written back at its last.

THE OPERANDS' FORM. Heads lead (``[H, T, .]``: a block's last two dimensions
are then a sub-chunk's positions and one head's channels, whole as they lie).
``k`` is laid in rows of ``Wk`` lanes, ``dk + 2`` rounded up to whole lanes of 128
(128 at the published 96): the key in the first ``dk``, then ``G_t`` and ``beta_t``
of the row's position as two more lanes, so that the kernel has both as
COLUMNS (what scales a row of ``K``, ``Q`` or ``A``) without a transpose, then
zeros; the kernel masks the key's lanes out of it, and zero lanes leave ``K
K^T`` what it is. ``q`` is padded with zeros to the same width. ``G`` and ``beta``
arrive a second time as ROWS (``[H, n, 8, c]``: what scales a column). The state
scratch is ``[Wk, dv]`` with zero rows behind ``dk``.

:func:`kernel_serves` answers from shapes, dtypes and the backend whether
:func:`chunked` runs; everything else (the CPU, the tests' toy widths, a batch
of several sequences) keeps ``delta_rule.gdn_chunked``, which is also what the
kernel is held against (``tests/test_gdn_chunk_kernel.py``: Pallas' TPU
interpreter). The device operation is named ``gdn_chunk.N``.

ON THE CHIP (a v5e, PERF.md, PR 65): a layer's call over 1024 positions 1.26 ms
where ``gdn_chunked`` takes 1.79, 0.32 against 0.44 over 256, equal to it to 2e-7
and as far from the float64 recurrence as it is. The kernel's static schedule is
2,460 bundles a head a sub-chunk, three quarters of them with work for the
matrix unit: a float32 product is six bfloat16 passes, and ``c = 64`` rows fill
half a pass.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ray_tpu.ops import delta_rule

F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST

_INVERSE_BLOCK = delta_rule._INVERSE_BLOCK

#: heads a grid step where nothing else is said: the largest divisor of ``H`` up
#: to this. On the chip, 30 heads of 96 x 192 over 1024 positions, 32 calls in one
#: device loop (PERF.md, PR 65): 1.313 ms a call at one head a step, 1.280 at 2,
#: 1.264 at 3, 1.261 at 5, 1.256 at 6, 1.253 at 10 (``gdn_chunked``: 1.787): the
#: matrix unit binds every one of them, a step's heads are unrolled code
_HEAD_BLOCK = 5


def kernel_serves(S, q, v, chunk: int, backend: str | None = None) -> bool:
    """Whether :func:`chunked` runs a kernel over such operands (anything with
    their shapes and dtypes): on a TPU, float32, ``T`` whole sub-chunks of
    ``chunk`` positions, ``chunk`` a power of two in whole lanes' halves (64:
    what the inverse by blocks splits evenly and a ``c x c`` matrix's lanes
    hold), ``dk`` and ``dv`` whole sublanes of 8. Decided at trace time."""
    backend = backend or jax.default_backend()
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    return (
        backend == "tpu"
        and all(a.dtype == jnp.float32 for a in (S, q, v))
        and T % chunk == 0
        and chunk % 64 == 0 and chunk & (chunk - 1) == 0
        and dk % 8 == 0 and dv % 8 == 0
        and tuple(S.shape) == (B, H, dk, dv)
    )


def _mm(a, b, contract=((1,), (0,))):
    return jax.lax.dot_general(
        a, b, (contract, ((), ())), precision=_HIGHEST, preferred_element_type=F32
    )


def _unit_lower_inverse(L, row, col):
    """``(I + L)^-1`` of a strictly lower triangular ``L [c, c]`` as
    ``delta_rule._unit_lower_inverse`` takes it, the same products in the same
    order, laid out so that the matrix unit is handed few rows. The diagonal
    blocks of ``b <= 16`` by the product formula ``(I - L)(I + L^2)(I + L^4)...``
    with the blocks SIDE BY SIDE along the lanes (``[b, c]``: one product of
    ``b`` rows against the block-diagonal matrix of the right-hand blocks
    multiplies all of them; a zero adds nothing). Then neighbours merged,
    ``[[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]]``: with ``inv``
    block-diagonal and ``C`` the pairs' lower left blocks, the rows of the pairs'
    second blocks times ``C`` times ``inv`` are ``B^-1 C A^-1`` in each. ``row``,
    ``col``: the matrix's indices ``[c, c]`` int32."""
    c = L.shape[-1]
    b = c
    while b > _INVERSE_BLOCK:
        b //= 2
    block = lambda i, b: i >> (b.bit_length() - 1)  # noqa: E731  i // b, b a power of two
    same = block(row, b) == block(col, b)

    def side_by_side(M):  # the diagonal blocks of [c, c] -> [b, c]
        M = jnp.where(same, M, 0.0)
        return functools.reduce(jnp.add, [M[i * b : (i + 1) * b] for i in range(c // b)])

    def diagonal(P):  # [b, c] -> [c, c], the blocks on the diagonal
        return jnp.where(same, jnp.concatenate([P] * (c // b), axis=0), 0.0)

    X = side_by_side(-L)
    ones = jax.lax.broadcasted_iota(jnp.int32, (b, c), 0) == (jax.lax.broadcasted_iota(jnp.int32, (b, c), 1) & (b - 1))
    inv, power = jnp.where(ones, 1.0, 0.0) + X, X
    for _ in range(max(0, math.ceil(math.log2(b)) - 1)):
        power = _mm(power, diagonal(power))
        inv = inv + _mm(inv, diagonal(power))
    inv = diagonal(inv)
    while b < c:
        pair = block(row, 2 * b) == block(col, 2 * b)
        C = jnp.where(pair & ~same, L, 0.0)
        second = jnp.concatenate([inv[i * b : (i + 1) * b] for i in range(1, c // b, 2)], axis=0)
        low = _mm(_mm(second, C), inv)  # [c / 2, c]: B^-1 C A^-1 of each pair
        zeros = jnp.zeros((b, c), F32)
        inv = inv - jnp.concatenate(
            [part for i in range(c // (2 * b)) for part in (zeros, low[i * b : (i + 1) * b])], axis=0
        )
        same, b = pair, 2 * b
    return inv


def _kernel(
    kx_ref,  # VMEM [hb, c, Wk]: k | G | beta | zeros along the lanes
    q_ref,  # VMEM [hb, c, Wk]: q | zeros
    v_ref,  # VMEM [hb, c, dv]
    rows_ref,  # VMEM [hb, 8, c]: G and beta as rows (the other six unused)
    s_ref,  # VMEM [hb, dk, dv]: the state before the chunk
    o_ref,  # VMEM [hb, c, dv]
    s_out,  # VMEM [hb, dk, dv]: the state after it
    S,  # VMEM scratch [hb, Wk, dv]: the state between the sub-chunks, zero rows behind dk
    *,
    dk: int,
):
    from jax.experimental import pallas as pl

    hb, c, Wk = kx_ref.shape
    j, n = pl.program_id(1), pl.num_programs(1)

    @pl.when(j == 0)
    def _():
        S[...] = jnp.zeros(S.shape, F32)
        S[:, :dk, :] = s_ref[...]

    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, Wk), 1)
    for h in range(hb):
        kx, q, v = kx_ref[h], q_ref[h], v_ref[h]
        G, beta = kx[:, dk : dk + 1], kx[:, dk + 1 : dk + 2]  # [c, 1]
        G_row, beta_row = rows_ref[h, 0:1, :], rows_ref[h, 1:2, :]  # [1, c]
        k = jnp.where(lane < dk, kx, 0.0)
        # nothing from here to P reads the state
        decay = jnp.where(row >= col, jnp.exp(jnp.where(row >= col, G - G_row, 0.0)), 0.0)
        A = _mm(jnp.concatenate([k, q], axis=0), k, ((1,), (1,)))  # ONE product for both
        A_kk, A_qk = A[:c] * decay, A[c:] * decay
        Tb = _unit_lower_inverse(beta * jnp.where(row > col, A_kk, 0.0), row, col) * beta_row
        e_G = jnp.exp(G)
        last = G[c - 1 : c, :]
        U, V = _mm(Tb, k * e_G), _mm(Tb, v)
        # the three products that do
        S_h = S[h]
        P = _mm(jnp.concatenate([U, q * e_G], axis=0), S_h)  # [U; Q e^G] S
        W = V - P[:c]
        o_ref[h] = P[c:] + _mm(A_qk, W)
        decay_S = jnp.exp(jnp.broadcast_to(last, (1, S_h.shape[1])))  # along the lanes, then down the sublanes
        S[h] = decay_S * S_h + _mm(k * jnp.exp(last - G), W, ((0,), (0,)))

    @pl.when(j == n - 1)
    def _():
        s_out[...] = S[:, :dk, :]


@functools.partial(jax.jit, static_argnames=("chunk", "head_block", "interpret"))
def _call(S, q, k, v, g, beta, *, chunk, head_block, interpret):
    # imported here, as ops/kda.py does: a second of import that only a
    # process which runs the kernel pays
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, heads, dk = q.shape
    dv = v.shape[-1]
    H = B * heads  # a sequence's heads are as apart as two sequences': one axis
    c, n, hb = chunk, T // chunk, head_block
    Wk = -(-(dk + 2) // 128) * 128

    def heads_lead(a):  # [B, T, heads, *w] -> [H, T, *w]
        return jnp.swapaxes(a, 1, 2).reshape(H, T, *a.shape[3:])

    with jax.named_scope("gdn_chunk.operands"):
        G = jnp.cumsum(heads_lead(g).reshape(H, n, c), axis=-1)  # the gate's running sum inside a sub-chunk
        beta = heads_lead(beta)
        kx = jnp.concatenate(
            [heads_lead(k), G.reshape(H, T, 1), beta[..., None], jnp.zeros((H, T, Wk - dk - 2), F32)], axis=-1
        )
        qx = jnp.pad(heads_lead(q), ((0, 0), (0, 0), (0, Wk - dk)))
        rows = jnp.concatenate(
            [G[:, :, None], beta.reshape(H, n, 1, c), jnp.zeros((H, n, 6, c), F32)], axis=2
        )

    def of_positions(width):
        return pl.BlockSpec((hb, c, width), lambda i, j: (i, j, 0))

    state = pl.BlockSpec((hb, dk, dv), lambda i, j: (i, 0, 0))
    o, S = pl.pallas_call(
        functools.partial(_kernel, dk=dk),
        grid=(H // hb, n),
        in_specs=[
            of_positions(Wk), of_positions(Wk), of_positions(dv),
            pl.BlockSpec((hb, None, 8, c), lambda i, j: (i, j, 0, 0)), state,
        ],
        out_specs=[of_positions(dv), state],
        out_shape=[jax.ShapeDtypeStruct((H, T, dv), F32), jax.ShapeDtypeStruct((H, dk, dv), F32)],
        scratch_shapes=[pltpu.VMEM((hb, Wk, dv), F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        name="gdn_chunk",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(kx, qx, heads_lead(v), rows, S.reshape(H, dk, dv))
    return S.reshape(B, heads, dk, dv), jnp.swapaxes(o.reshape(B, heads, T, dv), 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _chunked(S, q, k, v, g, beta, chunk, head_block, interpret):
    return _call(S, q, k, v, g, beta, chunk=chunk, head_block=head_block, interpret=interpret)


def _chunked_fwd(*operands_and_statics):
    return _chunked(*operands_and_statics), operands_and_statics[:6]


def _chunked_bwd(chunk, head_block, interpret, operands, cotangents):
    # the kernel has no transpose: the gradient is the plain form's
    return jax.vjp(functools.partial(delta_rule.gdn_chunked, chunk=chunk), *operands)[1](cotangents)


_chunked.defvjp(_chunked_fwd, _chunked_bwd)


def chunked(S, q, k, v, g, beta, chunk: int, *, head_block=None, interpret=None):
    """``delta_rule.gdn_chunked`` through the kernel: ``S [B, H, dk, dv]``
    float32 before the first position, ``q, k [B, T, H, dk]``, ``v [B, T, H,
    dv]``, ``g, beta [B, T, H]`` float32, ``T`` a multiple of ``chunk``, a power of
    two -> ``(S after the last position, o [B, T, H, dv])``. A position with
    ``beta = 0`` and ``g = 0`` (a padded tail) moves nothing. Its gradient is
    ``gdn_chunked``'s.

    ``head_block``: heads a grid step (default: the largest divisor of ``H`` up
    to 5). ``interpret``: run the kernel in Pallas' TPU interpreter (what
    the CPU tests do); by default wherever the backend is not a TPU."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    H = q.shape[2]
    hb = head_block or max(d for d in range(1, _HEAD_BLOCK + 1) if H % d == 0)
    if q.shape[1] % chunk or chunk & (chunk - 1) or H % hb:
        raise ValueError(
            f"the kernel takes whole sub-chunks of a power of two and a block of heads that divides {H}: "
            f"q {q.shape}, chunk {chunk}, head_block {hb}"
        )
    return _chunked(*(a.astype(F32) for a in (S, q, k, v, g, beta)), chunk, hb, bool(interpret))
