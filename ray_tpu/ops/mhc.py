"""Sinkhorn-Knopp on the ``n x n`` residual maps of a hyper-connected
sublayer (``models/xing4.py::mhc_maps``), with the TOKENS on the minor axis.

A token's map is ``n x n`` numbers (n = 4: 16) and a step has hundreds to
thousands of tokens. Laid ``[T, n, n]`` and normalised with ``sum(axis=-1)``
and ``sum(axis=-2)``, every one of the 2 x 20 normalisations is a reduce over
a trailing axis of 4 that ends an XLA fusion and flips the layout: 77 fusions
and 42 copies of ``f32[1024, 4]`` a sublayer on a v5e (PERF.md, PR 50), each a
device operation of its own on 16 KB. Here an entry ``(i, j)`` of the map is
ONE array over the tokens, a row's or a column's sum is ``n - 1`` explicit adds
of such arrays, and the rounds are one loop over ``n^2`` arrays that never
change shape (:func:`rounds`).

On a TPU the loop runs inside ONE Pallas kernel, ``mhc_sinkhorn``: a block is
``[n^2, 8, 128]`` float32 (1024 tokens: an entry of the map is one whole vector
register), ``exp`` of the clamped logits and all the rounds happen in VMEM, and
the device sees one operation where it saw ~120. Elsewhere (the CPU; a dtype
the kernel does not take) the SAME function runs on ``[T]`` arrays under
``lax.fori_loop``: the same order of operations, and a gradient. The kernel's
gradient is that body's (``jax.custom_vjp``), so ``forward`` may train through
either.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

#: tokens a block of the kernel holds: 8 sublanes x 128 lanes, so that each
#: of the ``n^2`` entries is one float32 vector register
_BLOCK_TOKENS = 8 * 128


def kernel_serves(logits, backend: str | None = None) -> bool:
    """Whether :func:`sinkhorn` runs the kernel over ``logits [n^2, T]``: on a
    TPU, in float32 (the maps are float32 whatever the model's dtype).
    Decided at trace time from what the code can observe."""
    return (backend or jax.default_backend()) == "tpu" and logits.dtype == jnp.float32


def rounds(m, iters: int, eps: float):
    """``iters`` Sinkhorn-Knopp rounds on ``m``, a tuple of the ``n^2``
    entries of the maps in row-major order, each an array over the tokens
    (any one shape): every row divided by its sum + ``eps``, then every
    column. Sums are explicit adds, left to right: no reduce, no reshape."""
    n = math.isqrt(len(m))

    def normalise(m, lines):
        m = list(m)
        for line in lines:
            total = m[line[0]]
            for k in line[1:]:
                total = total + m[k]
            total = total + eps
            for k in line:
                m[k] = m[k] / total
        return m

    row_lines = [[i * n + j for j in range(n)] for i in range(n)]
    col_lines = [[i * n + j for i in range(n)] for j in range(n)]
    return jax.lax.fori_loop(
        0, iters, lambda _, m: tuple(normalise(normalise(m, row_lines), col_lines)), tuple(m)
    )


def _sinkhorn_jnp(logits, iters: int, eps: float, clamp: float):
    m = jnp.exp(jnp.clip(logits, -clamp, clamp))
    return jnp.stack(rounds(tuple(m[k] for k in range(m.shape[0])), iters, eps))


def _kernel(x_ref, o_ref, *, iters: int, eps: float, clamp: float):
    m = tuple(jnp.exp(jnp.clip(x_ref[k], -clamp, clamp)) for k in range(x_ref.shape[0]))
    for k, entry in enumerate(rounds(m, iters, eps)):
        o_ref[k] = entry


@functools.partial(jax.jit, static_argnames=("iters", "eps", "clamp", "interpret"))
def _call(logits, *, iters, eps, clamp, interpret):
    # imported here, as ops/kda.py does: a second of import that only a
    # process which runs the kernel pays
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nn, T = logits.shape
    blocks = -(-T // _BLOCK_TOKENS)
    # padding tokens: logits 0, a map of ones, normalised like any other
    x = jnp.pad(logits, ((0, 0), (0, blocks * _BLOCK_TOKENS - T))).reshape(nn, blocks * 8, 128)
    block = pl.BlockSpec((nn, 8, 128), lambda t: (0, t, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, iters=iters, eps=eps, clamp=clamp),
        grid=(blocks,),
        in_specs=[block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        name="mhc_sinkhorn",
        interpret=pltpu.InterpretParams() if interpret else False,
    )(x)
    return out.reshape(nn, -1)[:, :T]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _sinkhorn_kernel(logits, iters, eps, clamp, interpret):
    return _call(logits, iters=iters, eps=eps, clamp=clamp, interpret=interpret)


def _kernel_fwd(logits, iters, eps, clamp, interpret):
    return _call(logits, iters=iters, eps=eps, clamp=clamp, interpret=interpret), logits


def _kernel_bwd(iters, eps, clamp, interpret, logits, g):
    del interpret
    return jax.vjp(lambda x: _sinkhorn_jnp(x, iters, eps, clamp), logits)[1](g)


_sinkhorn_kernel.defvjp(_kernel_fwd, _kernel_bwd)


def sinkhorn(logits, *, iters: int, eps: float, clamp: float):
    """``logits [n^2, T]`` (entry ``i n + j`` of every token's map, the
    tokens minor) -> the doubly stochastic maps in the same form: ``exp`` of
    the logits clamped to ``+- clamp``, then ``iters`` rounds of rows, then
    columns, each divided by its sum + ``eps``. The kernel where
    :func:`kernel_serves` (in Pallas' TPU interpreter if a test says so off a
    TPU), :func:`rounds` on ``[T]`` arrays elsewhere."""
    if kernel_serves(logits):
        interpret = jax.default_backend() != "tpu"
        return _sinkhorn_kernel(logits, iters, float(eps), float(clamp), interpret)
    return _sinkhorn_jnp(logits, iters, eps, clamp)

