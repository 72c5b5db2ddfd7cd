"""Jamba-family decoder LM: Mamba-1 SELECTIVE STATE-SPACE layers and a few
multi-query attention layers WITHOUT any position term in one pre-norm residual
stream (26 Mamba + 2 attention of 28 at the published sizes: layer ``l``
attends iff ``l % attn_layer_period == attn_layer_offset``), a dense gated MLP
in every layer, the embedding read again as the head (tied).

What a sequence leaves behind is of TWO kinds (``models/interface.py``): a K
and a V row a token in the layers that attend (``CacheLayout`` of kind
``"kv"``: ONE KV head of ``head_dim`` numbers each, 2 of 28 layers: 1,024 B a
token) and, in the layers that recur, the state ``h`` and the convolution's
last ``d_conv - 1`` inputs a SEQUENCE (``StateLayout`` ``"mamba1"``, 26 of 28
layers: 358,400 B a layer whatever the length). The third kind of state in the
pool, and the first model whose recurrent layers are nearly all of it.

A layer: ``x + mix(norm(x))``, ``x + mlp(norm(x))``. The mixers::

    mamba:      [x | z] = u W_in                          (D -> 2 Di, no bias)
                x_t = silu(sum_{j < K} taps[j] * x_{t - (K - 1) + j} + b_conv)   depthwise, causal
                [dt | B | C] = x_t W_x                    (Di -> R + N + N, no bias)
                dt, B, C = rms(dt) w_dt, rms(B) w_b, rms(C) w_c
                D_t = softplus(dt W_dt + b_dt)            (R -> Di)
                h_t = exp(D_t A) h_{t-1} + (D_t x_t) B_t^T         A = -exp(A_log) [N, Di], float32
                y_t = h_t^T C_t + Dskip x_t;   out = (y_t silu(z_t)) W_out
    attention:  q, k, v = u Wq, u Wk, u Wv                (H x hd | KV x hd | KV x hd), no rotary,
                causal softmax(q k^T hd^-1/2) v, grouped;  out = o Wo

The recurrence is ``ops/selective_scan.py``'s (a prefill chunk from the
sequence's slot, the state written back behind the chunk's last REAL row: a
padded row is handed ``D_t = 0`` and leaves ``h`` as it was; a decode batch
one position a slot in place), float32 throughout: ``exp``, ``softplus``, the
three inner norms and ``h``; the matmuls in the model's dtype with float32
accumulation. The convolution is ``ops/short_conv.py``'s with the bias and the
SiLU applied here. The attention reads the paged cache through
``models/paged_kv.py``, the write and the three ways chosen at trace time from
shapes and backend, as every K/V model: a decode step on a TPU the Pallas kernel
``ops/paged_attention.py`` (ONE KV head: a block is ``[bs, hd]`` rows, stored
flat), a prefill chunk on a TPU the flash kernel ``ops/latent_flash.py`` over K
and V gathered through the table (20 query heads a key head), everything else
the gather with the softmax materialised.

``A_log`` is stored ``[N, Di]`` (the published layout is ``[Di, N]``): the
state index major, as the state itself lies.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import paged_kv
from ray_tpu.models.interface import AttentionPath, CacheLayout, Model, StateLayout, lm_head
from ray_tpu.ops import selective_scan, short_conv
from ray_tpu.ops.layers import rms_norm
from ray_tpu.ops.moe import gated_mlp
from ray_tpu.parallel.sharding import constrain

F32 = jnp.float32


@dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    dim: int = 2560
    n_layers: int = 28
    #: layer ``l`` ATTENDS iff ``l % attn_period == attn_offset``; every other layer is Mamba
    attn_period: int = 14
    attn_offset: int = 7
    n_heads: int = 20
    n_kv_heads: int = 1
    head_dim: int = 128
    mlp_hidden: int = 8192
    #: the selective state-space mixer: states a channel, taps of the causal
    #: convolution, the rank of the step size's projection, channels a model width
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    expand: int = 2
    max_seq_len: int = 8192
    norm_eps: float = 1e-6
    dtype: Any = jnp.float32

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    @property
    def kinds(self) -> Tuple[str, ...]:
        """``"attn"`` | ``"mamba"`` for each layer, in order."""
        return tuple(
            "attn" if l % self.attn_period == self.attn_offset else "mamba" for l in range(self.n_layers)
        )

    @property
    def n_attn_layers(self) -> int:
        return self.kinds.count("attn")

    @property
    def n_mamba_layers(self) -> int:
        return self.kinds.count("mamba")

    @staticmethod
    def tiny(**overrides) -> "JambaConfig":
        """CI-sized config: M M A M M (one attention layer among four Mamba),
        4 heads of 16 over ONE KV head, 4 states a channel."""
        base = dict(
            vocab_size=256, dim=64, n_layers=5, attn_period=5, attn_offset=2, n_heads=4, n_kv_heads=1,
            head_dim=16, mlp_hidden=96, d_state=4, d_conv=4, dt_rank=8, max_seq_len=64,
        )
        base.update(overrides)
        return JambaConfig(**base)


# ---------------------------------------------------------------------------
# params (one dict a layer) + logical axes


def _layer_shapes(cfg: JambaConfig, kind: str) -> Dict[str, Tuple[int, ...]]:
    D, Di, N, R = cfg.dim, cfg.d_inner, cfg.d_state, cfg.dt_rank
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    shapes: Dict[str, Tuple[int, ...]] = {"mixer_norm": (D,)}
    if kind == "mamba":
        shapes.update({
            "in_proj": (D, 2 * Di), "conv_taps": (cfg.d_conv, Di), "conv_bias": (Di,),
            "x_proj": (Di, R + 2 * N), "dt_norm": (R,), "b_norm": (N,), "c_norm": (N,),
            "dt_proj": (R, Di), "dt_bias": (Di,), "A_log": (N, Di), "D": (Di,), "out_proj": (Di, D),
        })
    else:
        shapes.update({"wq": (D, H, hd), "wk": (D, KV, hd), "wv": (D, KV, hd), "wo": (H, hd, D)})
    shapes.update({
        "ffn_norm": (D,), "w_gate": (D, cfg.mlp_hidden), "w_up": (D, cfg.mlp_hidden),
        "w_down": (cfg.mlp_hidden, D),
    })
    return shapes


_AXES = {
    "in_proj": ("embed", None), "out_proj": (None, "embed"),
    "wq": ("embed", "heads", "head_dim"), "wk": ("embed", "kv_heads", "head_dim"),
    "wv": ("embed", "kv_heads", "head_dim"), "wo": ("heads", "head_dim", "embed"),
    "w_gate": ("embed", "mlp"), "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed"),
}


def logical_axes(cfg: JambaConfig) -> Dict[str, Any]:
    """Pytree (same structure as params) of logical-axis-name tuples."""
    layers = [
        {k: _AXES.get(k, (None,) * len(shape)) for k, shape in _layer_shapes(cfg, kind).items()}
        for kind in cfg.kinds
    ]
    return {"embed": ("vocab", "embed"), "layers": layers, "final_norm": (None,)}


def init_params(cfg: JambaConfig, rng: jax.Array) -> Dict[str, Any]:
    """Seeded weights under which the recurrence MATTERS (Mamba's published
    initialisation). Projections normal / sqrt(fan-in) in ``cfg.dtype``; each
    sublayer's LAST projection (``out_proj``, ``wo``, ``w_down``) a further 1
    / sqrt(2 x layers) smaller; ``A_log`` = log(1 .. N) a state index for every
    channel; ``dt_bias`` such that ``softplus(dt_bias)`` is log-uniform in
    [1e-3, 1e-1]: the slowest states then decay by ``exp(-1e-3)`` a position and
    outlive a chunk of 1024, so a carry dropped at a chunk edge shows; the
    convolution's taps normal / sqrt(taps) (every tap a quarter of the
    output's variance: a dropped tap or a tail cut in the wrong place shows),
    its bias normal x 0.1; ``D`` (the skip) 1; norm vectors 1."""
    with jax.threefry_partitionable(True):
        return _init_params(cfg, rng)


def _init_params(cfg: JambaConfig, rng: jax.Array) -> Dict[str, Any]:
    k_embed, k_layers = jax.random.split(rng)

    def dense(key, shape, fan_in, slices: int = 1):
        """Normal / sqrt(fan_in), drawn ``slices`` slices of the leading axis
        at a time (``models/kimi_linear.py``: the float32 draw of a
        vocabulary-sized matrix whole is gigabytes beside the weights)."""
        if slices == 1:
            return (jax.random.normal(key, shape, F32) / math.sqrt(fan_in)).astype(cfg.dtype)
        part = (shape[0] // slices, *shape[1:])
        draw = lambda k: (jax.random.normal(k, part, F32) / math.sqrt(fan_in)).astype(cfg.dtype)  # noqa: E731
        return jax.lax.map(draw, jax.random.split(key, slices)).reshape(shape)

    def layer(key, kind: str):
        shapes = _layer_shapes(cfg, kind)
        out = {}
        for (name, shape), k in zip(shapes.items(), jax.random.split(key, len(shapes))):
            if name.endswith("norm") or name == "D":
                out[name] = jnp.ones(shape, cfg.dtype)
            elif name == "A_log":
                states = jnp.arange(1, shape[0] + 1, dtype=F32)
                out[name] = jnp.broadcast_to(jnp.log(states)[:, None], shape).astype(cfg.dtype)
            elif name == "dt_bias":
                dt = jnp.exp(jax.random.uniform(k, shape, F32, math.log(1e-3), math.log(1e-1)))
                out[name] = (dt + jnp.log(-jnp.expm1(-dt))).astype(cfg.dtype)  # softplus^-1
            elif name == "conv_bias":
                out[name] = (0.1 * jax.random.normal(k, shape, F32)).astype(cfg.dtype)
            else:
                # contraction dims: the taps of the filter; heads x hd of
                # ``wo``; the first of every other projection
                fan_in = shape[0] * shape[1] if name == "wo" else shape[0]
                if name in ("out_proj", "wo", "w_down"):
                    fan_in *= 2 * cfg.n_layers
                out[name] = dense(k, shape, fan_in)
        return out

    return {
        "embed": dense(k_embed, (cfg.vocab_size, cfg.dim), cfg.dim, slices=math.gcd(16, cfg.vocab_size)),
        "layers": [layer(k, kind) for k, kind in zip(jax.random.split(k_layers, cfg.n_layers), cfg.kinds)],
        "final_norm": jnp.ones((cfg.dim,), cfg.dtype),
    }


def param_count(cfg: JambaConfig) -> int:
    layers = sum(sum(math.prod(s) for s in _layer_shapes(cfg, kind).values()) for kind in cfg.kinds)
    return cfg.vocab_size * cfg.dim + layers + cfg.dim


# ---------------------------------------------------------------------------
# the pieces of a layer


def _qkv(p, h):
    """The projections of one attention on normed activations ``h [B, C,
    D]``: ``(q [B, C, H, hd], k, v [B, C, KV, hd])``; no position term."""
    q = jnp.einsum("bcd,dhk->bchk", h, p["wq"])
    k = jnp.einsum("bcd,dhk->bchk", h, p["wk"])
    v = jnp.einsum("bcd,dhk->bchk", h, p["wv"])
    return q, k, v


def _ssm_inputs(p, h):
    """``(x, z)`` of one Mamba mixer on normed activations ``h [..., D]``:
    the convolution's input and the output's gate, ``[..., Di]`` each."""
    with jax.named_scope("ssm.in_proj"):
        x, z = jnp.split(h @ p["in_proj"], 2, axis=-1)
        return x, z


def _ssm_activate(p, c):
    """The convolution's float32 output ``c [..., Di]`` with its bias, through SiLU."""
    return jax.nn.silu(c + p["conv_bias"].astype(F32))


def _ssm_params(cfg: JambaConfig, p, x):
    """What the recurrence takes at each position from the convolved ``x [...,
    Di]`` float32: ``(dt [..., Di], B [..., N], C [..., N], A [N, Di])``, float32:
    the low-rank projection, Jamba's three inner norms, the step size through
    its own projection, bias and softplus."""
    with jax.named_scope("ssm.params"):
        R, N = cfg.dt_rank, cfg.d_state
        low = jnp.dot(x.astype(cfg.dtype), p["x_proj"], preferred_element_type=F32)
        dt, Bm, Cm = low[..., :R], low[..., R : R + N], low[..., R + N :]
        dt = rms_norm(dt, p["dt_norm"].astype(F32), cfg.norm_eps)
        Bm = rms_norm(Bm, p["b_norm"].astype(F32), cfg.norm_eps)
        Cm = rms_norm(Cm, p["c_norm"].astype(F32), cfg.norm_eps)
        dt = jnp.dot(dt.astype(cfg.dtype), p["dt_proj"], preferred_element_type=F32)
        dt = jax.nn.softplus(dt + p["dt_bias"].astype(F32))
        return dt, Bm, Cm, -jnp.exp(p["A_log"].astype(F32))


def _ssm_output(cfg: JambaConfig, p, y, x, z):
    """The mixer's output from the recurrence's read-out ``y``, the skip and the gate."""
    with jax.named_scope("ssm.out"):
        y = (y + p["D"].astype(F32) * x) * jax.nn.silu(z.astype(F32))
        return y.astype(cfg.dtype) @ p["out_proj"]


def _mamba_chunk(cfg: JambaConfig, p, h, state, layer: int, slot, fresh, true_len):
    """The Mamba mixer over a window of several positions of ONE sequence,
    ``h [C, D]``, from the sequence's slot of the layer's slab of both state
    arrays (zeros where ``fresh``), both written back behind the first
    ``true_len`` rows: ``(out [C, D], state)``."""
    C, keep = h.shape[0], cfg.d_conv - 1
    x, z = _ssm_inputs(p, h)
    with jax.named_scope("ssm.conv"):
        pool = state["conv_tail"]
        at = (jnp.int32(layer), slot, jnp.int32(0))
        tail = jax.lax.dynamic_slice(pool, at, (1, 1, pool.shape[2]))[0]
        tail = jnp.where(fresh, 0, tail).reshape(1, keep, -1)
        c, tail = short_conv.chunk(x[None], tail, p["conv_taps"], jnp.reshape(true_len, (1,)))
        pool = jax.lax.dynamic_update_slice(pool, tail.reshape(1, 1, -1).astype(pool.dtype), at)
        x = _ssm_activate(p, c[0])
    dt, Bm, Cm, A = _ssm_params(cfg, p, x)
    with jax.named_scope("ssm.scan"):
        # a padded row must not advance the state: exp(0 x A) = 1 and nothing added
        dt = jnp.where((jnp.arange(C) < true_len)[:, None], dt, 0.0)
        y, ssm = selective_scan.chunk(state["ssm"], layer, slot, fresh, dt, x, Bm, Cm, A)
    return _ssm_output(cfg, p, y, x, z), {"ssm": ssm, "conv_tail": pool}


def _mamba_step(cfg: JambaConfig, p, h, state, layer: int, slots, fresh):
    """The Mamba mixer one position a slot, ``h [B, D]``, in place in the
    layer's slab of both state arrays: ``(out [B, D], state)``."""
    x, z = _ssm_inputs(p, h)
    with jax.named_scope("ssm.conv"):
        c, pool = short_conv.step(state["conv_tail"], layer, slots, x, p["conv_taps"], fresh)
        x = _ssm_activate(p, c)
    dt, Bm, Cm, A = _ssm_params(cfg, p, x)
    with jax.named_scope("ssm.update"):
        y, ssm = selective_scan.step(state["ssm"], layer, slots, fresh, dt, x, Bm, Cm, A)
    return _ssm_output(cfg, p, y, x, z), {"ssm": ssm, "conv_tail": pool}


def _mlp(cfg: JambaConfig, p, x):
    with jax.named_scope("mlp"):
        return gated_mlp(rms_norm(x, p["ffn_norm"], cfg.norm_eps), p["w_gate"], p["w_up"], p["w_down"])


# ---------------------------------------------------------------------------
# forward (the full sequence: the tests' other side; no cache, no slots)


def forward(cfg: JambaConfig, params, tokens, *, remat=False, mesh=None, rules=None,
            return_aux: bool = False):
    """tokens [B, S] int32 -> logits [B, S, vocab] (f32): every Mamba layer
    from a zero state and a zero tail, every attention causal over the
    sequence itself."""
    del remat
    B, S = tokens.shape
    causal = jnp.tril(jnp.ones((S, S), bool))
    rep = cfg.n_heads // cfg.n_kv_heads
    x = constrain(params["embed"], mesh, rules, (None, None))[tokens]
    for p, kind in zip(params["layers"], cfg.kinds):
        h = rms_norm(x, p["mixer_norm"], cfg.norm_eps)
        if kind == "mamba":
            xs, z = _ssm_inputs(p, h)
            tail = jnp.zeros((B, cfg.d_conv - 1, cfg.d_inner), h.dtype)
            c, _ = short_conv.chunk(xs, tail, p["conv_taps"], jnp.full((B,), S, jnp.int32))
            xs = _ssm_activate(p, c)
            dt, Bm, Cm, A = _ssm_params(cfg, p, xs)
            h0 = jnp.zeros((cfg.d_state, cfg.d_inner), F32)
            y, _ = jax.vmap(lambda *a: selective_scan.scan_positions(h0, *a, A))(dt, xs, Bm, Cm)
            mix = _ssm_output(cfg, p, y, xs, z)
        else:
            q, k, v = _qkv(p, h)
            s = jnp.einsum("bcgrh,bsgh->bgrcs", q.reshape(B, S, cfg.n_kv_heads, rep, -1), k).astype(F32)
            s = jnp.where(causal, s * cfg.head_dim ** -0.5, -1e30)
            o = jnp.einsum("bgrcs,bsgh->bcgrh", jax.nn.softmax(s, axis=-1).astype(v.dtype), v)
            mix = jnp.einsum("bchk,hkd->bcd", o.reshape(B, S, cfg.n_heads, -1), p["wo"])
        x = x + mix
        x = x + _mlp(cfg, p, x)
    logits = constrain(lm_head(params, x, cfg.norm_eps, tied=True), mesh, rules, ("act_batch", "act_seq", "act_vocab"))
    if return_aux:
        return logits, jnp.zeros((), F32)
    return logits


# ---------------------------------------------------------------------------
# the two pools and the serving steps over ONE body
#
# ``cache["k"]``, ``cache["v"]``: the attending layers alone, ``[n_attn,
# num_blocks, block_size x n_kv, hd]`` where a head is whole lanes (ONE KV head:
# a block ``[16, 128]``, one whole tile, nothing padded: 1,024 B a token over 2
# layers at the published widths), at the tests' toy widths ``[.., block_size,
# n_kv, hd]``. ``state["ssm"] [n_mamba, num_slots, N, G, lanes]`` float32
# (``ops/selective_scan.py::state_shape``) and ``state["conv_tail"] [n_mamba,
# num_slots, (K - 1) x Di]`` in the model's dtype (a sequence's last inputs
# stored as ONE row): 9,318,400 B a sequence over 26 layers whatever its
# length. Slot 0 is the null slot: a padding slot of a decode batch reads and
# writes it.


def cache_layout(cfg: JambaConfig, block_size: int, dtype=None) -> CacheLayout:
    row = (cfg.n_kv_heads, cfg.head_dim)
    return CacheLayout(
        kind="kv", n_layers=cfg.n_attn_layers, block_size=block_size,
        arrays=(("k", row), ("v", row)), dtype=dtype or cfg.dtype,
        flat_blocks=(
            cfg.head_dim % 128 == 0 and cfg.n_kv_heads % 8 != 0
            and (block_size * cfg.n_kv_heads) % 16 == 0
        ),
    )


def state_layout(cfg: JambaConfig) -> StateLayout:
    return StateLayout(
        kind="mamba1", n_layers=cfg.n_mamba_layers,
        arrays=(
            ("ssm", selective_scan.state_shape(cfg.d_state, cfg.d_inner), F32),
            ("conv_tail", ((cfg.d_conv - 1) * cfg.d_inner,), cfg.dtype),
        ),
    )


def _shapes(cfg: JambaConfig) -> Dict[str, int]:
    """What ``models/paged_kv.py`` is told beside the cache's shape."""
    return {"n_kv": cfg.n_kv_heads, "head_dim": cfg.head_dim}


def _attention_mix(cfg: JambaConfig, p, cache, index: int, h, pos, valid, block_tables):
    """The attention mixer of one layer (index ``index`` of the attending
    ones) on normed activations ``h [B, C, D]`` at positions ``pos`` (which
    place the rows in the cache and bound what a query sees, and enter nothing
    else): q / k / v, the write of the window's K and V where ``valid``
    (``paged_kv.write_kv``: a padding row's to the null block, or, in a chunk
    written by whole blocks, nowhere), the attention over the cache (after the
    write: a window attends to itself) and ``wo``. Returns ``(cache, out [B,
    C, D])``."""
    bs = paged_kv.block_size(cache["k"], **_shapes(cfg))
    at = paged_kv.rows_at(block_tables, pos, valid, bs)
    with jax.named_scope("attn.full"):
        q, k, v = _qkv(p, h)
        cache = paged_kv.write_kv(cache, index, block_tables, pos, valid, k, v, at=at)
        o = paged_kv.attention_counted(
            q, cache["k"], cache["v"], index, block_tables, pos, valid.sum(axis=1, dtype=jnp.int32),
            **_shapes(cfg),
        )
        return cache, jnp.einsum("bchk,hkd->bcd", o.astype(h.dtype), p["wo"])


def _paged_layers(cfg: JambaConfig, params, cache, state, tokens, pos, valid, block_tables, slots):
    """Every layer of the model over the two pools: the body of the serving
    steps. ``tokens [B, C]``, ``pos [B, C]`` (contiguous a slot), ``valid [B,
    C]`` (the real rows lead), ``block_tables [B, M]``, ``slots [B]``. A Mamba
    layer reads its slots' state and tail (zeros where the slot's sequence
    starts here: ``pos[b, 0] == 0``), runs the window and writes them back in
    place; an attending layer writes the window's K and V to its blocks and
    attends over the cache. Returns ``(cache, state, x [B, C, D])``."""
    real = valid.any(axis=1)
    # a padding slot is pointed at the null block and the null slot
    block_tables = jnp.where(real[:, None], block_tables, 0)
    slots = jnp.where(real, slots, 0)
    true_lens = valid.sum(axis=1, dtype=jnp.int32)
    fresh = pos[:, 0] == 0
    B, C = pos.shape
    x = params["embed"][tokens]
    i_mamba = i_attn = 0
    for p, kind in zip(params["layers"], cfg.kinds):
        h = rms_norm(x, p["mixer_norm"], cfg.norm_eps)
        if kind == "mamba" and C == 1:
            # a decode batch: the rows' own slots of the layer's slab, in place
            mix, state = _mamba_step(cfg, p, h[:, 0], state, i_mamba, slots, fresh)
            mix = mix[:, None]
            i_mamba += 1
        elif kind == "mamba":
            assert B == 1, "a window of several positions is ONE request's prefill chunk"
            mix, state = _mamba_chunk(cfg, p, h[0], state, i_mamba, slots[0], fresh[0], true_lens[0])
            mix = mix[None]
            i_mamba += 1
        else:
            cache, mix = _attention_mix(cfg, p, cache, i_attn, h, pos, valid, block_tables)
            i_attn += 1
        x = x + mix
        x = x + _mlp(cfg, p, x)
    return cache, state, x


def paged_prefill_step(cfg: JambaConfig, params, cache, state, tokens, block_table, ctx_len,
                       true_len, slot):
    """One prefill chunk for ONE request, as ``models/llama.py::
    paged_prefill_step`` with the state pool after the cache and the
    request's slot last. A chunk at ``ctx_len == 0`` starts from a zero state
    and a zero tail (a re-admitted request re-derives them from position 0)."""
    idx = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    cache, state, x = _paged_layers(
        cfg, params, cache, state, tokens[None], (ctx_len + idx)[None], (idx < true_len)[None],
        block_table[None], jnp.reshape(slot, (1,)),
    )
    return cache, state, lm_head(params, x[0, jnp.maximum(true_len - 1, 0)], cfg.norm_eps, tied=True)


def paged_decode_step(cfg: JambaConfig, params, cache, state, tokens, positions, block_tables,
                      ctx_lens, slots):
    """One decode step for a batch of slots, as ``models/llama.py::
    paged_decode_step`` with the state pool after the cache and the slots'
    indices ``[B]`` last (a slot whose token would be written to the null
    block is padding: it reads and writes the null slot)."""
    del ctx_lens
    pos = positions[:, None]
    valid = paged_kv.block_at(block_tables, pos, paged_kv.block_size(cache["k"], **_shapes(cfg))) != 0
    cache, state, x = _paged_layers(
        cfg, params, cache, state, tokens[:, None], pos, valid, block_tables, slots
    )
    return cache, state, lm_head(params, x[:, 0], cfg.norm_eps, tied=True)


def paged_verify_step(cfg: JambaConfig, *args, **kwargs):
    """Not there: a verify window over recurrent layers needs the state
    after EACH of its positions (the accepted prefix's is kept, the rest
    rolled back); the engine refuses speculation on a model with a state
    description."""
    raise NotImplementedError(
        "speculative verification is not implemented over recurrent (selective state-space) layers: "
        "the state after each position of the window would have to be kept for the roll-back"
    )


# ---------------------------------------------------------------------------
# what the runtime knows of this module (models/interface.py)


def _program_path(cfg: JambaConfig, window: int, cache, backend=None) -> tuple:
    return paged_kv.program_path(
        window, cache["k"], cfg.max_seq_len, (cfg.n_heads,), backend=backend, **_shapes(cfg)
    )


def _attention_path(cfg: JambaConfig, window: int, cache, backend=None) -> AttentionPath:
    """The mixers' paths of a program of that window, named together: the
    Mamba layers' (a chunk: ``ssm.scan``, one position a slot: ``ssm.update``,
    each ``.kernel`` where ``ops/selective_scan.py`` serves the pool) and the
    attending layers' (``models/paged_kv.py::way``); what a launch reads of the
    paged cache is the latter's."""
    (_, shape, dtype), _ = state_layout(cfg).arrays
    pool = jax.ShapeDtypeStruct((cfg.n_mamba_layers, 1, *shape), dtype)  # any number of slots
    ssm = "ssm.scan" if window > 1 else "ssm.update"
    if selective_scan.kernel_serves(pool, backend):
        ssm += ".kernel"
    way, reads, _ = _program_path(cfg, window, cache, backend)
    return AttentionPath(f"{ssm}+{way}", reads)


MODEL = Model(
    name="jamba",
    init_params=init_params,
    forward=forward,
    logical_axes=logical_axes,
    param_count=param_count,
    cache_layout=cache_layout,
    paged_prefill_step=paged_prefill_step,
    paged_verify_step=paged_verify_step,
    paged_decode_step=paged_decode_step,
    attention_path=_attention_path,
    held_experts=lambda cfg: None,
    key_tile=lambda cfg, window, cache: _program_path(cfg, window, cache)[2],
    state_layout=state_layout,
)
