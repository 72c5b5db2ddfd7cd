"""LFM2-family decoder LM: gated SHORT-CONVOLUTION layers and grouped-query
attention layers of narrow heads in one pre-norm residual stream (18 conv + 6
attention of 24 at the published sizes, the attention layers where
``attn_layers`` says: the pattern is irregular), leading dense MLPs and then
sigmoid-routed experts with a choice bias and no shared expert, of which this
process may hold a range (one chip's share of an expert-parallel deployment).
The embedding is read again as the head (tied).

What a sequence leaves behind is of TWO kinds (``models/interface.py``): a K
and a V row a token in the layers that attend (``CacheLayout`` of kind
``"kv"``: ``n_kv_heads x head_dim`` numbers each, 6 of 24 layers) and the last
``conv_kernel - 1`` inputs of the convolution a SEQUENCE in the layers that
convolve (``StateLayout`` ``"short_conv"``, 18 of 24 layers: 8 KB a layer
whatever the length). The first model with a ``"kv"`` cache AND a state pool.

A layer: ``x + mix(norm(x))``, ``x + ffn(norm(x))``. The mixers::

    conv:       [B | C | X] = u W_in                     (D -> 3 D, no bias)
                z_t = B_t * X_t
                c_t = sum_{j < K} taps[j] * z_{t - (K - 1) + j}     depthwise, causal
                out = (C_t * c_t) W_out
    attention:  q, k, v = u Wq, u Wk, u Wv               (H x hd | KV x hd | KV x hd)
                q, k RMS-normalised over EACH HEAD's hd (one weight [hd] for
                all heads), then rotary (halves: lane i pairs with i + hd / 2)
                causal softmax(q k^T hd^-1/2) v, GQA;  out = o Wo

The convolution is ``ops/short_conv.py``'s (a prefill chunk with the tail
carried in from the sequence's slot and cut behind the chunk's last REAL
input; a decode batch one step in place in the layer's slab of the pool). The
attention reads the paged cache through ``models/paged_kv.py``, the write and
the three ways chosen at trace time from shapes and backend, as every K/V model: a
decode step on a TPU the Pallas kernel ``ops/paged_attention.py`` (each slot's
live blocks; narrow heads ride in lanes), a prefill chunk on a TPU the flash
kernel ``ops/latent_flash.py`` over K and V gathered through the table
(narrow heads in pairs), everything else the gather with the softmax
materialised. The experts are ``ops/moe.py``'s (sigmoid scores, the bias in
the choice alone, gates renormalised over the kept).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import paged_kv
from ray_tpu.models.interface import AttentionPath, CacheLayout, Model, StateLayout
from ray_tpu.models.interface import lm_head, stack_aux, step_counters, step_outputs
from ray_tpu.ops import short_conv
from ray_tpu.ops.layers import rms_norm
from ray_tpu.ops.moe import DENSE_AXES, MOE_AXES, gated_mlp, routed_ffn
from ray_tpu.parallel.sharding import constrain

F32 = jnp.float32


@dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 65536
    dim: int = 2048
    n_layers: int = 24
    #: the layers that ATTEND, 0-indexed as ``layer_types`` is published;
    #: every other layer is a gated short convolution
    attn_layers: Tuple[int, ...] = (2, 6, 10, 14, 18, 21)
    n_heads: int = 32
    n_kv_heads: int = 8
    #: taps of the depthwise causal convolution (``conv_L_cache``)
    conv_kernel: int = 3
    #: the first ``n_dense_layers`` have a dense MLP, the rest routed experts
    n_dense_layers: int = 2
    mlp_hidden: int = 7168
    moe_hidden: int = 1792
    #: how many experts the ROUTER chooses among (its width)
    n_routed_experts: int = 32
    #: the range ``(lo, hi)`` of them this process holds and computes
    held_experts: Tuple[int, int] = (0, 32)
    moe_top_k: int = 4
    routed_scaling_factor: float = 1.0
    rope_theta: float = 1e6
    max_seq_len: int = 8192
    norm_eps: float = 1e-5
    dtype: Any = jnp.float32

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def n_held(self) -> int:
        return self.held_experts[1] - self.held_experts[0]

    @property
    def kinds(self) -> Tuple[str, ...]:
        """``"attn"`` | ``"conv"`` for each layer, in order."""
        return tuple("attn" if l in self.attn_layers else "conv" for l in range(self.n_layers))

    @property
    def n_attn_layers(self) -> int:
        return self.kinds.count("attn")

    @property
    def n_conv_layers(self) -> int:
        return self.kinds.count("conv")

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @staticmethod
    def tiny(**overrides) -> "Lfm2Config":
        """CI-sized config: C C A C A C C (the last attention after one
        convolution, then two more), the first layer dense, 8 experts of
        which this process holds all unless told; heads of 16."""
        base = dict(
            vocab_size=256, dim=64, n_layers=7, attn_layers=(2, 4), n_heads=4, n_kv_heads=2,
            n_dense_layers=1, mlp_hidden=96, moe_hidden=32, n_routed_experts=8,
            held_experts=(0, 8), moe_top_k=2, max_seq_len=64,
        )
        base.update(overrides)
        return Lfm2Config(**base)


# ---------------------------------------------------------------------------
# params (one dict a layer) + logical axes


def _layer_shapes(cfg: Lfm2Config, kind: str, moe: bool) -> Dict[str, Tuple[int, ...]]:
    D, H, KV, hd = cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    shapes: Dict[str, Tuple[int, ...]] = {"operator_norm": (D,)}
    if kind == "conv":
        shapes.update({"conv_in": (D, 3 * D), "conv_taps": (cfg.conv_kernel, D), "conv_out": (D, D)})
    else:
        shapes.update({
            "wq": (D, H, hd), "wk": (D, KV, hd), "wv": (D, KV, hd),
            "q_norm": (hd,), "k_norm": (hd,), "wo": (H, hd, D),
        })
    shapes["ffn_norm"] = (D,)
    if moe:
        Fm = cfg.moe_hidden
        shapes.update({
            "router": (D, cfg.n_routed_experts), "router_bias": (cfg.n_routed_experts,),
            "w_gate": (cfg.n_held, D, Fm), "w_up": (cfg.n_held, D, Fm), "w_down": (cfg.n_held, Fm, D),
        })
    else:
        shapes.update({
            "w_gate": (D, cfg.mlp_hidden), "w_up": (D, cfg.mlp_hidden), "w_down": (cfg.mlp_hidden, D),
        })
    return shapes


def _layers(cfg: Lfm2Config) -> List[Tuple[str, bool]]:
    """``(mixer kind, is it an expert layer)`` for each layer."""
    return [(kind, l >= cfg.n_dense_layers) for l, kind in enumerate(cfg.kinds)]


_AXES = {
    "conv_in": ("embed", None), "conv_out": (None, "embed"),
    "wq": ("embed", "heads", "head_dim"), "wk": ("embed", "kv_heads", "head_dim"),
    "wv": ("embed", "kv_heads", "head_dim"), "wo": ("heads", "head_dim", "embed"),
}


def logical_axes(cfg: Lfm2Config) -> Dict[str, Any]:
    """Pytree (same structure as params) of logical-axis-name tuples."""
    layers = []
    for kind, moe in _layers(cfg):
        own = {**_AXES, **(MOE_AXES if moe else DENSE_AXES)}
        layers.append({
            k: own.get(k, (None,) * len(shape)) for k, shape in _layer_shapes(cfg, kind, moe).items()
        })
    return {"embed": ("vocab", "embed"), "layers": layers, "final_norm": (None,)}


def init_params(cfg: Lfm2Config, rng: jax.Array) -> Dict[str, Any]:
    """Seeded weights under which what is new MATTERS. Projections and
    experts normal / sqrt(fan-in) in ``cfg.dtype``; each sublayer's LAST
    projection (``conv_out``, ``wo``, ``w_down``) a further 1 / sqrt(2 x
    layers) smaller and a ROUTED expert's an eighth of that (``models/xing4.py
    ::init_params`` says why: a hard top-k over independent random experts
    through many layers; at a quarter, as there, the first run on the chip
    read a logit 0.136 off under a limit of 0.3, and the reading is
    heavy-tailed: ``models/kimi_linear.py`` found the same); the router and its
    bias float32, the bias normal x 0.03 (it changes some choices and no
    gate); norm vectors 1, the head norms' too. The convolution's taps normal
    / sqrt(taps): every tap carries a third of the output's variance, so a
    dropped tap or a tail cut in the wrong place shows."""
    with jax.threefry_partitionable(True):
        return _init_params(cfg, rng)


def _init_params(cfg: Lfm2Config, rng: jax.Array) -> Dict[str, Any]:
    k_embed, k_layers = jax.random.split(rng)

    def dense(key, shape, fan_in, dtype=cfg.dtype, slices: int = 1):
        """Normal / sqrt(fan_in), drawn ``slices`` slices of the leading axis
        at a time (``models/kimi_linear.py``: the float32 draw of a
        vocabulary-sized matrix whole is gigabytes beside the weights)."""
        if slices == 1:
            return (jax.random.normal(key, shape, F32) / math.sqrt(fan_in)).astype(dtype)
        part = (shape[0] // slices, *shape[1:])
        draw = lambda k: (jax.random.normal(k, part, F32) / math.sqrt(fan_in)).astype(dtype)  # noqa: E731
        return jax.lax.map(draw, jax.random.split(key, slices)).reshape(shape)

    def layer(key, kind: str, moe: bool):
        shapes = _layer_shapes(cfg, kind, moe)
        out = {}
        for (name, shape), k in zip(shapes.items(), jax.random.split(key, len(shapes))):
            if name.endswith("norm"):
                out[name] = jnp.ones(shape, cfg.dtype)
            elif name == "router":
                out[name] = dense(k, shape, shape[0], F32)  # routing is precision-sensitive
            elif name == "router_bias":
                out[name] = 0.03 * jax.random.normal(k, shape, F32)
            else:
                # contraction dims: the taps of the filter; heads x hd of
                # ``wo``; the first of a projection; an expert's own input width
                fan_in = {"wq": shape[0], "wk": shape[0], "wv": shape[0], "wo": shape[0] * shape[1],
                          "conv_taps": shape[0]}.get(name, shape[-2])
                if name in ("conv_out", "wo", "w_down"):
                    fan_in *= 2 * cfg.n_layers
                if moe and name == "w_down":
                    fan_in *= 64  # a ROUTED expert's output an eighth of that
                out[name] = dense(k, shape, fan_in)
        return out

    return {
        "embed": dense(k_embed, (cfg.vocab_size, cfg.dim), cfg.dim, slices=math.gcd(16, cfg.vocab_size)),
        "layers": [
            layer(k, kind, moe)
            for k, (kind, moe) in zip(jax.random.split(k_layers, cfg.n_layers), _layers(cfg))
        ],
        "final_norm": jnp.ones((cfg.dim,), cfg.dtype),
    }


def param_count(cfg: Lfm2Config) -> int:
    layers = sum(
        sum(math.prod(s) for s in _layer_shapes(cfg, kind, moe).values()) for kind, moe in _layers(cfg)
    )
    return cfg.vocab_size * cfg.dim + layers + cfg.dim


# ---------------------------------------------------------------------------
# the pieces of a layer


def _rope(cfg: Lfm2Config, x, pos):
    """``x [B, C, heads, hd]`` rotated at int positions ``pos [B, C]``: lane
    ``i`` pairs with lane ``i + hd / 2`` (the halves convention)."""
    half = cfg.head_dim // 2
    inv_freq = 1.0 / (cfg.rope_theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _qkv(cfg: Lfm2Config, p, h, pos):
    """The projections of one attention on normed activations ``h [B, C,
    D]``: ``(q [B, C, H, hd], k, v [B, C, KV, hd])``, q and k normalised a
    HEAD (over its ``hd`` numbers; ``models/llama.py``'s ``qk_norm`` is over
    the whole projection: another equation) and rotated at ``pos``."""
    q = jnp.einsum("bcd,dhk->bchk", h, p["wq"])
    k = jnp.einsum("bcd,dhk->bchk", h, p["wk"])
    v = jnp.einsum("bcd,dhk->bchk", h, p["wv"])
    q = _rope(cfg, rms_norm(q, p["q_norm"], cfg.norm_eps), pos)
    k = _rope(cfg, rms_norm(k, p["k_norm"], cfg.norm_eps), pos)
    return q, k, v


def _conv_inputs(p, h):
    """``(z, gate)`` of one convolution layer on normed activations ``h [...,
    D]``: the convolution's input ``B * X`` and the output's gate ``C``."""
    with jax.named_scope("conv.in"):
        b, c, x = jnp.split(h @ p["conv_in"], 3, axis=-1)
        return b * x, c


def _conv_output(p, gate, c):
    with jax.named_scope("conv.out"):
        return (gate * c.astype(gate.dtype)) @ p["conv_out"]


def _conv_chunk(p, h, tail, true_len):
    """The convolution mixer over a window of several positions ``h [B, C,
    D]`` from ``tail [B, K - 1, D]``: ``(out [B, C, D], tail)`` behind the
    first ``true_len [B]`` rows."""
    z, gate = _conv_inputs(p, h)
    with jax.named_scope("conv.taps"):
        c, tail = short_conv.chunk(z, tail, p["conv_taps"], true_len)
    return _conv_output(p, gate, c), tail


def _conv_step(p, h, pool, layer: int, slots, fresh):
    """The convolution mixer one position a slot, ``h [B, D]``, in place in
    the layer's slab of the pool (``ops/short_conv.py::step``): ``(out [B, D], pool)``."""
    z, gate = _conv_inputs(p, h)
    with jax.named_scope("conv.taps"):
        c, pool = short_conv.step(pool, layer, slots, z, p["conv_taps"], fresh)
    return _conv_output(p, gate, c), pool


def _ffn(cfg: Lfm2Config, p, h, valid, moe: bool):
    """The FFN of one layer on normed activations ``h [B, C, D]``: ``(ffn(h),
    aux)``: a dense layer the gated SiLU MLP; an expert layer
    ``ops/moe.py::routed_ffn``, no shared expert."""
    if not moe:
        return gated_mlp(h, p["w_gate"], p["w_up"], p["w_down"]), {}
    return routed_ffn(
        p, h, valid, top_k=cfg.moe_top_k, scale=cfg.routed_scaling_factor, held=cfg.held_experts, shared=False
    )


# ---------------------------------------------------------------------------
# forward (the full sequence: the tests' other side; no cache, no slots)


def forward(cfg: Lfm2Config, params, tokens, *, remat=False, mesh=None, rules=None,
            return_aux: bool = False):
    """tokens [B, S] int32 -> logits [B, S, vocab] (f32): every convolution
    from a zero tail, every attention causal over the sequence itself."""
    del remat
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    causal = jnp.tril(jnp.ones((S, S), bool))
    rep = cfg.n_heads // cfg.n_kv_heads
    x = constrain(params["embed"], mesh, rules, (None, None))[tokens]
    aux = []
    for p, (kind, moe) in zip(params["layers"], _layers(cfg)):
        h = rms_norm(x, p["operator_norm"], cfg.norm_eps)
        if kind == "conv":
            tail = jnp.zeros((B, cfg.conv_kernel - 1, cfg.dim), h.dtype)
            mix, _ = _conv_chunk(p, h, tail, jnp.full((B,), S, jnp.int32))
        else:
            q, k, v = _qkv(cfg, p, h, pos)
            s = jnp.einsum("bcgrh,bsgh->bgrcs", q.reshape(B, S, cfg.n_kv_heads, rep, -1), k).astype(F32)
            s = jnp.where(causal, s * cfg.head_dim ** -0.5, -1e30)
            o = jnp.einsum("bgrcs,bsgh->bcgrh", jax.nn.softmax(s, axis=-1).astype(v.dtype), v)
            mix = jnp.einsum("bchk,hkd->bcd", o.reshape(B, S, cfg.n_heads, -1), p["wo"])
        x = x + mix
        y, a = _ffn(cfg, p, rms_norm(x, p["ffn_norm"], cfg.norm_eps), None, moe)
        x = x + y
        if a:
            aux.append(a)
    logits = constrain(lm_head(params, x, cfg.norm_eps, tied=True), mesh, rules, ("act_batch", "act_seq", "act_vocab"))
    if return_aux:
        return logits, (stack_aux(aux)["aux_loss"].sum() if aux else jnp.zeros((), F32))
    return logits


# ---------------------------------------------------------------------------
# the two pools and the serving steps over ONE body
#
# ``cache["k"]``, ``cache["v"]`` ``[n_attn, num_blocks, block_size, n_kv x hd]``:
# the attending layers alone, a token's heads side by side in one row of whole
# lanes (12,288 B a token over 6 layers at the published widths; at widths
# that are not whole lanes, the tests', ``[.., block_size, n_kv, hd]``).
# ``state["conv_tail"] [n_conv, num_slots, (K - 1) x D]`` in the model's dtype:
# a sequence's last inputs stored as ONE row (two rows of 2048 would pad to a
# tile of 16), 147,456 B a sequence over 18 layers whatever its length. Slot 0
# is the null slot: a padding slot of a decode batch reads and writes it.


def cache_layout(cfg: Lfm2Config, block_size: int, dtype=None) -> CacheLayout:
    row = (cfg.n_kv_heads, cfg.head_dim)
    return CacheLayout(
        kind="kv", n_layers=cfg.n_attn_layers, block_size=block_size,
        arrays=(("k", row), ("v", row)), dtype=dtype or cfg.dtype,
        flat_blocks=(
            cfg.head_dim % 128 != 0 and (cfg.n_kv_heads * cfg.head_dim) % 128 == 0
            and block_size % 8 == 0
        ),
    )


def state_layout(cfg: Lfm2Config) -> StateLayout:
    return StateLayout(
        kind="short_conv", n_layers=cfg.n_conv_layers,
        arrays=(("conv_tail", ((cfg.conv_kernel - 1) * cfg.dim,), cfg.dtype),),
    )


def _shapes(cfg: Lfm2Config) -> Dict[str, int]:
    """What ``models/paged_kv.py`` is told beside the cache's shape."""
    return {"n_kv": cfg.n_kv_heads, "head_dim": cfg.head_dim}


def _attention_mix(cfg: Lfm2Config, p, cache, index: int, h, pos, valid, block_tables):
    """The attention mixer of one layer (index ``index`` of the attending
    ones) on normed activations ``h [B, C, D]`` at positions ``pos``: q / k /
    v, the head norms, the rotation, the write of the window's K and V where
    ``valid`` (``paged_kv.write_kv``: a padding row's to the null block, or, in
    a chunk written by whole blocks, nowhere), the attention over the cache
    (after the write: a window attends to itself) and ``wo``. Returns
    ``(cache, out [B, C, D])``."""
    B, C = pos.shape
    bs = paged_kv.block_size(cache["k"], **_shapes(cfg))
    at = paged_kv.rows_at(block_tables, pos, valid, bs)
    with jax.named_scope("attn.full"):
        q, k, v = _qkv(cfg, p, h, pos)
        if cache["k"].ndim == 4:  # a token's heads in one row
            k, v = k.reshape(B, C, 1, -1), v.reshape(B, C, 1, -1)
        cache = paged_kv.write_kv(cache, index, block_tables, pos, valid, k, v, at=at)
        o = paged_kv.attention_counted(
            q, cache["k"], cache["v"], index, block_tables, pos, valid.sum(axis=1, dtype=jnp.int32),
            **_shapes(cfg),
        )
        return cache, jnp.einsum("bchk,hkd->bcd", o.astype(h.dtype), p["wo"])


def _paged_layers(cfg: Lfm2Config, params, cache, state, tokens, pos, valid, block_tables, slots):
    """Every layer of the model over the two pools: the body of the serving
    steps. ``tokens [B, C]``, ``pos [B, C]`` (contiguous a slot), ``valid [B,
    C]`` (the real rows lead), ``block_tables [B, M]``, ``slots [B]``. A
    convolution layer reads its slots' tails (zeros where the slot's sequence
    starts here: ``pos[b, 0] == 0``), runs the window and writes them back in
    place; an attending layer writes the window's K and V to its blocks and
    attends over the cache. Returns ``(cache, state, x [B, C, D], aux)``."""
    real = valid.any(axis=1)
    # a padding slot is pointed at the null block and the null slot
    block_tables = jnp.where(real[:, None], block_tables, 0)
    slots = jnp.where(real, slots, 0)
    true_lens = valid.sum(axis=1, dtype=jnp.int32)
    fresh = pos[:, 0] == 0
    B, C = pos.shape
    keep, pool = cfg.conv_kernel - 1, state["conv_tail"]
    x = params["embed"][tokens]
    aux = []
    i_conv = i_attn = 0
    for p, (kind, moe) in zip(params["layers"], _layers(cfg)):
        h = rms_norm(x, p["operator_norm"], cfg.norm_eps)
        if kind == "conv" and C == 1:
            # a decode batch: the rows' own slots of the layer's slab, in place
            mix, pool = _conv_step(p, h[:, 0], pool, i_conv, slots, fresh)
            mix = mix[:, None]
            i_conv += 1
        elif kind == "conv":
            assert B == 1, "a window of several positions is ONE request's prefill chunk"
            at = (jnp.int32(i_conv), slots[0], jnp.int32(0))
            tail = jax.lax.dynamic_slice(pool, at, (1, 1, pool.shape[2]))[0]
            tail = jnp.where(fresh[0], 0, tail).reshape(1, keep, -1)
            mix, tail = _conv_chunk(p, h, tail, true_lens)
            pool = jax.lax.dynamic_update_slice(pool, tail.reshape(1, 1, -1).astype(pool.dtype), at)
            i_conv += 1
        else:
            cache, mix = _attention_mix(cfg, p, cache, i_attn, h, pos, valid, block_tables)
            i_attn += 1
        x = x + mix
        y, a = _ffn(cfg, p, rms_norm(x, p["ffn_norm"], cfg.norm_eps), valid, moe)
        x = x + y
        if a:
            aux.append(a)
    return cache, {"conv_tail": pool}, x, stack_aux(aux)


def paged_prefill_step(cfg: Lfm2Config, params, cache, state, tokens, block_table, ctx_len,
                       true_len, slot):
    """One prefill chunk for ONE request, as ``models/llama.py::
    paged_prefill_step`` with the state pool after the cache and the
    request's slot last. A chunk at ``ctx_len == 0`` starts from a zero tail
    (a re-admitted request re-derives its state from position 0)."""
    idx = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    cache, state, x, aux = _paged_layers(
        cfg, params, cache, state, tokens[None], (ctx_len + idx)[None], (idx < true_len)[None],
        block_table[None], jnp.reshape(slot, (1,)),
    )
    logits = lm_head(params, x[0, jnp.maximum(true_len - 1, 0)], cfg.norm_eps, tied=True)
    return step_outputs(cache, logits, step_counters(aux), state)


def paged_decode_step(cfg: Lfm2Config, params, cache, state, tokens, positions, block_tables,
                      ctx_lens, slots):
    """One decode step for a batch of slots, as ``models/llama.py::
    paged_decode_step`` with the state pool after the cache and the slots'
    indices ``[B]`` last (a slot whose token would be written to the null
    block is padding: it reads and writes the null slot)."""
    del ctx_lens
    pos = positions[:, None]
    valid = paged_kv.block_at(block_tables, pos, paged_kv.block_size(cache["k"], **_shapes(cfg))) != 0
    cache, state, x, aux = _paged_layers(
        cfg, params, cache, state, tokens[:, None], pos, valid, block_tables, slots
    )
    return step_outputs(cache, lm_head(params, x[:, 0], cfg.norm_eps, tied=True), step_counters(aux), state)


def paged_verify_step(cfg: Lfm2Config, *args, **kwargs):
    """Not there: a verify window over recurrent layers needs the state
    after EACH of its positions (the accepted prefix's is kept, the rest
    rolled back); the engine refuses speculation on a model with a state
    description."""
    raise NotImplementedError(
        "speculative verification is not implemented over recurrent (short-convolution) layers: "
        "the tail after each position of the window would have to be kept for the roll-back"
    )


# ---------------------------------------------------------------------------
# what the runtime knows of this module (models/interface.py)


def _program_path(cfg: Lfm2Config, window: int, cache, backend=None) -> tuple:
    return paged_kv.program_path(
        window, cache["k"], cfg.max_seq_len, (cfg.n_heads,), backend=backend, **_shapes(cfg)
    )


def _attention_path(cfg: Lfm2Config, window: int, cache, backend=None) -> AttentionPath:
    """The mixers' paths of a program of that window, named together: the
    convolution layers' (``conv.step`` one position a slot, ``conv.chunk``)
    and the attending layers' (``models/paged_kv.py::way``); what a launch
    reads of the paged cache is the latter's."""
    conv = "conv.chunk" if window > 1 else "conv.step"
    way, reads, _ = _program_path(cfg, window, cache, backend)
    return AttentionPath(f"{conv}+{way}", reads)


MODEL = Model(
    name="lfm2",
    init_params=init_params,
    forward=forward,
    logical_axes=logical_axes,
    param_count=param_count,
    cache_layout=cache_layout,
    paged_prefill_step=paged_prefill_step,
    paged_verify_step=paged_verify_step,
    paged_decode_step=paged_decode_step,
    attention_path=_attention_path,
    held_experts=lambda cfg: cfg.held_experts if cfg.n_moe_layers > 0 else None,
    key_tile=lambda cfg, window, cache: _program_path(cfg, window, cache)[2],
    state_layout=state_layout,
)
