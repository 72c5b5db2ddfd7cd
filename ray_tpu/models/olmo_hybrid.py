"""Olmo-Hybrid-family decoder LM: GATED DELTANET layers and multi-head
attention layers WITHOUT any position term, 3 : 1 in one residual stream
(``layer_types``: ``linear_attention`` x 3, ``full_attention`` x 1, eight times
at the published depth), a dense gated MLP in every layer, an untied head. The
OLMo 2 / OLMo 3 block: the norm stands on each sublayer's OUTPUT::

    h   = x + RMSNorm(Mixer(x))
    out = h + RMSNorm(MLP(h)),      MLP(h) = W_down(SiLU(W_gate h) * W_up h)

What a sequence leaves behind is of TWO kinds (``models/interface.py``): a K
and a V row a token in the layers that attend (``CacheLayout`` of kind
``"kv"``: 30 KV heads of 128, 4 of 16 layers at the served depth: 61,440 B a
token) and, in the layers that recur, the matrix state of every head and the
convolution's last ``conv_kernel - 1`` inputs a SEQUENCE (``StateLayout``
``"gdn"``, 12 of 16 layers: 2,280,960 B a layer whatever the length). The
fourth kind of state in the pool, and the first configuration whose two pools
are BOTH large on one chip.

The mixers (``x [.., D]`` a layer's input, un-normed)::

    gated deltanet (H heads of dk x dv, dv = 2 dk at the published widths):
        [q~ | k~ | v~] = SiLU(conv(x W_qkv))         depthwise causal, conv_kernel taps
        q = l2norm(q~) dk^-1/2,  k = l2norm(k~),  v = v~           a head
        beta_t = 2 sigmoid(x_t W_b)                  [H]; the 2 is ``allow_neg_eigval``
        g_t = -exp(A_log) softplus(x_t W_a + dt_bias)             [H], ONE number a head
        S_t = e^{g_t} S_{t-1};  S_t += beta_t k_t (v_t - S_t^T k_t)^T;  o_t = S_t^T q_t
        out = W_o [ RMSNorm_dv(o_t) * SiLU(x_t W_g) ]
    attention (H heads over H KV heads of hd, no rotary):
        q, k = RMSNorm(x W_q), RMSNorm(x W_k)        over the WHOLE projection (QK-norm)
        causal softmax(q k^T hd^-1/2) v;  out = o W_o

Beside ``models/kimi_linear.py``'s KDA: the gate is a scalar a head where KDA's
is a vector of ``dk`` through a low-rank pair; ``beta`` reaches 2; ``dv != dk``;
the output gate is full-rank and SiLU where KDA's is low-rank and a sigmoid. The
recurrence itself is the ONE of ``ops/delta_rule.py``: a decode step applies it
once (``kda_update`` with the gate broadcast; over a decode batch on a TPU ONE
pass over the layer's slab of the pool, ``ops/kda.py``), a prefill chunk runs
the chunked (WY) form for a gate a head (``gdn_chunked``: the decays of a pair of
positions are one number, so ``A^kk`` and ``A^qk`` are matmuls; on a TPU at widths
of whole sublanes ONE kernel that keeps the state in VMEM over the sub-chunks,
``ops/gdn_chunk.py``). The convolution
is ``ops/short_conv.py``'s. The attention reads and writes the paged cache
through ``models/paged_kv.py``, the way chosen at trace time from shapes and
backend as for every K/V model: 30 KV heads of 128 under ONE query row each are
stored flat, ``[.., block_size x 30, 128]`` (a block 120 KB of K and as much of
V), which the decode kernel ``ops/paged_attention.py`` and the chunk's flash
kernel ``ops/latent_flash.py`` both serve.

THE STATE'S FORM in the pool. A head's state ``[dk, dv] = [96, 192]`` fills no
whole lane: as ``[.., H, 96, 192]`` the device stores 192 lanes as 256, a third
more bytes to hold, read and write. The pool keeps a slot's heads JOINED along
the lanes, ``gdn_state [n_gdn, slots, dk, H x dv]`` (30 x 192 = 45 x 128 lanes,
nothing padded; ``ops/kda.py`` has both forms' times on the chip); where a
step wants heads apart (the chunked form, the ``jnp`` update off the chip) it
views one slot's or one slab's state as ``[.., H, dk, dv]`` (``ops/kda.py::heads_apart``).

The layers are a Python loop (``params["layers"]``, one dict a layer): the
state pool is updated in place layer by layer in the donated argument.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import paged_kv
from ray_tpu.models.interface import AttentionPath, CacheLayout, Model, StateLayout, lm_head
from ray_tpu.ops import delta_rule, gdn_chunk, kda, short_conv
from ray_tpu.ops.layers import rms_norm
from ray_tpu.ops.moe import DENSE_AXES, gated_mlp
from ray_tpu.parallel.sharding import constrain

F32 = jnp.float32

_PERIOD = ("linear_attention",) * 3 + ("full_attention",)


@dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    dim: int = 3840
    n_layers: int = 32
    #: ``"linear_attention"`` (Gated DeltaNet) | ``"full_attention"`` a layer, as published
    layer_types: Tuple[str, ...] = _PERIOD * 8
    n_heads: int = 30
    n_kv_heads: int = 30
    head_dim: int = 128
    #: the Gated DeltaNet mixer: heads (key heads = value heads), a key's and a
    #: value's width a head, taps of the causal convolution over q, k and v
    gdn_heads: int = 30
    gdn_key_dim: int = 96
    gdn_value_dim: int = 192
    conv_kernel: int = 4
    #: ``beta`` in (0, 2): the transition ``I - beta k k^T`` may have an eigenvalue in (-1, 1)
    allow_neg_eigval: bool = True
    #: positions a sub-chunk of the chunked form
    gdn_chunk: int = 64
    mlp_hidden: int = 11008
    max_seq_len: int = 65536
    norm_eps: float = 1e-6
    dtype: Any = jnp.float32

    def __post_init__(self):
        if len(self.layer_types) != self.n_layers or set(self.layer_types) - set(_PERIOD):
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers of kinds {sorted(set(self.layer_types))}: "
                f"a model of {self.n_layers} layers wants one of {sorted(set(_PERIOD))} each"
            )

    @property
    def n_attn_layers(self) -> int:
        return self.layer_types.count("full_attention")

    @property
    def n_gdn_layers(self) -> int:
        return self.layer_types.count("linear_attention")

    @property
    def key_width(self) -> int:
        """Channels of each of q and k in a Gated DeltaNet layer."""
        return self.gdn_heads * self.gdn_key_dim

    @property
    def value_width(self) -> int:
        """Channels of v (and of the output gate) in a Gated DeltaNet layer."""
        return self.gdn_heads * self.gdn_value_dim

    @property
    def conv_width(self) -> int:
        """Channels the convolution runs over: q, k and v side by side."""
        return 2 * self.key_width + self.value_width

    @staticmethod
    def tiny(**overrides) -> "OlmoHybridConfig":
        """CI-sized config: two periods less a layer (G G G A | G G A), 3 heads,
        a state of 8 x 16 a head (``dv = 2 dk``, no whole lane)."""
        base = dict(
            vocab_size=256, dim=48, n_layers=7, layer_types=_PERIOD + _PERIOD[1:], n_heads=3, n_kv_heads=3,
            head_dim=16, gdn_heads=3, gdn_key_dim=8, gdn_value_dim=16, gdn_chunk=8, mlp_hidden=96,
            max_seq_len=64,
        )
        base.update(overrides)
        return OlmoHybridConfig(**base)


# ---------------------------------------------------------------------------
# params (one dict a layer) + logical axes


def _layer_shapes(cfg: OlmoHybridConfig, kind: str) -> Dict[str, Tuple[int, ...]]:
    D, H, hd = cfg.dim, cfg.n_heads, cfg.head_dim
    if kind == "linear_attention":
        shapes: Dict[str, Tuple[int, ...]] = {
            "gdn_wqkv": (D, cfg.conv_width), "gdn_conv": (cfg.conv_kernel, cfg.conv_width),
            "gdn_wab": (D, 2 * cfg.gdn_heads), "gdn_a_log": (cfg.gdn_heads,), "gdn_dt_bias": (cfg.gdn_heads,),
            "gdn_wg": (D, cfg.value_width), "gdn_o_norm": (cfg.gdn_value_dim,), "gdn_wo": (cfg.value_width, D),
        }
    else:
        shapes = {
            "wq": (D, H, hd), "wk": (D, cfg.n_kv_heads, hd), "wv": (D, cfg.n_kv_heads, hd),
            "q_norm": (H * hd,), "k_norm": (cfg.n_kv_heads * hd,), "wo": (H, hd, D),
        }
    shapes.update({
        "mixer_norm": (D,), "w_gate": (D, cfg.mlp_hidden), "w_up": (D, cfg.mlp_hidden),
        "w_down": (cfg.mlp_hidden, D), "mlp_norm": (D,),
    })
    return shapes


_AXES = {
    "gdn_wqkv": ("embed", None), "gdn_wg": ("embed", None), "gdn_wo": (None, "embed"),
    "wq": ("embed", "heads", "head_dim"), "wk": ("embed", "kv_heads", "head_dim"),
    "wv": ("embed", "kv_heads", "head_dim"), "wo": ("heads", "head_dim", "embed"), **DENSE_AXES,
}


def logical_axes(cfg: OlmoHybridConfig) -> Dict[str, Any]:
    """Pytree (same structure as params) of logical-axis-name tuples."""
    layers = [
        {k: _AXES.get(k, (None,) * len(shape)) for k, shape in _layer_shapes(cfg, kind).items()}
        for kind in cfg.layer_types
    ]
    return {"embed": ("vocab", "embed"), "layers": layers, "final_norm": (None,),
            "lm_head": ("embed", "vocab")}


def init_params(cfg: OlmoHybridConfig, rng: jax.Array) -> Dict[str, Any]:
    """Seeded weights under which what is new MATTERS. Projections normal /
    sqrt(fan-in) in ``cfg.dtype``; the embedding normal (unit rows: beside it
    every sublayer ADDS a normed vector); the norm on a sublayer's output 1 /
    sqrt(2 x layers) (the sublayers' sum then weighs what the embedding
    weighs, and the stream's size stays about 1 to 1.5 through the depth:
    with 1 the Gated DeltaNet layers' inputs, which no norm precedes, would
    grow with the square root of the depth and push every gate to its end);
    the head norm, the QK-norms and the final norm 1. Gated DeltaNet: ``A =
    exp(A_log)`` uniform in [1, 16] a head and ``dt_bias`` the inverse softplus
    of a log-uniform draw in [1e-3, 1e-1] a head (the published family's law,
    as ``models/kimi_linear.py``), float32, and the decay gate's projection
    half the usual size, so that the slow heads of a state outlive a prefill
    chunk (``e^-1`` over 1024 positions at ``A dt = 1e-3``) while the fast ones
    forget in a token: a fault at a chunk's edge shows. The filter's taps normal
    / sqrt(taps)."""
    with jax.threefry_partitionable(True):
        return _init_params(cfg, rng)


def _init_params(cfg: OlmoHybridConfig, rng: jax.Array) -> Dict[str, Any]:
    k_embed, k_head, k_layers = jax.random.split(rng, 3)

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
            if name in ("mixer_norm", "mlp_norm"):
                out[name] = jnp.full(shape, 1.0 / math.sqrt(2 * cfg.n_layers), cfg.dtype)
            elif name.endswith("norm"):
                out[name] = jnp.ones(shape, cfg.dtype)
            elif name == "gdn_a_log":
                out[name] = jnp.log(jax.random.uniform(k, shape, F32, 1.0, 16.0))
            elif name == "gdn_dt_bias":
                dt = jnp.exp(jax.random.uniform(k, shape, F32, math.log(1e-3), math.log(1e-1)))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
            elif name == "gdn_wab":
                k_a, k_b = jax.random.split(k)
                half = (shape[0], shape[1] // 2)
                out[name] = jnp.concatenate(
                    [dense(k_a, half, 4 * shape[0]), dense(k_b, half, shape[0])], axis=1
                )
            else:
                # contraction dims: the taps of the filter; heads x hd of ``wo``;
                # the first of every other projection
                out[name] = dense(k, shape, shape[0] * shape[1] if name == "wo" else shape[0])
        return out

    return {
        "embed": dense(k_embed, (cfg.vocab_size, cfg.dim), 1, slices=math.gcd(16, cfg.vocab_size)),
        "layers": [
            layer(k, kind) for k, kind in zip(jax.random.split(k_layers, cfg.n_layers), cfg.layer_types)
        ],
        "final_norm": jnp.ones((cfg.dim,), cfg.dtype),
        "lm_head": dense(k_head, (cfg.dim, cfg.vocab_size), cfg.dim, slices=math.gcd(16, cfg.dim)),
    }


def param_count(cfg: OlmoHybridConfig) -> int:
    layers = sum(sum(math.prod(s) for s in _layer_shapes(cfg, kind).values()) for kind in cfg.layer_types)
    return 2 * cfg.vocab_size * cfg.dim + layers + cfg.dim


# ---------------------------------------------------------------------------
# the pieces of a layer


def _heads(x, heads: int):
    return x.reshape(*x.shape[:-1], heads, x.shape[-1] // heads)


def _once(value, window: int):
    """``value`` of a prefill chunk (``window`` positions) made ONCE on a TPU, behind
    an optimization barrier. The compiler's rematerialisation runs on every
    program whose donated pools, counted as arguments AND as results, pass the
    device's memory (this model's 6.7 GB of pools beside 8.2 GB of weights do),
    can never get under that limit, and so recomputes what it can for each
    consumer: in the parent's chunk of 1024 the product ``x W_qkv`` three and four
    times a layer, the convolution behind it twice, a layer's write of K and V
    twice: 24 of 110 ms (PERF.md, PR 65). What stands behind a barrier is not
    recomputed. A decode step's program, and every program off a TPU, is what
    it was."""
    return jax.lax.optimization_barrier(value) if window > 1 and jax.default_backend() == "tpu" else value


def _gdn_inputs(cfg: OlmoHybridConfig, p, x, tail, valid):
    """Everything a Gated DeltaNet layer's recurrence takes, from the layer's
    input ``x [B, C, D]``, the last ``conv_kernel - 1`` inputs of the
    convolution before the window ``tail [B, K - 1, 2 Wk + Wv]`` and ``valid
    [B, C]``: ``(q, k [B, C, H, dk], v [B, C, H, dv], g, beta [B, C, H])``
    float32 with ``g = 0`` and ``beta = 0`` on the rows that are not real, and
    ``window [B, K - 1 + C, 2 Wk + Wv]``, the convolution's inputs with the tail
    in front (the next tail is cut from it)."""
    H, Wk = cfg.gdn_heads, cfg.key_width
    with jax.named_scope("gdn.conv"):
        window = _once(jnp.concatenate([tail, x @ p["gdn_wqkv"]], axis=1), x.shape[1])
        mixed = _once(jax.nn.silu(short_conv.taps_over(window, p["gdn_conv"], x.shape[1])), x.shape[1])
        q, k, v = (_heads(a, H) for a in jnp.split(mixed, (Wk, 2 * Wk), axis=-1))
        q, k = delta_rule._l2_norm(q) * cfg.gdn_key_dim ** -0.5, delta_rule._l2_norm(k)
    with jax.named_scope("gdn.gate"):
        a, b = jnp.split((x @ p["gdn_wab"]).astype(F32), 2, axis=-1)
        g = -jnp.exp(p["gdn_a_log"]) * jax.nn.softplus(a + p["gdn_dt_bias"])
        beta = (2.0 if cfg.allow_neg_eigval else 1.0) * jax.nn.sigmoid(b)
        g = jnp.where(valid[..., None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
    return q, k, v, g, beta, window


def _gdn_output(cfg: OlmoHybridConfig, p, x, o):
    """``W_o [ RMSNorm_dv(o) * SiLU(x W_g) ]``: ``o [B, C, H, dv]`` float32 -> ``[B, C, D]``."""
    with jax.named_scope("gdn.out"):
        inv = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
        gate = jax.nn.silu((x @ p["gdn_wg"]).astype(F32))
        y = (o * inv).astype(x.dtype) * p["gdn_o_norm"] * _heads(gate, cfg.gdn_heads).astype(x.dtype)
        return y.reshape(*y.shape[:2], -1) @ p["gdn_wo"]


def _gdn_recur(cfg: OlmoHybridConfig, S, q, k, v, g, beta):
    """The recurrence over a window from a state ``S [B, H, dk, dv]``: ``(S,
    o [B, C, H, dv])`` after it. One position a slot: the recurrence once;
    more: the chunked form for a gate a head, through the kernel of
    ``ops/gdn_chunk.py`` where it serves the shapes and the backend."""
    C = q.shape[1]
    if C == 1:
        with jax.named_scope("gdn.update"):
            S, o = delta_rule.kda_update(S, q[:, 0], k[:, 0], v[:, 0], g[:, 0, :, None], beta[:, 0])
            return S, o[:, None]
    with jax.named_scope("gdn.chunk"):
        chunk = min(cfg.gdn_chunk, C)
        pad = -C % chunk  # positions past the window: beta = 0, g = 0, nothing moves
        if pad:
            q, k, v, g, beta = (
                jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (q, k, v, g, beta)
            )
        in_kernel = gdn_chunk.kernel_serves(S, q, v, chunk)
        S, o = (gdn_chunk.chunked if in_kernel else delta_rule.gdn_chunked)(S, q, k, v, g, beta, chunk)
        return S, o[:, :C]


def _gdn_in_pool(layer: int, fresh, pool, q, k, v, g, beta):
    """The recurrence once for EVERY slot of the pool ``[n_gdn, slots, dk, H
    x dv]`` (one position a slot, in slot order), in place in the layer's slab
    through the kernel of ``ops/kda.py``: ``(pool, o [slots, 1, H, dv])``. A
    slot that is ``fresh`` starts from zeros whatever lies there."""
    with jax.named_scope("gdn.kernel"):
        pool, o = kda.update(pool, layer, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], fresh)
        return pool, o[:, None]


def _gdn_mix(cfg: OlmoHybridConfig, p, x, S, tail, valid, recur=None):
    """The Gated DeltaNet mixer of one layer on its input ``x [B, C, D]`` from
    a state ``S [B, H, dk, dv]`` and a convolution tail ``[B, K - 1, 2 Wk +
    Wv]``: ``(out [B, C, D], S, tail)`` after the window's real rows (the first
    ``valid.sum(1)`` of each slot). ``recur(S, q, k, v, g, beta) -> (S, o [B, C,
    H, dv])`` is the recurrence over the window: :func:`_gdn_recur` unless given
    (a decode batch hands the pool as ``S`` and :func:`_gdn_in_pool`); the
    convolution, the gates and the output are the same around either."""
    q, k, v, g, beta, window = _gdn_inputs(cfg, p, x, tail, valid)
    S, o = (recur or functools.partial(_gdn_recur, cfg))(S, q, k, v, g, beta)
    keep = cfg.conv_kernel - 1
    if x.shape[1] == 1:  # a slot moves on by its one row or stands still: a select, not a gather a slot
        tail = jnp.where(valid[:, :, None], window[:, 1:], window[:, :keep])
    else:
        tail = short_conv.next_tail(window, valid.sum(axis=1, dtype=jnp.int32), keep)
    return _gdn_output(cfg, p, x, o), S, tail


def _qkv(cfg: OlmoHybridConfig, p, x):
    """The projections of one attention on the layer's input ``x [B, C, D]``:
    ``(q [B, C, H, hd], k, v [B, C, KV, hd])``, q and k normed over the WHOLE
    projection (the OLMo family's QK-norm); no position term."""
    q = jnp.einsum("bcd,dhk->bchk", x, p["wq"])
    k = jnp.einsum("bcd,dhk->bchk", x, p["wk"])
    v = jnp.einsum("bcd,dhk->bchk", x, p["wv"])
    lead = x.shape[:2]
    q = rms_norm(q.reshape(*lead, -1), p["q_norm"], cfg.norm_eps).reshape(q.shape)
    k = rms_norm(k.reshape(*lead, -1), p["k_norm"], cfg.norm_eps).reshape(k.shape)
    return q, k, v


def _mlp(cfg: OlmoHybridConfig, p, h):
    with jax.named_scope("mlp"):
        return rms_norm(gated_mlp(h, p["w_gate"], p["w_up"], p["w_down"]), p["mlp_norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# forward (the full sequence: the tests' other side; no cache, no slots)


def forward(cfg: OlmoHybridConfig, params, tokens, *, remat=False, mesh=None, rules=None,
            return_aux: bool = False):
    """tokens [B, S] int32 -> logits [B, S, vocab] (f32): every Gated DeltaNet
    layer from a zero state through the chunked form, every attention causal
    over the sequence itself."""
    del remat
    B, S = tokens.shape
    causal = jnp.tril(jnp.ones((S, S), bool))
    valid = jnp.ones((B, S), bool)
    rep = cfg.n_heads // cfg.n_kv_heads
    x = constrain(params["embed"], mesh, rules, (None, None))[tokens]
    for p, kind in zip(params["layers"], cfg.layer_types):
        if kind == "linear_attention":
            S0 = jnp.zeros((B, cfg.gdn_heads, cfg.gdn_key_dim, cfg.gdn_value_dim), F32)
            tail = jnp.zeros((B, cfg.conv_kernel - 1, cfg.conv_width), x.dtype)
            mix, _, _ = _gdn_mix(cfg, p, x, S0, tail, valid)
        else:
            q, k, v = _qkv(cfg, p, x)
            s = jnp.einsum("bcgrh,bsgh->bgrcs", q.reshape(B, S, cfg.n_kv_heads, rep, -1), k).astype(F32)
            s = jnp.where(causal, s * cfg.head_dim ** -0.5, -1e30)
            o = jnp.einsum("bgrcs,bsgh->bcgrh", jax.nn.softmax(s, axis=-1).astype(v.dtype), v)
            mix = jnp.einsum("bchk,hkd->bcd", o.reshape(B, S, cfg.n_heads, -1), p["wo"])
        x = x + rms_norm(mix, p["mixer_norm"], cfg.norm_eps)
        x = x + _mlp(cfg, p, x)
    logits = constrain(lm_head(params, x, cfg.norm_eps, tied=False), mesh, rules, ("act_batch", "act_seq", "act_vocab"))
    if return_aux:
        return logits, jnp.zeros((), F32)
    return logits


# ---------------------------------------------------------------------------
# the two pools and the serving steps over ONE body
#
# ``cache["k"]``, ``cache["v"]``: the attending layers alone, ``[n_attn,
# num_blocks, block_size x n_kv, hd]`` where a head is whole lanes (30 KV heads:
# a block ``[480, 128]``, 30 whole bf16 tiles, nothing padded: 61,440 B a token
# over 4 layers at the served depth), at the tests' toy widths ``[.., block_size,
# n_kv, hd]``. ``state["gdn_state"] [n_gdn, num_slots, dk, H x dv]`` float32 (a
# slot's heads joined along the lanes: the module's docstring) and
# ``state["gdn_conv"] [n_gdn, num_slots, (K - 1) x (2 Wk + Wv)]`` in the model's
# dtype (a sequence's last inputs stored as ONE row): 27,371,520 B a sequence
# over 12 layers whatever its length. Slot 0 is the null slot: a padding slot of
# a decode batch reads and writes it.


def cache_layout(cfg: OlmoHybridConfig, block_size: int, dtype=None) -> CacheLayout:
    row = (cfg.n_kv_heads, cfg.head_dim)
    return CacheLayout(
        kind="kv", n_layers=cfg.n_attn_layers, block_size=block_size,
        arrays=(("k", row), ("v", row)), dtype=dtype or cfg.dtype,
        flat_blocks=(
            cfg.head_dim % 128 == 0 and cfg.n_kv_heads % 8 != 0
            and (block_size * cfg.n_kv_heads) % 16 == 0
        ),
    )


def state_layout(cfg: OlmoHybridConfig) -> StateLayout:
    return StateLayout(
        kind="gdn", n_layers=cfg.n_gdn_layers,
        arrays=(
            ("gdn_state", (cfg.gdn_key_dim, cfg.value_width), F32),
            ("gdn_conv", ((cfg.conv_kernel - 1) * cfg.conv_width,), cfg.dtype),
        ),
    )


def _shapes(cfg: OlmoHybridConfig) -> Dict[str, int]:
    """What ``models/paged_kv.py`` is told beside the cache's shape."""
    return {"n_kv": cfg.n_kv_heads, "head_dim": cfg.head_dim}


def _attention_mix(cfg: OlmoHybridConfig, p, cache, index: int, x, pos, valid, block_tables):
    """The attention mixer of one layer (index ``index`` of the attending
    ones) on its input ``x [B, C, D]`` at positions ``pos`` (which place the
    rows in the cache and bound what a query sees, and enter nothing else): q /
    k / v, the write of the window's K and V where ``valid``
    (``paged_kv.write_kv``: a padding row's to the null block, or, in a chunk
    written by whole blocks, nowhere), the attention over the cache (after the
    write: a window attends to itself) and ``wo``. Returns ``(cache, out [B, C, D])``."""
    bs = paged_kv.block_size(cache["k"], **_shapes(cfg))
    at = paged_kv.rows_at(block_tables, pos, valid, bs)
    with jax.named_scope("attn.full"):
        q, k, v = _qkv(cfg, p, x)
        cache = _once(paged_kv.write_kv(cache, index, block_tables, pos, valid, k, v, at=at), x.shape[1])
        o = paged_kv.attention(q, cache["k"], cache["v"], index, block_tables, pos, valid, **_shapes(cfg))
        return cache, jnp.einsum("bchk,hkd->bcd", o.astype(x.dtype), p["wo"])


def _slot_rows(state, names, layer: int, slot, fresh):
    """A prefill chunk's view of the state pool, as ``delta_rule.slot_state``:
    one layer's rows of ONE slot in each array of ``names``, ``[1, *shape]``,
    zeros where ``fresh``. By ONE dynamic slice of the pool an array: behind
    ``a[layer]`` the slice of the slot is a copy of the layer's WHOLE slab first
    (144 MB of a pool of 65 slots: 0.43 ms a layer on a v5e, and held beside a
    program whose memory is full: PERF.md, PR 65)."""
    out = []
    for a in (state[name] for name in names):
        start = (jnp.int32(layer), slot) + (jnp.int32(0),) * (a.ndim - 2)
        rows = jax.lax.dynamic_slice(a, start, (1, 1, *a.shape[2:]))[0]
        out.append(jnp.where(fresh, jnp.zeros_like(rows), rows))
    return out


def _paged_layers(cfg: OlmoHybridConfig, params, cache, state, tokens, pos, valid, block_tables, slots):
    """Every layer of the model over the two pools: the body of the serving
    steps. ``tokens [B, C]``, ``pos [B, C]`` (contiguous a slot), ``valid [B,
    C]`` (the real rows lead), ``block_tables [B, M]``, ``slots [B]``. A Gated
    DeltaNet layer reads its slots' state (zeros where the slot's sequence
    starts here: ``pos[b, 0] == 0``), runs the window and writes the state back
    in place; an attending layer writes the window's K and V to its blocks and
    attends over the cache. Returns ``(cache, state, x [B, C, D])``."""
    real = valid.any(axis=1)
    # a padding slot is pointed at the null block and the null slot
    block_tables = jnp.where(real[:, None], block_tables, 0)
    slots = jnp.where(real, slots, 0)
    fresh = pos[:, 0] == 0
    H, keep = cfg.gdn_heads, cfg.conv_kernel - 1
    # one position a slot over many slots (decode) works on the pool in slot order
    by_slot, n_slots = pos.shape[1] == 1 and pos.shape[0] > 1, state["gdn_state"].shape[1]
    if by_slot:
        row_of, held = delta_rule.rows_of_slots(slots, real, n_slots)
        fresh_of = fresh[row_of] & held
        in_kernel = kda.kernel_serves(state["gdn_state"], heads=H)
    x = params["embed"][tokens]
    i_gdn = i_attn = 0
    for p, kind in zip(params["layers"], cfg.layer_types):
        if kind == "linear_attention" and by_slot:
            # a decode batch: the layer's WHOLE slab of the pool in slot order,
            # read once and written once where it lies, the rows' inputs carried to
            # their slots and the mixer's output back (a slot nobody holds has no
            # valid row: beta = 0, g = 0, nothing of it moves). ``S``: the slab,
            # heads apart, with zeros where a sequence starts; where the kernel
            # serves, the POOL itself, which comes back with the slab updated
            pool = state["gdn_state"]
            S = pool if in_kernel else kda.heads_apart(jnp.where(fresh_of[:, None, None], 0.0, pool[i_gdn]), H)
            tail = jnp.where(fresh_of[:, None], 0, state["gdn_conv"][i_gdn])
            mix, S, tail = _gdn_mix(
                cfg, p, x[row_of], S, tail.reshape(n_slots, keep, -1), held[:, None],
                functools.partial(_gdn_in_pool, i_gdn, fresh_of) if in_kernel else None,
            )
            if not in_kernel:
                S = kda.heads_joined(S)
                if i_gdn == 0 and jax.default_backend() == "tpu":
                    # the first in-place write into the donated pool a plain copy:
                    # ``models/kimi_linear.py::_paged_layers`` says why
                    S, tail = jax.lax.optimization_barrier((S, tail))
                S = pool.at[i_gdn].set(S)
            state = {"gdn_state": S, "gdn_conv": state["gdn_conv"].at[i_gdn].set(tail.reshape(n_slots, -1))}
            mix = mix[slots]
            i_gdn += 1
        elif kind == "linear_attention":
            assert pos.shape[0] == 1, "a window of several positions is ONE request's prefill chunk"
            S, tail = _slot_rows(state, ("gdn_state", "gdn_conv"), i_gdn, slots[0], fresh[0])
            mix, S, tail = _gdn_mix(cfg, p, x, kda.heads_apart(S, H), tail.reshape(1, keep, -1), valid)
            state = delta_rule.write_slot_state(
                state, i_gdn, slots[0], {"gdn_state": kda.heads_joined(S), "gdn_conv": tail.reshape(1, -1)}
            )
            i_gdn += 1
        else:
            cache, mix = _attention_mix(cfg, p, cache, i_attn, x, pos, valid, block_tables)
            i_attn += 1
        x = x + rms_norm(mix, p["mixer_norm"], cfg.norm_eps)
        x = x + _mlp(cfg, p, x)
    return cache, state, x


def paged_prefill_step(cfg: OlmoHybridConfig, params, cache, state, tokens, block_table, ctx_len,
                       true_len, slot):
    """One prefill chunk for ONE request, as ``models/llama.py::
    paged_prefill_step`` with the state pool after the cache and the
    request's slot last. A chunk at ``ctx_len == 0`` starts from a zero
    state (a re-admitted request re-derives its state from position 0)."""
    idx = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    cache, state, x = _paged_layers(
        cfg, params, cache, state, tokens[None], (ctx_len + idx)[None], (idx < true_len)[None],
        block_table[None], jnp.reshape(slot, (1,)),
    )
    return cache, state, lm_head(params, x[0, jnp.maximum(true_len - 1, 0)], cfg.norm_eps, tied=False)


def paged_decode_step(cfg: OlmoHybridConfig, params, cache, state, tokens, positions, block_tables,
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
    return cache, state, lm_head(params, x[:, 0], cfg.norm_eps, tied=False)


def paged_verify_step(cfg: OlmoHybridConfig, *args, **kwargs):
    """Not there: a verify window over recurrent layers needs the state
    after EACH of its positions (the accepted prefix's is kept, the rest
    rolled back); the engine refuses speculation on a model with a state
    description."""
    raise NotImplementedError(
        "speculative verification is not implemented over recurrent (Gated DeltaNet) layers: the "
        "state after each position of the window would have to be kept for the roll-back"
    )


# ---------------------------------------------------------------------------
# what the runtime knows of this module (models/interface.py)


def _program_path(cfg: OlmoHybridConfig, window: int, cache, backend=None) -> tuple:
    return paged_kv.program_path(
        window, cache["k"], cfg.max_seq_len, (cfg.n_heads,), backend=backend, **_shapes(cfg)
    )


def _attention_path(cfg: OlmoHybridConfig, window: int, cache, backend=None) -> AttentionPath:
    """The mixers' paths of a program of that window, named together: the
    Gated DeltaNet layers' (one position a slot: ``gdn.kernel`` where
    ``ops/kda.py`` serves the pool, else ``gdn.update``; a chunk: ``gdn.chunk_kernel``
    where ``ops/gdn_chunk.py`` serves a slot's state, else ``gdn.chunk``) and the
    attending layers' (``models/paged_kv.py::way``, as ``kv.<way>``); what a
    launch reads of the paged cache is the latter's."""
    if window > 1:  # one slot's chunk, as ``_gdn_recur`` hands it over
        H, chunk = cfg.gdn_heads, min(cfg.gdn_chunk, window)
        f32 = lambda *width: jax.ShapeDtypeStruct((1, -(-window // chunk) * chunk, H, *width), F32)  # noqa: E731
        S = jax.ShapeDtypeStruct((1, H, cfg.gdn_key_dim, cfg.gdn_value_dim), F32)
        in_kernel = gdn_chunk.kernel_serves(S, f32(cfg.gdn_key_dim), f32(cfg.gdn_value_dim), chunk, backend)
        gdn = "gdn.chunk_kernel" if in_kernel else "gdn.chunk"
    else:
        (_, shape, dtype), _ = state_layout(cfg).arrays
        pool = jax.ShapeDtypeStruct((cfg.n_gdn_layers, 1, *shape), dtype)  # any number of slots
        gdn = "gdn.kernel" if kda.kernel_serves(pool, backend, heads=cfg.gdn_heads) else "gdn.update"
    way, reads, _ = _program_path(cfg, window, cache, backend)
    return AttentionPath(f"{gdn}+kv.{way}", reads)


MODEL = Model(
    name="olmo_hybrid",
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
