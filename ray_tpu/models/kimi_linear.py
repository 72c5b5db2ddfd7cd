"""Kimi-Linear-family decoder LM: gated delta-rule layers (KDA) and latent
attention layers (MLA, NoPE) alternating 3 : 1 in one pre-norm residual
stream, a leading dense MLP and then sigmoid-routed experts beside a shared
expert, of which this process may hold a range (one chip's share of an
expert-parallel deployment).

What a sequence leaves behind is of TWO kinds (``models/interface.py``):
rows a token in the layers that attend (``CacheLayout``: one latent row of
``kv_lora_rank + qk_rope_head_dim`` numbers, 7 of 27 layers at the published
sizes) and fixed-size arrays a SEQUENCE in the layers that recur
(``StateLayout``: the matrix state ``S [H, dk, dv]`` float32 and the last
``conv_kernel - 1`` inputs of the short convolutions, 20 of 27 layers). The
latent attention is ``models/latent.py``'s, shared with ``models/xing4.py``;
the routing and the experts are ``ops/moe.py``'s.

A layer: ``x + mix(norm(x))``, ``x + ffn(norm(x))``. The mixers:

KDA (``H`` heads of ``dk = dv``)::

    [q | k | v] = SiLU(conv(x W_qkv))        depthwise causal, conv_kernel taps
    q, k L2-normalised a head, q x dk^-1/2
    g_t = -exp(A_log_h) softplus(W_f_up W_f_down x_t + dt_bias)   [H, dk], a CHANNEL
    beta_t = sigmoid(x_t W_beta)                                   [H]
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t
    out = W_o [ RMSNorm_head(o_t) * sigmoid(W_g_up W_g_down x_t) ]

served two ways that are the same mathematics, both of ``ops/delta_rule.py``
(the ONE place of the gated delta rule since PR 64: ``kda_update``,
``kda_chunked``, ``_unit_lower_inverse`` and ``_l2_norm`` moved there from this
module, which imports them; ``models/olmo_hybrid.py``'s Gated DeltaNet, the
same rule with one gate a head, runs its sibling ``gdn_chunked``): a decode
step applies the recurrence once (``kda_update``; over a decode batch on a TPU
the same four lines as ONE pass over the layer's slab of the pool,
``ops/kda.py``, whose kernel serves both models' states, chosen from the pool's
shape and the backend); a prefill chunk runs the chunked (WY) form in
sub-chunks of ``kda_chunk`` (``kda_chunked``): with ``G_t`` the
running sum of ``g`` inside a sub-chunk and ``S`` the state before it,

    W = (I + Diag(beta) stril(A^kk))^-1 Diag(beta) (V - (K * e^G) S)
    A^kk_ts = sum_c k_tc k_sc e^(G_tc - G_sc),   A^qk alike with q_t, s <= t
    O = (Q * e^G) S + tril(A^qk) W
    S <- Diag(e^G_last) S + (K * e^(G_last - G))^T W

every exponent non-positive, so nothing overflows however fast a channel
forgets. Past a window's real rows ``beta = 0`` and ``g = 0``: the state
stands still, and the convolution's tail is cut from the last REAL inputs.

MLA, NoPE: ``q = x W_q`` straight to ``H x (dn + dr)``; ``[c | k_shared] = x
W_kva``, ``c = rms(c)``; ``[k_nope | v] = c W_kvb`` a head; the ``dr`` shared
key dimensions are NOT rotated; scores x ``(dn + dr)^-1/2``. The cache holds
``(c, k_shared)``.

The layers are a Python loop (``params["layers"]``, one dict a layer, as
``models/llama.py``): the state pool is updated in place layer by layer in
the donated argument, which a pool carried through a scan was not.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import latent, paged_kv
from ray_tpu.models.interface import AttentionPath, Model, StateLayout
from ray_tpu.models.interface import lm_head, stack_aux, step_counters, step_outputs
from ray_tpu.ops import kda, latent_flash, short_conv
from ray_tpu.ops.delta_rule import _l2_norm, _unit_lower_inverse, kda_chunked, kda_update  # noqa: F401
from ray_tpu.ops.delta_rule import rows_of_slots as _rows_of_slots
from ray_tpu.ops.delta_rule import slot_state, write_slot_state
from ray_tpu.ops.layers import rms_norm
from ray_tpu.ops.moe import DENSE_AXES, MOE_AXES, gated_mlp, routed_ffn
from ray_tpu.parallel.sharding import constrain

F32 = jnp.float32


@dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 163840
    dim: int = 2304
    n_layers: int = 27
    #: the first ``n_dense_layers`` have a dense MLP, the rest routed experts
    n_dense_layers: int = 1
    #: the layers that ATTEND (latent attention), 1-indexed as published
    #: (``linear_attn_config.full_attn_layers``); every other layer is KDA
    mla_layers: Tuple[int, ...] = (4, 8, 12, 16, 20, 24, 27)
    n_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kda_heads: int = 32
    kda_head_dim: int = 128
    #: taps of the depthwise causal convolution over q, k and v
    conv_kernel: int = 4
    #: rank of the two low-rank gates (the decay's and the output's); the
    #: published module takes the head size
    kda_gate_rank: int = 128
    #: positions a sub-chunk of the chunked form
    kda_chunk: int = 64
    mlp_hidden: int = 9216
    moe_hidden: int = 1024
    #: how many experts the ROUTER chooses among (its width)
    n_routed_experts: int = 256
    #: the range ``(lo, hi)`` of them this process holds and computes
    held_experts: Tuple[int, int] = (0, 256)
    n_shared_experts: int = 1
    moe_top_k: int = 8
    routed_scaling_factor: float = 2.446
    max_seq_len: int = 8192
    norm_eps: float = 1e-5
    dtype: Any = jnp.float32

    @property
    def n_held(self) -> int:
        return self.held_experts[1] - self.held_experts[0]

    @property
    def latent_width(self) -> int:
        """One token's cache row in one attending layer: the normed latent and the shared key part."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def attn_scale(self) -> float:
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5

    @property
    def kinds(self) -> Tuple[str, ...]:
        """``"mla"`` | ``"kda"`` for each layer, in order."""
        return tuple("mla" if l + 1 in self.mla_layers else "kda" for l in range(self.n_layers))

    @property
    def n_mla_layers(self) -> int:
        return self.kinds.count("mla")

    @property
    def n_kda_layers(self) -> int:
        return self.kinds.count("kda")

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @property
    def kda_width(self) -> int:
        """Channels of each of q, k and v in a KDA layer."""
        return self.kda_heads * self.kda_head_dim

    @staticmethod
    def tiny(**overrides) -> "KimiLinearConfig":
        """CI-sized config: two periods (K K K A | K K A), the first layer
        dense, 8 experts of which this process holds all unless told."""
        base = dict(
            vocab_size=256, dim=64, n_layers=7, n_dense_layers=1, mla_layers=(4, 7), n_heads=4,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            kda_heads=4, kda_head_dim=16, kda_gate_rank=8, kda_chunk=8, mlp_hidden=96,
            moe_hidden=32, n_routed_experts=8, held_experts=(0, 8), moe_top_k=2, max_seq_len=64,
        )
        base.update(overrides)
        return KimiLinearConfig(**base)


# ---------------------------------------------------------------------------
# params (one dict a layer) + logical axes


def _layer_shapes(cfg: KimiLinearConfig, kind: str, moe: bool) -> Dict[str, Tuple[int, ...]]:
    D, H = cfg.dim, cfg.n_heads
    shapes: Dict[str, Tuple[int, ...]] = {"attn_norm": (D,)}
    if kind == "kda":
        W, r = cfg.kda_width, cfg.kda_gate_rank
        shapes.update({
            "kda_wqkv": (D, 3 * W), "kda_conv": (cfg.conv_kernel, 3 * W),
            "kda_f_down": (D, r), "kda_f_up": (r, W), "kda_dt_bias": (W,), "kda_a_log": (cfg.kda_heads,),
            "kda_wbeta": (D, cfg.kda_heads),
            "kda_g_down": (D, r), "kda_g_up": (r, W), "kda_o_norm": (cfg.kda_head_dim,),
            "kda_wo": (W, D),
        })
    else:
        shapes.update({
            "w_q": (D, H, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim),
            "w_kva": (D, cfg.latent_width), "kv_norm": (cfg.kv_lora_rank,),
            "w_kvb": (cfg.kv_lora_rank, H, cfg.qk_nope_head_dim + cfg.v_head_dim),
            "wo": (H, cfg.v_head_dim, D),
        })
    shapes["mlp_norm"] = (D,)
    if moe:
        Fm, Fs = cfg.moe_hidden, cfg.n_shared_experts * cfg.moe_hidden
        shapes.update({
            "router": (D, cfg.n_routed_experts), "router_bias": (cfg.n_routed_experts,),
            "w_gate": (cfg.n_held, D, Fm), "w_up": (cfg.n_held, D, Fm), "w_down": (cfg.n_held, Fm, D),
            "shared_gate": (D, Fs), "shared_up": (D, Fs), "shared_down": (Fs, D),
        })
    else:
        shapes.update({
            "w_gate": (D, cfg.mlp_hidden), "w_up": (D, cfg.mlp_hidden), "w_down": (cfg.mlp_hidden, D),
        })
    return shapes


def _layers(cfg: KimiLinearConfig) -> List[Tuple[str, bool]]:
    """``(mixer kind, is it an expert layer)`` for each layer."""
    return [(kind, l >= cfg.n_dense_layers) for l, kind in enumerate(cfg.kinds)]


_AXES = {
    "kda_wqkv": ("embed", None), "kda_wo": (None, "embed"),
    "w_q": ("embed", "heads", "head_dim"), "w_kva": ("embed", None),
    "w_kvb": (None, "heads", "head_dim"), "wo": ("heads", "head_dim", "embed"),
    "shared_gate": ("embed", "mlp"), "shared_up": ("embed", "mlp"), "shared_down": ("mlp", "embed"),
}


def logical_axes(cfg: KimiLinearConfig) -> Dict[str, Any]:
    """Pytree (same structure as params) of logical-axis-name tuples."""
    layers = []
    for kind, moe in _layers(cfg):
        own = {**_AXES, **(MOE_AXES if moe else DENSE_AXES)}
        layers.append({
            k: own.get(k, (None,) * len(shape)) for k, shape in _layer_shapes(cfg, kind, moe).items()
        })
    return {"embed": ("vocab", "embed"), "layers": layers, "final_norm": (None,),
            "lm_head": ("embed", "vocab")}


def init_params(cfg: KimiLinearConfig, rng: jax.Array) -> Dict[str, Any]:
    """Seeded weights under which what is new MATTERS. Projections and
    experts normal / sqrt(fan-in) in ``cfg.dtype``; each sublayer's LAST
    projection (``kda_wo``, ``wo``, ``w_down``, ``shared_down``) a further 1
    / sqrt(2 x layers) smaller and a ROUTED expert's an eighth of that
    (``models/xing4.py::init_params`` says why: a hard top-k over independent
    random experts through many layers; at a quarter, as there, 2 of 13 runs
    on the chip read a logit 0.19 off where the others read 0.03-0.09: 8 of
    256 experts a token flip more often than 4 of 64); the router and its bias float32,
    the bias normal x 0.03; norm vectors 1. KDA: ``A = exp(A_log)`` uniform
    in [1, 16] a head and ``dt_bias`` the inverse softplus of a log-uniform
    draw in [1e-3, 1e-1] a channel (the published family's law), float32,
    and the decay gate's up-projection half the usual size, so that the
    slow channels of a state outlive a prefill chunk (``e^-1`` over 1024
    positions at ``A dt = 1e-3``) while the fast ones forget in a token:
    a fault at a chunk's edge shows."""
    with jax.threefry_partitionable(True):
        return _init_params(cfg, rng)


def _init_params(cfg: KimiLinearConfig, rng: jax.Array) -> Dict[str, Any]:
    k_embed, k_head, k_layers = jax.random.split(rng, 3)

    def dense(key, shape, fan_in, dtype=cfg.dtype, slices: int = 1):
        """Normal / sqrt(fan_in), drawn ``slices`` slices of the leading axis
        at a time: the float32 draw of a vocabulary-sized matrix whole is
        gigabytes beside the weights being made."""
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
            elif name == "kda_a_log":
                out[name] = jnp.log(jax.random.uniform(k, shape, F32, 1.0, 16.0))
            elif name == "kda_dt_bias":
                dt = jnp.exp(jax.random.uniform(k, shape, F32, math.log(1e-3), math.log(1e-1)))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1
            elif name == "kda_conv":
                out[name] = dense(k, shape, shape[0])
            else:
                # contraction dims: all but the last of a 2-D weight; heads x
                # v of ``wo``; the rank of ``w_kvb``; an expert's own input width
                fan_in = {"w_q": shape[0], "w_kvb": shape[0], "wo": shape[0] * shape[1]}.get(
                    name, shape[-2]
                )
                if name in ("kda_wo", "wo", "w_down", "shared_down"):
                    fan_in *= 2 * cfg.n_layers
                if moe and name == "w_down":
                    fan_in *= 64  # a ROUTED expert's output an eighth of that
                if name == "kda_f_up":
                    fan_in *= 4
                out[name] = dense(k, shape, fan_in)
        return out

    v, d = math.gcd(16, cfg.vocab_size), math.gcd(16, cfg.dim)
    return {
        "embed": dense(k_embed, (cfg.vocab_size, cfg.dim), cfg.dim, slices=v),
        "layers": [
            layer(k, kind, moe)
            for k, (kind, moe) in zip(jax.random.split(k_layers, cfg.n_layers), _layers(cfg))
        ],
        "final_norm": jnp.ones((cfg.dim,), cfg.dtype),
        "lm_head": dense(k_head, (cfg.dim, cfg.vocab_size), cfg.dim, slices=d),
    }


def param_count(cfg: KimiLinearConfig) -> int:
    layers = sum(
        sum(math.prod(s) for s in _layer_shapes(cfg, kind, moe).values()) for kind, moe in _layers(cfg)
    )
    return 2 * cfg.vocab_size * cfg.dim + layers + cfg.dim


# ---------------------------------------------------------------------------
# the pieces of a layer


def _heads(x, heads: int):
    return x.reshape(*x.shape[:-1], heads, x.shape[-1] // heads)


def _kda_inputs(cfg: KimiLinearConfig, p, h, tail, valid):
    """Everything a KDA layer's recurrence takes, from normed activations
    ``h [B, C, D]``, the last ``conv_kernel - 1`` inputs of the convolution
    before the window ``tail [B, K - 1, 3 W]`` and ``valid [B, C]``: ``(q, k
    [B, C, H, dk], v [B, C, H, dv], g [B, C, H, dk], beta [B, C, H])`` float32
    with ``g = 0`` and ``beta = 0`` on the rows that are not real, and
    ``window [B, K - 1 + C, 3 W]``, the convolution's inputs with the tail in
    front (the next tail is cut from it)."""
    H = cfg.kda_heads
    with jax.named_scope("kda.conv"):
        window = jnp.concatenate([tail, h @ p["kda_wqkv"]], axis=1)
        mixed = short_conv.taps_over(window, p["kda_conv"], h.shape[1])
        q, k, v = (_heads(a, H) for a in jnp.split(jax.nn.silu(mixed), 3, axis=-1))
        q, k = _l2_norm(q) * cfg.kda_head_dim ** -0.5, _l2_norm(k)
    with jax.named_scope("kda.gate"):
        f = ((h @ p["kda_f_down"]) @ p["kda_f_up"]).astype(F32) + p["kda_dt_bias"]
        g = -jnp.exp(p["kda_a_log"])[:, None] * _heads(jax.nn.softplus(f), H)
        beta = jax.nn.sigmoid((h @ p["kda_wbeta"]).astype(F32))
        g = jnp.where(valid[..., None, None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
    return q, k, v, g, beta, window


def _kda_output(cfg: KimiLinearConfig, p, h, o):
    """``W_o [ RMSNorm_head(o) * sigmoid(W_g_up W_g_down h) ]``: ``o [B, C,
    H, dv]`` float32 -> ``[B, C, D]``."""
    with jax.named_scope("kda.out"):
        inv = jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + cfg.norm_eps)
        gate = jax.nn.sigmoid(((h @ p["kda_g_down"]) @ p["kda_g_up"]).astype(F32))
        y = (o * inv).astype(h.dtype) * p["kda_o_norm"] * _heads(gate, cfg.kda_heads).astype(h.dtype)
        return y.reshape(*y.shape[:2], -1) @ p["kda_wo"]


def _kda_recur(cfg: KimiLinearConfig, S, q, k, v, g, beta):
    """The recurrence over a window from a state ``S [B, H, dk, dv]``: ``(S,
    o [B, C, H, dv])`` after it. One position a slot: the recurrence once;
    more: the chunked form."""
    C = q.shape[1]
    if C == 1:
        with jax.named_scope("kda.update"):
            S, o = kda_update(S, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
            return S, o[:, None]
    with jax.named_scope("kda.chunk"):
        chunk = min(cfg.kda_chunk, C)
        pad = -C % chunk  # positions past the window: beta = 0, g = 0, nothing moves
        if pad:
            q, k, v, g, beta = (
                jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (q, k, v, g, beta)
            )
        S, o = kda_chunked(S, q, k, v, g, beta, chunk)
        return S, o[:, :C]


def _kda_in_pool(layer: int, fresh, pool, q, k, v, g, beta):
    """The recurrence once for EVERY slot of the pool ``[n_kda, slots, H, dk,
    dv]`` (one position a slot, in slot order), in place in the layer's slab
    through the kernel of ``ops/kda.py``: ``(pool, o [slots, 1, H, dv])``. A
    slot that is ``fresh`` starts from zeros whatever lies there."""
    with jax.named_scope("kda.kernel"):
        pool, o = kda.update(pool, layer, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], fresh)
        return pool, o[:, None]


def _kda_mix(cfg: KimiLinearConfig, p, h, S, tail, valid, recur=None):
    """The KDA mixer of one layer on normed activations ``h [B, C, D]`` from
    a state ``S [B, H, dk, dv]`` and a convolution tail ``[B, K - 1, 3 W]``:
    ``(out [B, C, D], S, tail)`` after the window's real rows (the first
    ``valid.sum(1)`` of each slot). ``recur(S, q, k, v, g, beta) -> (S, o
    [B, C, H, dv])`` is the recurrence over the window: :func:`_kda_recur`
    unless given (a decode batch hands the pool as ``S`` and
    :func:`_kda_in_pool`); the convolution, the gates and the output are the
    same around either."""
    q, k, v, g, beta, window = _kda_inputs(cfg, p, h, tail, valid)
    S, o = (recur or functools.partial(_kda_recur, cfg))(S, q, k, v, g, beta)
    keep = cfg.conv_kernel - 1
    if h.shape[1] == 1:  # a slot moves on by its one row or stands still: a select, not a gather a slot
        tail = jnp.where(valid[:, :, None], window[:, 1:], window[:, :keep])
    else:
        tail = short_conv.next_tail(window, valid.sum(axis=1, dtype=jnp.int32), keep)
    return _kda_output(cfg, p, h, o), S, tail


def _mla_qkv(cfg: KimiLinearConfig, p, h):
    """The projections of one latent attention on normed activations ``h [B,
    C, D]``: ``(q_nope [B, C, H, dn], q_shared [B, C, H, dr], row [B, C, kr +
    dr])``, the row what the cache holds of the token. Nothing is rotated."""
    dn, kr = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    with jax.named_scope("mla.q"):
        q = jnp.einsum("bcd,dhk->bchk", h, p["w_q"])
    with jax.named_scope("mla.latent"):
        ckv = h @ p["w_kva"]
        row = jnp.concatenate(
            [rms_norm(ckv[..., :kr], p["kv_norm"], cfg.norm_eps), ckv[..., kr:]], axis=-1
        )
    return q[..., :dn], q[..., dn:], row


def _ffn(cfg: KimiLinearConfig, p, h, valid, moe: bool):
    """The FFN of one layer on normed activations ``h [B, C, D]``: ``(ffn(h),
    aux)``: a dense layer the gated SiLU MLP; an expert layer
    ``ops/moe.py::routed_ffn`` with the shared expert."""
    if not moe:
        return gated_mlp(h, p["w_gate"], p["w_up"], p["w_down"]), {}
    return routed_ffn(
        p, h, valid, top_k=cfg.moe_top_k, scale=cfg.routed_scaling_factor, held=cfg.held_experts, shared=True
    )


# ---------------------------------------------------------------------------
# forward (the full sequence: the tests' other side; no cache, no slots)


def forward(cfg: KimiLinearConfig, params, tokens, *, remat=False, mesh=None, rules=None,
            return_aux: bool = False):
    """tokens [B, S] int32 -> logits [B, S, vocab] (f32): every KDA layer
    from a zero state through the chunked form, every attention causal over
    the sequence itself with K and V expanded."""
    del remat
    B, S = tokens.shape
    causal = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool)), (B, S, S))
    valid = jnp.ones((B, S), bool)
    x = constrain(params["embed"], mesh, rules, (None, None))[tokens]
    aux = []
    for p, (kind, moe) in zip(params["layers"], _layers(cfg)):
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        if kind == "kda":
            S0 = jnp.zeros((B, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim), F32)
            tail = jnp.zeros((B, cfg.conv_kernel - 1, 3 * cfg.kda_width), h.dtype)
            mix, _, _ = _kda_mix(cfg, p, h, S0, tail, valid)
        else:
            q_nope, q_shared, row = _mla_qkv(cfg, p, h)
            o = latent.attend_expanded(cfg, p, q_nope, q_shared, row, causal)
            mix = jnp.einsum("bchk,hkd->bcd", o.astype(h.dtype), p["wo"])
        x = x + mix
        y, a = _ffn(cfg, p, rms_norm(x, p["mlp_norm"], cfg.norm_eps), None, moe)
        x = x + y
        if a:
            aux.append(a)
    logits = constrain(lm_head(params, x, cfg.norm_eps, tied=False), mesh, rules, ("act_batch", "act_seq", "act_vocab"))
    if return_aux:
        return logits, (stack_aux(aux)["aux_loss"].sum() if aux else jnp.zeros((), F32))
    return logits


# ---------------------------------------------------------------------------
# the two pools and the serving steps over ONE body
#
# ``cache["latent"] [n_mla, num_blocks, block_size x (kr + dr)]``: the flat-
# block latent cache of ``models/latent.py``, the attending layers alone
# (8,064 B a token over 7 layers at the published widths). ``state``:
# ``kda_state [n_kda, num_slots, H, dk, dv]`` float32 and ``kda_conv [n_kda,
# num_slots, (K - 1) x 3 W]`` in the model's dtype (a sequence's last inputs
# stored as ONE row: three rows of 12288 would pad to a tile of 16), 43.4 MB
# a sequence over 20 layers whatever its length. Slot 0 is the null slot: a
# padding slot of a decode batch reads and writes it.


def cache_layout(cfg: KimiLinearConfig, block_size: int, dtype=None):
    return latent.cache_layout(cfg, block_size, dtype, n_layers=cfg.n_mla_layers)


def state_layout(cfg: KimiLinearConfig) -> StateLayout:
    H, d = cfg.kda_heads, cfg.kda_head_dim
    return StateLayout(
        kind="kda", n_layers=cfg.n_kda_layers,
        arrays=(
            ("kda_state", (H, d, d), F32),
            ("kda_conv", ((cfg.conv_kernel - 1) * 3 * cfg.kda_width,), cfg.dtype),
        ),
    )


def _paged_layers(cfg: KimiLinearConfig, params, cache, state, tokens, pos, valid, block_tables, slots):
    """Every layer of the model over the two pools: the body of the serving
    steps. ``tokens [B, C]``, ``pos [B, C]`` (contiguous a slot), ``valid [B,
    C]`` (the real rows lead), ``block_tables [B, M]``, ``slots [B]``. A KDA
    layer reads its slots' state (zeros where the slot's sequence starts
    here: ``pos[b, 0] == 0``), runs the window and writes the state back in
    place; an attending layer reads the latent cache through
    ``latent.latent_attention`` and its blocks are written, all attending
    layers at once, after the last layer. Returns ``(cache, state, x [B, C,
    D], aux)``."""
    real = valid.any(axis=1)
    # a padding slot is pointed at the null block and the null slot
    block_tables = jnp.where(real[:, None], block_tables, 0)
    slots = jnp.where(real, slots, 0)
    true_lens = valid.sum(axis=1, dtype=jnp.int32)
    fresh = pos[:, 0] == 0
    window, bs = pos.shape[1], latent.block_size_of(cfg, cache)
    flash = not latent.absorbs(cfg, window) and latent.flash_serves(
        cfg, window, cache, block_tables.shape[1] * bs
    )
    keep = cfg.conv_kernel - 1
    # one position a slot over many slots (decode) works on the pool in slot order
    by_slot, n_slots = window == 1 and pos.shape[0] > 1, state["kda_state"].shape[1]
    if by_slot:
        row_of, held = _rows_of_slots(slots, real, n_slots)
        fresh_of = fresh[row_of] & held
        in_kernel = kda.kernel_serves(state["kda_state"])
    x = params["embed"][tokens]
    blocks, aux = [], []
    i_kda = i_mla = 0
    for p, (kind, moe) in zip(params["layers"], _layers(cfg)):
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
        if kind == "kda" and by_slot:
            # a decode batch: the layer's WHOLE slab of the pool in slot order,
            # read once and written once where it lies, the rows' activations
            # carried to their slots and the mixer's output back (a slot nobody
            # holds has no valid row: beta = 0, g = 0, nothing of it moves)
            # ``S``: the slab with zeros where a sequence starts; where the kernel
            # serves, the POOL itself, which comes back with the slab updated
            pool = state["kda_state"]
            S = pool if in_kernel else jnp.where(fresh_of[:, None, None, None], 0.0, pool[i_kda]).astype(F32)
            tail = jnp.where(fresh_of[:, None], 0, state["kda_conv"][i_kda])
            mix, S, tail = _kda_mix(
                cfg, p, h[row_of], S, tail.reshape(n_slots, keep, -1), held[:, None],
                functools.partial(_kda_in_pool, i_kda, fresh_of) if in_kernel else None,
            )
            if not in_kernel:
                if i_kda == 0 and jax.default_backend() == "tpu":
                    # The FIRST in-place write into the donated pool must be a
                    # plain copy. Fused with its own read (``S`` of this layer is
                    # a function of the pool's slab 0), it is an instruction whose
                    # only large operand is an entry parameter, and XLA:TPU's
                    # rematerialisation clones such an instruction a user (the
                    # next layer reads the pool three times) WITHOUT knowing that
                    # it runs in place on the donated buffer: each clone then
                    # decays and updates slab 0 again (PR 36: the check's state
                    # reading 1.02). Behind the barrier the write is ``pool[0] =
                    # S``: a clone of that is the same bytes again. (The kernel
                    # aliases the pool in and out: there is no fusion to clone.)
                    S, tail = jax.lax.optimization_barrier((S, tail))
                S = pool.at[i_kda].set(S.astype(pool.dtype))
            state = {"kda_state": S, "kda_conv": state["kda_conv"].at[i_kda].set(tail.reshape(n_slots, -1))}
            mix = mix[slots]
            i_kda += 1
        elif kind == "kda":
            assert pos.shape[0] == 1, "a window of several positions is ONE request's prefill chunk"
            S, tail = slot_state(state, ("kda_state", "kda_conv"), i_kda, slots[0], fresh[0])
            mix, S, tail = _kda_mix(cfg, p, h, S.astype(F32), tail.reshape(1, keep, -1), valid)
            state = write_slot_state(state, i_kda, slots[0], {"kda_state": S, "kda_conv": tail.reshape(1, -1)})
            i_kda += 1
        else:
            q_nope, q_shared, row = _mla_qkv(cfg, p, h)
            o, blk = latent.latent_attention(
                cfg, p, q_nope, q_shared, row, cache, i_mla, block_tables, pos, true_lens, flash=flash,
            )
            mix = jnp.einsum("bchk,hkd->bcd", o.astype(h.dtype), p["wo"])
            blocks.append(blk)
            i_mla += 1
        x = x + mix
        y, a = _ffn(cfg, p, rms_norm(x, p["mlp_norm"], cfg.norm_eps), valid, moe)
        x = x + y
        if a:
            aux.append(a)
    cache = latent.write_blocks(cfg, cache, block_tables, pos[:, 0], jnp.stack(blocks))
    return cache, state, x, stack_aux(aux)


def paged_prefill_step(cfg: KimiLinearConfig, params, cache, state, tokens, block_table, ctx_len,
                       true_len, slot):
    """One prefill chunk for ONE request, as ``models/llama.py::
    paged_prefill_step`` with the state pool after the cache and the
    request's slot last. A chunk at ``ctx_len == 0`` starts from a zero
    state (a re-admitted request re-derives its state from position 0)."""
    idx = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    cache, state, x, aux = _paged_layers(
        cfg, params, cache, state, tokens[None], (ctx_len + idx)[None], (idx < true_len)[None],
        block_table[None], jnp.reshape(slot, (1,)),
    )
    logits = lm_head(params, x[0, jnp.maximum(true_len - 1, 0)], cfg.norm_eps, tied=False)
    return step_outputs(cache, logits, step_counters(aux), state)


def paged_decode_step(cfg: KimiLinearConfig, params, cache, state, tokens, positions, block_tables,
                      ctx_lens, slots):
    """One decode step for a batch of slots, as ``models/llama.py::
    paged_decode_step`` with the state pool after the cache and the slots'
    indices ``[B]`` last (a slot whose token would be written to the null
    block is padding: it reads and writes the null slot)."""
    del ctx_lens
    pos = positions[:, None]
    valid = paged_kv.block_at(block_tables, pos, latent.block_size_of(cfg, cache)) != 0
    cache, state, x, aux = _paged_layers(
        cfg, params, cache, state, tokens[:, None], pos, valid, block_tables, slots
    )
    return step_outputs(cache, lm_head(params, x[:, 0], cfg.norm_eps, tied=False), step_counters(aux), state)


def paged_verify_step(cfg: KimiLinearConfig, *args, **kwargs):
    """Not there: a verify window over recurrent layers needs the state
    after EACH of its positions (the accepted prefix's is kept, the rest
    rolled back); the engine refuses speculation on a model with a state
    description."""
    raise NotImplementedError(
        "speculative verification is not implemented over recurrent (KDA) layers: the state "
        "after each position of the window would have to be kept for the roll-back"
    )


# ---------------------------------------------------------------------------
# what the runtime knows of this module (models/interface.py)


def _attention_path(cfg: KimiLinearConfig, window: int, cache, backend=None) -> AttentionPath:
    """The mixers' paths of a program of that window, named together: the
    KDA layers' (one position a slot: ``kda.kernel`` where ``ops/kda.py``
    serves the pool, else ``kda.update``; ``kda.chunk``) and the attending
    layers' (as ``models/xing4.py``); what a launch reads of the paged cache
    is the latter's."""
    (_, head_state, dtype), _ = state_layout(cfg).arrays
    pool = jax.ShapeDtypeStruct((cfg.n_kda_layers, 1, *head_state), dtype)  # any number of slots
    kda_path = "kda.chunk" if window > 1 else "kda.kernel" if kda.kernel_serves(pool, backend) else "kda.update"
    if latent.paged_serves(cfg, window, cache, backend=backend):
        return AttentionPath(f"{kda_path}+latent.paged", "blocks")
    if latent.absorbs(cfg, window):
        return AttentionPath(f"{kda_path}+latent.absorbed", "slots")
    if latent.flash_serves(cfg, window, cache, backend=backend):
        return AttentionPath(f"{kda_path}+latent.flash", "live")
    return AttentionPath(f"{kda_path}+latent.expanded", "table")


MODEL = Model(
    name="kimi_linear",
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
    key_tile=lambda cfg, window, cache: latent_flash.tiles(window, latent.table_keys(cfg, cache))[1],
    gather_rungs=latent.gather_rungs,
    state_layout=state_layout,
)
