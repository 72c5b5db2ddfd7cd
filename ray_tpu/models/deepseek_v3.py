"""DeepSeek-V3-family decoder LM (GigaChat3.1: ``model_type`` ``deepseek_v3``)
with its multi-token-prediction module kept as the DRAFTER of a serving step.

The block is ``models/xing4.py``'s WITHOUT the hyper-connected residual
(``hc_mult`` 0: the plain ``x + F(norm(x))``): latent attention over a paged
cache of latent rows (``models/latent.py``), YaRN's table, leading dense
layers, then sigmoid-routed experts beside a shared expert over a HELD range
(``ops/moe.py::dropless_moe_ffn``), the choice limited to the best
``topk_group`` of ``n_group`` groups of experts. Parameters, ``forward``, the
training step (the main model's: ``xing4.make_train_step``) and the one paged
body are that module's, run with this configuration: nothing of them is
copied here. A value head need not be as
wide as a key's nope part (``v_head_dim`` 192 beside 128 + 64).

What is new here is the MTP module (one; DeepSeek-V3, arXiv 2412.19437 section
2.2). For position ``i``, with ``h_i`` the main model's last residual BEFORE
its final norm and ``t_{i+1}`` the token after it:

    h'     = W_eh [rms_e(Emb(t_{i+1})) ; rms_h(h_i)]        # [2 D] -> D, the embedding half first
    h''    = one decoder layer of the expert kind on h'     # its own weights, its own cache row at i, rope position i
    logits = Head(rms_s(h''))                               # Emb and Head are the MAIN model's

whose argmax is the draft of ``t_{i+2}``. It writes ONE more latent row a
token (``CacheLayout.n_layers`` = the main layers + 1: its layer is the cache's
last), and it is run three ways, all over the paged body above:

* by :func:`paged_prefill_step` over a prompt's chunk, when it is told the
  token that follows the chunk (``next_token``: the prompt's own; none after
  the last chunk, whose last position's row waits for the first output token);
* by :func:`paged_mtp_step`, the ONE program of an all-greedy decode step:
  the main model verifies each slot's window ``[x_n, d]`` (the committed last
  token and the draft), the picks are taken (argmax beside the logits), the
  draft is compared, the module runs over the 1 or 2 positions that were
  committed and drafts again. The host reads three small integer arrays;
* by :func:`paged_mtp_verify` and :func:`paged_mtp_draft`, the same step as
  two programs around the host's sampler, for a batch with a sampled request:
  the first hands the logits of both rows to the host and keeps the residuals
  on the device, the second takes them back with the tokens the host sampled.

A slot that has no draft yet (the step after its prefill) rides the same
programs with the window ONE POSITION EARLIER, ``[t_{P-1}, t_P]`` both
committed (``known`` 2): row 1 gives its next token, and the module runs over
both positions, which writes the row that waited. A rejected draft's rows,
the main model's and the module's, are stale and overwritten by the next
step's window, as ``paged_verify_step``'s always were.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import latent, xing4
from ray_tpu.models.interface import CacheLayout, Drafter, Model, step_counters, step_outputs
from ray_tpu.models.xing4 import Xing4Config, forward
from ray_tpu.ops.layers import rms_norm

F32 = jnp.float32


@dataclass(frozen=True)
class DeepseekV3Config(Xing4Config):
    """``Xing4Config`` with the plain residual, the group limit and the MTP
    module; the defaults are GigaChat3.1-702B-A36B's published widths with
    every expert held."""

    vocab_size: int = 128256
    dim: int = 7168
    n_layers: int = 64
    n_dense_layers: int = 3
    n_heads: int = 64
    q_lora_rank: int = 1536
    v_head_dim: int = 192
    mlp_hidden: int = 18432
    moe_hidden: int = 2048
    n_routed_experts: int = 256
    held_experts: Tuple[int, int] = (0, 256)
    moe_top_k: int = 8
    routed_scaling_factor: float = 2.5
    n_group: int = 8
    topk_group: int = 4
    hc_mult: int = 0
    rope_theta: float = 100000.0
    #: MTP modules kept (``num_nextn_predict_layers``): 1, or 0 for none
    n_mtp_layers: int = 1

    @property
    def depth(self) -> int:
        return self.n_layers + self.n_mtp_layers

    @staticmethod
    def tiny(**overrides) -> "DeepseekV3Config":
        """CI-sized: 1 dense + 2 expert layers and the MTP module, 8 experts
        in 4 groups of which 2 stay, 2 a token, all held unless told, a value
        head one and a half times the nope part."""
        base = dict(
            vocab_size=256, dim=64, n_layers=3, n_dense_layers=1, n_heads=4,
            q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=24, mlp_hidden=96, moe_hidden=32, n_routed_experts=8,
            held_experts=(0, 8), moe_top_k=2, n_group=4, topk_group=2, max_seq_len=64,
            rope_factor=4.0, rope_original_max=32,
        )
        base.update(overrides)
        return DeepseekV3Config(**base)


# ---------------------------------------------------------------------------
# params: the main model's as ``xing4.init_params`` makes them + ``mtp``


def init_params(cfg: DeepseekV3Config, rng: jax.Array) -> Dict[str, Any]:
    """``xing4.init_params`` (last projections scaled down by the depth
    INCLUDING the module: ``cfg.depth``) and, under ``mtp``, the module: its
    layer drawn as an expert layer of the main model is (one stacked layer),
    ``eh_proj`` normal / sqrt(2 D), its three norm vectors 1. The router's
    bias is normal x 0.03 as there; whether the group limit changes the kept
    set is counted (``group_changed``)."""
    k_main, k_mtp, k_eh = jax.random.split(rng, 3)
    params = xing4.init_params(cfg, k_main)
    if cfg.n_mtp_layers:
        with jax.threefry_partitionable(True):
            layer = xing4._init_group(cfg, k_mtp, 1, True)
            eh = xing4._draw(k_eh, (2, cfg.dim, cfg.dim), 2 * cfg.dim, cfg.dtype).reshape(2 * cfg.dim, cfg.dim)
        ones = jnp.ones((cfg.dim,), cfg.dtype)
        params["mtp"] = {"enorm": ones, "hnorm": ones, "eh_proj": eh, "final_norm": ones, "moe": layer}
    return params


def param_count(cfg: DeepseekV3Config) -> int:
    """The main model's and, kept, the module's: ``eh_proj``, three norm
    vectors, one expert layer."""
    D = cfg.dim
    layer = sum(math.prod(s) for s in xing4._group_shapes(cfg, True).values())
    return xing4.param_count(cfg) + cfg.n_mtp_layers * (2 * D * D + 3 * D + layer)


def logical_axes(cfg: DeepseekV3Config) -> Dict[str, Any]:
    axes = xing4.logical_axes(cfg)
    if cfg.n_mtp_layers:
        axes["mtp"] = {
            "enorm": (None,), "hnorm": (None,), "eh_proj": (None, "embed"), "final_norm": (None,),
            "moe": axes["moe"],
        }
    return axes


def cache_layout(cfg: DeepseekV3Config, block_size: int, dtype=None) -> CacheLayout:
    """The flat-block latent cache of the main layers and, last, the MTP
    module's: ONE more row a token."""
    return latent.cache_layout(cfg, block_size, dtype, n_layers=cfg.n_layers + cfg.n_mtp_layers)


# ---------------------------------------------------------------------------
# the MTP module over the paged cache


def _mtp_layers(cfg: DeepseekV3Config, params, cache, hidden, next_tokens, pos, valid, block_tables):
    """The module over a window: ``hidden [B, C, D]`` the main model's last
    residuals at positions ``pos [B, C]``, ``next_tokens [B, C]`` the token
    after each. Through ``xing4._paged_layers`` as a model of one layer that
    is fed ``h'`` in the embedding's place and writes the cache's last layer.
    Returns ``(cache, h'' [B, C, D], aux)``."""
    mtp = params["mtp"]
    with jax.named_scope("mtp.embed"):
        e = rms_norm(params["embed"][next_tokens], mtp["enorm"], cfg.norm_eps)
        h = rms_norm(hidden.astype(e.dtype), mtp["hnorm"], cfg.norm_eps)
    with jax.named_scope("mtp.proj"):
        x = jnp.concatenate([e, h], axis=-1) @ mtp["eh_proj"]
    with jax.named_scope("mtp.block"):
        return xing4._paged_layers(
            cfg, {"moe": mtp["moe"]}, cache, next_tokens, pos, valid, block_tables,
            embed=(x, cfg.n_layers),
        )


def _mtp_head(cfg: DeepseekV3Config, params, x):
    """The module's logits: ITS final norm, the main model's head."""
    with jax.named_scope("mtp.head"):
        head = {"final_norm": params["mtp"]["final_norm"], "lm_head": params["lm_head"]}
        return xing4._lm_head(cfg, head, x)


def _last_valid(x, true_lens):
    """Row ``true_lens[b] - 1`` of each slot's window ``x [B, C, ...]``."""
    at = jnp.maximum(true_lens - 1, 0)
    return jnp.take_along_axis(x, at.reshape(-1, 1, *([1] * (x.ndim - 2))), axis=1)[:, 0]


def _merge(main: Dict[str, Any], mtp: Dict[str, Any]) -> Dict[str, Any]:
    """The main layers' counters and the module's as ONE account, a row a
    layer (the module's last)."""
    return {
        k: jnp.concatenate([main[k], mtp[k]]) for k in step_counters(main)
    } if main else step_counters(mtp)


def _window(tokens, ctx_lens, true_lens):
    idx = jnp.arange(tokens.shape[1], dtype=jnp.int32)
    return ctx_lens[:, None] + idx, idx < true_lens[:, None]


# ---------------------------------------------------------------------------
# the paged entry points


def paged_prefill_step(cfg: DeepseekV3Config, params, cache, tokens, block_table, ctx_len, true_len,
                       next_token=None):
    """One prefill chunk for ONE request, as ``models/llama.py::
    paged_prefill_step``. Told ``next_token`` (an int32 scalar: the prompt's
    token after the chunk, or -1 after the last chunk) it runs the MTP module
    over the chunk too: position ``i``'s row takes the chunk's own token at
    ``i + 1``, the chunk's last position ``next_token``; without one that
    last position's row is left for the step that knows the first output
    token (:func:`paged_mtp_step` with ``known`` 2)."""
    idx = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    pos, valid = (ctx_len + idx)[None], (idx < true_len)[None]
    cache, X, aux = xing4._paged_layers(cfg, params, cache, tokens[None], pos, valid, block_table[None])
    logits = xing4._lm_head(cfg, params, X[0, jnp.maximum(true_len - 1, 0)])
    if next_token is None:
        return step_outputs(cache, logits, step_counters(aux))
    follows = jnp.where(idx + 1 < true_len, jnp.roll(tokens, -1), jnp.maximum(next_token, 0))
    rows = true_len - (next_token < 0)
    cache, _, mtp_aux = _mtp_layers(
        cfg, params, cache, X, follows[None], pos, (idx < rows)[None], block_table[None]
    )
    return cache, logits, _merge(aux, mtp_aux)


def paged_verify_step(cfg: DeepseekV3Config, params, cache, tokens, block_tables, ctx_lens, true_lens):
    """The main model over a window a slot, every row's logits: as
    ``models/xing4.py::paged_verify_step`` (the MTP module does not run)."""
    cache, logits, _, aux = paged_mtp_verify(cfg, params, cache, tokens, block_tables, ctx_lens, true_lens)
    return step_outputs(cache, logits, step_counters(aux))


def paged_mtp_verify(cfg: DeepseekV3Config, params, cache, tokens, block_tables, ctx_lens, true_lens):
    """The first half of a step with the host's sampler between: the main
    model over ``tokens [B, C]`` at ``ctx_lens[b] ..``. Returns ``(cache,
    logits [B, C, V], hidden [B, C, D], aux)``: ``hidden`` the last layer's
    residual before the final norm, what :func:`paged_mtp_draft` takes."""
    pos, valid = _window(tokens, ctx_lens, true_lens)
    cache, X, aux = xing4._paged_layers(cfg, params, cache, tokens, pos, valid, block_tables)
    return cache, xing4._lm_head(cfg, params, X), X, aux


def paged_mtp_draft(cfg: DeepseekV3Config, params, cache, hidden, next_tokens, block_tables, ctx_lens, true_lens):
    """The second half: the MTP module over the first ``true_lens[b]``
    positions of each window (those the host committed), ``next_tokens [B,
    C]`` the token after each. Returns ``(cache, logits [B, V] after each
    slot's last committed position, aux)``: their argmax is the next draft."""
    pos, valid = _window(next_tokens, ctx_lens, true_lens)
    cache, X, aux = _mtp_layers(cfg, params, cache, hidden, next_tokens, pos, valid, block_tables)
    return cache, _mtp_head(cfg, params, _last_valid(X, true_lens)), step_counters(aux)


def paged_mtp_step(cfg: DeepseekV3Config, params, cache, tokens, block_tables, ctx_lens, true_lens, known):
    """An all-greedy step in ONE program. ``tokens [B, 2]``: a slot's window
    at positions ``ctx_lens[b], ctx_lens[b] + 1``; ``true_lens [B]``: how many
    of the two are there (0: a padding slot; 1: no draft rides this step);
    ``known [B]``: how many of them are COMMITTED (1: ``[x_n, d]``, the second
    a draft; 2: both, a slot whose module has no row at the first yet).

    The main model verifies the window; ``picks`` are its argmax a row. Where
    the second token is a draft it is accepted iff row 0's pick equals it.
    The module then runs over the positions whose following token is now
    committed (1 + accepted; both where both were known) and its argmax after
    the last of them is the next draft. Returns ``(cache, (new [B, 2], accepted
    [B], draft [B]), aux)``, all int32: ``new[b, :1 + accepted[b]]`` are the
    slot's new tokens, in order."""
    with jax.named_scope("spec.verify"):
        cache, logits, X, aux = paged_mtp_verify(cfg, params, cache, tokens, block_tables, ctx_lens, true_lens)
    with jax.named_scope("spec.accept"):
        picks = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, 2]
        both = known >= 2
        accepted = (~both & (true_lens >= 2) & (picks[:, 0] == tokens[:, 1])).astype(jnp.int32)
        # the token after each position of the window, as far as it is committed
        follows = jnp.where(both[:, None], jnp.stack([tokens[:, 1], picks[:, 1]], axis=1), picks)
        rows = jnp.where(true_lens > 0, jnp.where(both, 2, 1 + accepted), 0)
        new = jnp.where(both[:, None], picks[:, 1:], picks)
    cache, draft_logits, mtp_aux = paged_mtp_draft(
        cfg, params, cache, X, follows, block_tables, ctx_lens, rows
    )
    draft = jnp.argmax(draft_logits, axis=-1).astype(jnp.int32)
    return cache, (new, accepted, draft), _merge(aux, mtp_aux)


MODEL = Model(
    name="deepseek_v3",
    init_params=init_params,
    forward=forward,
    logical_axes=logical_axes,
    param_count=param_count,
    cache_layout=cache_layout,
    paged_prefill_step=paged_prefill_step,
    paged_verify_step=paged_verify_step,
    # plain decode (an engine that does not draft): the body's own, the module idle
    paged_decode_step=xing4.paged_decode_step,
    attention_path=xing4.MODEL.attention_path,
    held_experts=xing4.MODEL.held_experts,
    # the module's layer too: ``xing4._paged_layers`` over a stack of one
    experts_in_place=xing4.MODEL.experts_in_place,
    key_tile=xing4.MODEL.key_tile,
    gather_rungs=xing4.MODEL.gather_rungs,
    drafter=lambda cfg: Drafter(
        kind="mtp", window=1 + cfg.n_mtp_layers, cache_layers=cfg.n_mtp_layers,
        step=paged_mtp_step, verify=paged_mtp_verify, draft=paged_mtp_draft,
    ) if cfg.n_mtp_layers else None,
)
