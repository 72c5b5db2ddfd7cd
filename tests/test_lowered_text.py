"""The programs a runner warms, as text (ISSUE 48): a decode or verify launch
is handed the table at ONE width, ``max_blocks_per_seq``, so what the runner
warms for a chip is each model's entry point lowered with that table and
nothing else, and off the chip too there is one decode program a batch bucket.

``tests/tools/lowered_text.py`` lowers a runner's warm-up without weights,
cache or compiler; the cases here hold it against the four model modules'
entry points lowered by hand, at toy widths in whole tiles, FOR a TPU (the
program's own predicates then choose the kernels: no predicate is patched),
and run it once over every file of ``perfbench/configs`` at its family's toy
sizes, so that the script a later PR is held to (``--ref <parent>``) still runs."""

import dataclasses
import functools
import importlib.util
import os
import sys
from functools import partial

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.inference.model_runner import decode_program, mtp_programs  # noqa: E402
from ray_tpu.models import deepseek_v3, kimi_linear, llama, xing4  # noqa: E402
from ray_tpu.models.interface import model_of  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)  # perfbench, for the tool's configuration files

_spec = importlib.util.spec_from_file_location("lowered_text", os.path.join(REPO, "tests", "tools", "lowered_text.py"))
lowered_text = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(lowered_text)

BS, NUM_BLOCKS, CHUNK, VERIFY, SLOTS = 16, 40, 32, 4, 4
#: heads and widths in whole tiles: on a TPU every short window of these takes its kernel
TILES = dict(kv_lora_rank=128, qk_rope_head_dim=64, n_heads=8, max_seq_len=512, dtype=jnp.bfloat16)
#: model -> (configuration, what its engine would hand the runner beside the buckets)
MODELS = {
    "llama": (
        dataclasses.replace(llama.LlamaConfig.tiny(), dim=1024, n_heads=8, n_kv_heads=8, max_seq_len=512,
                            dtype=jnp.bfloat16),
        dict(verify_buckets=(VERIFY,)),
    ),
    "xing4": (xing4.Xing4Config.tiny(**TILES), dict(verify_buckets=(VERIFY,))),
    "kimi_linear": (
        kimi_linear.KimiLinearConfig.tiny(**TILES, kda_head_dim=128, kda_heads=2), dict(state_slots=SLOTS),
    ),
    "deepseek_v3": (deepseek_v3.DeepseekV3Config.tiny(**TILES), dict(drafter=True)),
}
PROGRAMS = [
    (name, program)
    for name, programs in {
        "llama": ("paged_prefill_step", "paged_decode_step", "paged_verify_step"),
        "xing4": ("paged_prefill_step", "paged_decode_step", "paged_verify_step"),
        "kimi_linear": ("paged_prefill_step", "paged_decode_step"),  # a verify window is refused on a state
        "deepseek_v3": ("paged_prefill_step", "paged_mtp_step", "paged_mtp_verify", "paged_mtp_draft"),
    }.items()
    for program in programs
]


@functools.lru_cache(maxsize=None)  # a model's warm-up is lowered once a process, when a case first asks
def _warmed(name, platform, decode_buckets, **sizes):
    cfg, kw = MODELS[name]
    cfg = dataclasses.replace(cfg, **sizes)
    return lowered_text.warmed_texts(
        cfg, platform, num_blocks=max(NUM_BLOCKS, cfg.max_seq_len // BS + 8), block_size=BS,
        prefill_buckets=(CHUNK,), decode_buckets=decode_buckets, **kw,
    )


def _by_hand(name, program, B=8):
    """The entry point lowered for a TPU from shapes written out here: the
    table ``[B, M]`` with ``M = ceil(max_seq_len / block_size)``, under the jit
    the runner's constructor makes of it."""
    cfg, kw = MODELS[name]
    model = model_of(cfg)
    M = -(-cfg.max_seq_len // BS)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    params = jax.eval_shape(partial(model.init_params, cfg), jax.random.PRNGKey(0))
    pools = [jax.eval_shape(lambda: model.cache_layout(cfg, BS).init(NUM_BLOCKS))]
    slot = lambda *shape: ()  # noqa: E731
    if model.state_layout is not None:
        pools.append(jax.eval_shape(lambda: model.state_layout(cfg).init(SLOTS + 1)))
        slot = lambda *shape: (i32(*shape),)  # noqa: E731
    donated = tuple(range(1, 1 + len(pools)))
    drafter = model.drafter(cfg) if kw.get("drafter") else None
    if program == "paged_prefill_step":
        fn = partial(model.paged_prefill_step, cfg)
        args = (i32(CHUNK), i32(M), i32(), i32(), *slot(), *((i32(),) if drafter else ()))
    elif program == "paged_decode_step":
        fn = decode_program(model.paged_decode_step, cfg, len(pools), B)
        args = (i32(B), i32(B), i32(B, M), i32(B), *slot(B), i32(B))
    elif program == "paged_verify_step":
        fn = partial(model.paged_verify_step, cfg)
        args = (i32(B, VERIFY), i32(B, M), i32(B), i32(B))
    else:
        C = drafter.window
        window = (i32(B, C), i32(B, M), i32(B), i32(B))
        step, verify, draft = mtp_programs(drafter, cfg, B)
        if program == "paged_mtp_step":
            fn, args = step, (*window, i32(B), i32(B, C + 2))
        elif program == "paged_mtp_verify":
            fn, args = verify, window
        else:
            _, (_, hidden), _ = jax.eval_shape(verify, params, *pools, *window)
            fn, args = draft, (hidden, *window)
    with lowered_text.described("tpu"):
        return jax.jit(fn, donate_argnums=donated).trace(params, *pools, *args).lower(
            lowering_platforms=("tpu",)
        ).as_text()


@pytest.mark.parametrize("name, program", PROGRAMS, ids=lambda v: v)
def test_the_warmed_program_is_the_entry_point_at_the_table_s_full_width(name, program):
    """Under ONE label a program, at the table's full width, the runner warms
    the text the model's entry point lowers to when it is handed
    ``max_blocks_per_seq`` blocks a slot; where the window is short that text
    runs the model's kernel, which reads each slot's live blocks alone."""
    texts, runner = _warmed(name, "tpu", (8,))
    cfg = MODELS[name][0]
    window = {"paged_prefill_step": f"{CHUNK}", "paged_decode_step": f"8x{cfg.max_seq_len}",
              "paged_verify_step": f"8x{VERIFY}x{cfg.max_seq_len}"}.get(program, f"8x2x{cfg.max_seq_len}")
    labels = [label for label in texts if label.startswith(program + "[")]
    assert labels == [f"{program}[{window}]"]
    assert texts[labels[0]] == _by_hand(name, program)
    if program != "paged_prefill_step":
        queries = 1 if program == "paged_decode_step" else VERIFY if program == "paged_verify_step" else 2
        with lowered_text.described("tpu"):  # a window not asked for yet is asked now
            assert runner._path(queries).reads == "blocks"
        assert "tpu_custom_call" in texts[labels[0]]


@pytest.mark.parametrize("name", MODELS)
def test_off_the_chip_the_runner_warms_one_decode_program_a_batch_bucket(name):
    """No kernel serves on the CPU and the fallback gathers the table at its
    width, whatever the batch's contexts: nothing is left to compile a second
    decode (or verify, or drafter's) program a bucket for, at a table of 4096
    positions either (two rungs of the ladder there was)."""
    texts, runner = _warmed(name, "cpu", (4, 8), max_seq_len=4096)
    cfg, kw = runner.cfg, MODELS[name][1]
    assert runner.attention_paths[1].reads in ("table", "slots")
    assert not any("tpu_custom_call" in text for text in texts.values())
    steps = sorted(label for label in texts if not label.startswith(("paged_prefill_step", "copy_paged_blocks")))
    S = cfg.max_seq_len
    if kw.get("drafter"):
        assert steps == sorted(f"paged_mtp_{p}[{b}x2x{S}]" for p in ("step", "verify", "draft") for b in (4, 8))
    else:
        verify = [f"paged_verify_step[{b}x{VERIFY}x{S}]" for b in (4, 8) if kw.get("verify_buckets")]
        assert steps == sorted([f"paged_decode_step[4x{S}]", f"paged_decode_step[8x{S}]", *verify])


CONFIGS = sorted(f[: -len(".json")] for f in os.listdir(os.path.join(REPO, "perfbench", "configs")))


@pytest.mark.parametrize("config", CONFIGS)
def test_the_script_lowers_every_configuration_file_at_its_toy_sizes(config):
    """Every file of ``perfbench/configs`` through the script's own path (the
    family's adapter, the engine's three questions, the warm-up; the train
    step under its mesh): a row a program and platform, a prefill program a
    bucket, one decode program, and the same program twice gives the same hash."""
    rows = lowered_text.config_hashes(REPO, config, ("tpu", "cpu"), toy=True)
    assert all(len(digest) == 64 for digest in rows.values())
    for platform in ("tpu", "cpu"):
        labels = [key.split(" ", 2)[2] for key in rows if key.startswith(f"{config} {platform} ")]
        if labels[0].startswith("train_step"):
            assert len(labels) == 1
        else:
            assert labels == ["paged_prefill_step[16]", "paged_prefill_step[32]", "paged_decode_step[4x128]",
                              "copy_paged_blocks"]
    again = lowered_text.config_hashes(REPO, config, ("tpu",), toy=True)
    assert again == {key: digest for key, digest in rows.items() if key in again} and again
