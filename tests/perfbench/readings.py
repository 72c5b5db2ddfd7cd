"""What a family's test holds of each per-layer reading of ITS cell, now that
a cell JOINS the entry that already reads its counter instead of bringing a
copy under a suffix of its own (PR 37): exactly one entry of that name lists
the cell, the entry's file reads what the test expects, and the entry's arrow
is an end-to-end metric the cell reports.

``WANT`` says what the files of the entries more than one family reads must
hold (``kind``, ``key``, ``per``, ``scale``, ``name_regex``: a key that is
absent must be absent). A family's test lists its cell's readings by name and
adds what its own new entries must hold; it edits nothing here."""

from perfbench.harness import cells, layer_metrics as lm

_STEP = {f"step_{phase}_ms.batch": {"kind": "stats_delta", "key": ["step_phases", f"{phase}_s"],
                                    "per": ["total_steps"], "scale": 1000.0}
         for phase in ("host_serial", "schedule", "sample", "emit", "launch", "device_wait", "readback")}
_MOE = {f"{name}.moe": {"kind": "stats_delta", "key": ["moe", kind, key], "per": ["moe", kind, per], **scale}
        for name, kind, key, per, scale in (
            ("moe_experts_touched_share", "decode", "experts_touched", "expert_slots", {"scale": 100.0}),
            ("moe_load_imbalance", "decode", "max_load", "mean_load", {}),
            ("moe_rows_per_expert", "decode", "assignments", "experts_touched", {}),
            ("moe_rows_per_expert_prefill", "prefill", "assignments", "experts_touched", {}),
        )}
WANT = {
    **_STEP, **_MOE,
    "decode_step_device_ms.batch": {"kind": "device_trace", "name_regex": "paged_decode_step"},
    "prefill_step_device_ms.batch": {"kind": "device_trace", "name_regex": "paged_prefill_step"},
    "device_idle_share.batch": {"kind": "device_trace"},
    "tokens_per_engine_step.batch": {"kind": "stats_delta", "per": ["total_steps"]},
    "kv_pool_peak_share.batch": {"kind": "stats_delta", "key": ["blocks", "used_blocks"],
                                 "per": ["blocks", "num_blocks"], "scale": 100.0},
    "preemptions.batch": {"kind": "stats_delta", "key": ["scheduler", "total_preempted"]},
    "decode_table_width_tokens.batch": {"kind": "stats_delta", "key": ["decode_width", "width_tokens"],
                                        "per": ["decode_width", "launches"]},
    "decode_gather_live_share.batch": {"kind": "stats_delta", "key": ["decode_width", "live_tokens"],
                                       "per": ["decode_width", "gathered_tokens"], "scale": 100.0},
    "wakes_after_launch_share.batch": {"kind": "stats_delta", "key": ["wakes", "after_launch"],
                                       "per": ["wakes", "items"], "scale": 100.0},
    "wake_hold_ms.batch": {"kind": "stats_delta", "key": ["wakes", "held_s"], "per": ["wakes", "items"],
                           "scale": 1000.0},
    "recompiles_in_window.moe": {"kind": "stats_delta", "key": ["recompiles_after_warmup"]},
    "replica_init_s": {"kind": "stats_delta", "key": ["startup", "replica_init_s"]},
    "param_init_s": {"kind": "stats_delta", "key": ["startup", "param_init_s"]},
    "warmup_s": {"kind": "stats_delta", "key": ["startup", "warmup_s"]},
    "moe_ffn_time_share.moe": {"kind": "device_trace", "name_regex": "^(gmm|ragged-dot)"},
    "moe_held_assignment_share.mla": {"kind": "stats_delta", "key": ["moe", "decode", "held_assignments"],
                                      "per": ["moe", "decode", "assignments"], "scale": 100.0},
    "kv_bytes_per_token.mla": {"kind": "stats_delta", "key": ["kv_layout", "bytes_per_token"]},
    "prefill_read_live_share.longdoc": {"kind": "stats_delta", "key": ["prefill_width", "live_tokens"],
                                        "per": ["prefill_width", "read_tokens"], "scale": 100.0},
    "latent_flash_time_share.longdoc": {"kind": "device_trace", "name_regex": "^latent_flash"},
    "latent_rows_time_share": {"kind": "device_trace", "name_regex": "^latent_rows"},
}
#: the 24 readings PR 27 gave ``moe-chat-offline``, which PR 31 gave ``mla-longdoc-batch`` too. A literal:
#: what either cell reads later is its own test's to add
MOE_CHAT_OFFLINE = [f"{n}.batch" for n in (
    "decode_step_device_ms", "prefill_step_device_ms", "device_idle_share", "tokens_per_engine_step",
    "step_host_serial_ms", "step_launch_ms", "step_device_wait_ms", "step_readback_ms",
    "kv_pool_peak_share", "preemptions", "decode_table_width_tokens", "decode_gather_live_share",
    # the rest of a step's host time (the sampler runs over a vocabulary of 50304)
    "step_schedule_ms", "step_sample_ms", "step_emit_ms",
)] + ["replica_init_s", "param_init_s", "warmup_s"] + [f"{n}.moe" for n in (
    "recompiles_in_window", "moe_experts_touched_share", "moe_load_imbalance", "moe_rows_per_expert",
    "moe_ffn_time_share", "moe_rows_per_expert_prefill",  # the last: the prefill half of the moe account
)]
HELD = ("kind", "key", "per", "scale", "name_regex")
SOURCE = {"device_trace": "device_trace", "stats_delta": "program_counter"}


def check(bench, cell_name, name, want):
    """The reading ``name`` of ``cell_name``: ONE entry, its file as ``want``
    has it, its arrow a metric the cell reports. Returns the entry."""
    listing = [m for m in bench["per_layer"] if m["name"] == name and cell_name in m.get("workloads", ())]
    assert len(listing) == 1 and listing[0]["workloads"].count(cell_name) == 1, (name, cell_name, listing)
    (entry,) = listing
    spec = cells.layer_metric_spec(name)
    assert (spec["layer"], spec["unit"], spec["moves"]) == (entry["layer"], entry["unit"], entry["moves"])
    assert spec["kind"] in lm.READERS and entry["source"] == SOURCE[spec["kind"]]
    assert {k: spec.get(k) for k in HELD} == {k: want.get(k) for k in HELD}
    reported = {m["name"] for m in cells.metrics_of(bench, cell_name, "end_to_end")}
    assert entry["moves"] in reported, f"{name} moves {entry['moves']}, which {cell_name} does not report"
    return entry
