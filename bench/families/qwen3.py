"""Qwen3 (``model_type`` ``qwen3``): a dense GQA decoder with per-head RMS
norms of queries and keys, rotary over the whole head."""
from __future__ import annotations

from bench.families.dense import (LEAVES, Arch, check_program,  # noqa: F401
                                  decode_work, hidden, logits, prefill_work)


def arch(conf: dict) -> Arch:
    hd = conf["head_dim"]
    return Arch(conf["hidden_size"], conf["num_attention_heads"],
                conf["num_key_value_heads"], hd,
                conf["intermediate_size"], conf["vocab_size"],
                conf["num_hidden_layers"], conf["rms_norm_eps"],
                float(conf["rope_theta"]), hd, True,
                bool(conf["attention_bias"]))
