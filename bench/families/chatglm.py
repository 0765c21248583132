"""ChatGLM (``model_type`` ``chatglm``): a dense GQA decoder with QKV bias
and rotary over the first half of each head.

Departure from the published ChatGLM3 description: ChatGLM3 rotates
interleaved pairs of the first half of each head; this reference rotates
the pairs (i, i + 32) of that half, the layout in which the weights are
stored. That is the same model under a fixed permutation of the query and
key projection columns, which is how a converted checkpoint stores it.
"""
from __future__ import annotations

from bench.families.dense import (LEAVES, Arch, check_program,  # noqa: F401
                                  decode_work, hidden, logits, prefill_work)


def arch(conf: dict) -> Arch:
    hd = conf["kv_channels"]
    return Arch(conf["hidden_size"], conf["num_attention_heads"],
                conf["multi_query_group_num"], hd,
                conf["ffn_hidden_size"], conf["padded_vocab_size"],
                conf["num_layers"], conf["layernorm_epsilon"],
                10000.0 * conf.get("rope_ratio", 1), hd // 2, False,
                bool(conf["add_qkv_bias"]))
