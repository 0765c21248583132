"""A family for tests (``model_type`` ``dense_probe``): the dense forward
under a model type of its own, with a leaf rule and work counts of its own.
It and ``tiny-probe.json`` are all that such a configuration adds."""
from __future__ import annotations

from bench.families import dense
from bench.families.dense import (Arch, check_program, hidden,  # noqa: F401
                                  logits)

# its own rule: the second norm of each layer drawn as a bias
LEAVES = dict(dense.LEAVES, ln2="bias")


def arch(conf: dict) -> Arch:
    return Arch(conf["width"], conf["heads"], conf["kv_heads"],
                conf["head_width"], conf["ffn_width"], conf["vocab"],
                conf["depth"], conf["eps"], float(conf["theta"]),
                conf["head_width"], True, False)


def _params(a: Arch) -> int:
    return a.layers * dense.layer_params(a) + a.d * a.vocab


def decode_work(a: Arch, k: int, rows: int, ctx: int):
    """Every weight once per token computed, read once per tick."""
    return 2 * k * rows * _params(a), 2 * k * _params(a)


def prefill_work(a: Arch, s: int, start: int):
    return 2 * s * _params(a), 2 * _params(a)
