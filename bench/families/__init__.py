"""Model families: all that the benchmark knows of an architecture.

A configuration file's ``model_type`` names its family module,
``bench/families/<model_type>.py``, found by that name as the per-layer
readers are found by theirs (``bench/metrics/<name>.py``). A configuration
of a new architecture so brings its family as a new file, and no other file
of the benchmark knows an architecture. A family module provides:

* ``arch(conf)``: a frozen, hashable dataclass of the published widths,
  with at least ``vocab`` and ``layers``;
* ``check_program(cfg, a)``: raises if the program's ``ModelConfig``
  differs from those widths;
* ``hidden(params, tokens, a, fp8)`` and ``logits(h, params, a, fp8)``: the
  plain float32 forward at ``Precision.HIGHEST``, in blocks, that
  ``bench/reference.py`` drives; ``fp8`` gives the control;
* ``LEAVES``: each parameter-leaf name that ``bench/weights.py``'s default
  table lacks, with its kind (``norm``, ``bias``, ``matrix`` or
  ``embedding``);
* ``decode_work(a, k, rows, ctx)`` and ``prefill_work(a, s, start)``:
  (FLOPs, bytes) of one dispatch, as ``bench/roofline.py`` defines work.
"""
from __future__ import annotations

import importlib.util
import pathlib
import re
import sys
import types

DIR = pathlib.Path(__file__).resolve().parent
CONTRACT = ("arch", "check_program", "hidden", "logits", "LEAVES",
            "decode_work", "prefill_work")
_MODEL_TYPE = re.compile(r"^[A-Za-z0-9_-]+$")


def of(conf: dict) -> types.ModuleType:
    """The family module of a configuration, by its ``model_type``."""
    t = conf.get("model_type")
    path = DIR / f"{t}.py"
    if not (isinstance(t, str) and _MODEL_TYPE.match(t) and path.is_file()):
        raise ValueError(f"no family for model_type {t!r}: expected {path}")
    name = "bench_family_" + t.replace("-", "_")
    mod = sys.modules.get(name)
    if mod is None or pathlib.Path(mod.__file__) != path:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        # registered before it runs, as an import would be: a dataclass
        # looks its module up while it is made
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    missing = [n for n in CONTRACT if not hasattr(mod, n)]
    if missing:
        raise ValueError(f"family {path} lacks {', '.join(missing)}")
    return mod


def arch_of(conf: dict):
    """The architecture a published configuration describes."""
    return of(conf).arch(conf)
