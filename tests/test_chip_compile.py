"""Compile-only checks of the paged Pallas kernels and the paged step
programs for a TPU v5e.

The TPU compiler compiles for a chip that is described, not attached, so
these run on any host that has libtpu: each kernel is lowered and compiled
(``interpret=False``) at qwen3-32b's published widths (64 query heads, 8 KV
heads, head_dim 128, page 16, chunk 256, bf16) and must come out as a
Mosaic kernel (``tpu_custom_call``) — a BlockSpec the TPU lowering refuses
fails here, at no chip time. Nothing runs, so results are covered by the
interpret-mode oracle tests and by ``chip_smoke.py`` on the chip. The
decode and fused-prefill programs are compiled whole at the same widths
(2 layers), and their HLO must hold no copy of a page pool.

The topology is described inside a module fixture, never at import time:
only one process may hold libtpu, and every test worker imports this file.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_arch
from repro.kernels import paged_decode as PD
from repro.kernels import paged_prefill as PP
from repro.models import model as M
from repro.serving import paged_cache as PC
from repro.serving.scheduler import ContinuousBatchingScheduler

H, KVH, D, PAGE, CHUNK = 64, 8, 128, 16, 256      # qwen3-32b widths
B, N_PG, POOL = 2, 24, 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shapes(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return {"pool": sds((POOL, PAGE, KVH, D)),
            "chunk_kv": sds((B, CHUNK, KVH, D)),
            "chunk_q": sds((B, CHUNK, H, D)),
            "decode_q": sds((B, H, D)),
            "table": sds((B, N_PG), jnp.int32),
            "lens": sds((B,), jnp.int32)}


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_paged_prefill_write_compiles_for_v5e(shapes):
    s = shapes
    text = _compiled_text(
        lambda kn, vn, kp, vp, bt, st, ln: PP.paged_prefill_write(
            kn, vn, kp, vp, bt, st, ln, interpret=False),
        s["chunk_kv"], s["chunk_kv"], s["pool"], s["pool"], s["table"],
        s["lens"], s["lens"])
    assert "tpu_custom_call" in text


def test_paged_prefill_attend_compiles_for_v5e(shapes):
    s = shapes
    text = _compiled_text(
        lambda q, kp, vp, bt, st, ln: PP.paged_prefill_attend(
            q, kp, vp, bt, st, ln, interpret=False),
        s["chunk_q"], s["pool"], s["pool"], s["table"], s["lens"],
        s["lens"])
    assert "tpu_custom_call" in text


def test_paged_decode_partials_compiles_for_v5e(shapes):
    s = shapes
    text = _compiled_text(
        lambda q, kp, vp, bt, ln: PD.paged_decode_partials(
            q, kp, vp, bt, ln, interpret=False),
        s["decode_q"], s["pool"], s["pool"], s["table"], s["lens"])
    assert "tpu_custom_call" in text


# ------------------------------------------------- whole step programs --

STEP_LAYERS, STEP_K = 2, 2
# a pool large enough that one stacked pool leaf (2 x 8192 pages x 32 KiB)
# outweighs the rest of either program's temp memory (~0.2 GB at 2 layers:
# the per-dispatch relayout of the stacked weights)
BIG_POOL = 8192
_POOL_OPS = ("copy", "copy-start", "copy-done", "slice", "dynamic-slice",
             "dynamic-update-slice")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?(\S+)\s*=\s*(.*?)\s([\w\-]+)\(",
                    re.M)


def _step_program(topo, kind: str, pool: int):
    """The paged decode scan (``_decode_multi``, k=2) or one fused prefill
    chunk, at qwen3-32b widths cut to 2 layers, compiled for one v5e.
    -> (compiled, stacked pool leaf shapes)."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = dataclasses.replace(get_arch("qwen3-32b"), n_layers=STEP_LAYERS)

    def sds(tree, dtype=None):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, dtype or a.dtype, sharding=one_chip), tree)

    params = sds(jax.eval_shape(lambda: M.init(cfg, jax.random.PRNGKey(0))),
                 jnp.bfloat16)
    cache = sds(jax.eval_shape(
        lambda: PC.init_paged_cache(cfg, pool, PAGE, B)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32,  # noqa: E731
                                          sharding=one_chip)
    if kind == "decode":
        def fn(p, c, toks, lens, bt):
            return ContinuousBatchingScheduler._decode_multi(
                cfg, None, p, c, toks, lens, bt, k=STEP_K)
        args = (i32(B, 1), i32(B), i32(B, N_PG))
    else:
        def fn(p, c, toks, start, live, bt):
            hidden, c = M.paged_prefill_step(cfg, p, c, toks, start, live, bt)
            return M.final_logits(cfg, p, hidden[:, -1:]), c
        args = (i32(B, CHUNK), i32(B), i32(B), i32(B, N_PG))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    return compiled, [tuple(a.shape) for a in jax.tree.leaves(cache)]


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_paged_step_moves_no_pool_for_v5e(topo, kind):
    """No copy, slice or dynamic-(update-)slice, fused or not, outputs a
    page pool, stacked or one layer's: the layer scan carries the stacked
    pools and updates them in place."""
    compiled, stacked = _step_program(topo, kind, POOL)
    pools = set(stacked) | {s[1:] for s in stacked}
    moved = []
    for name, out_type, op in _INSTR.findall(compiled.as_text()):
        if op not in _POOL_OPS and not (
                op == "fusion" and any(w in name for w in _POOL_OPS)):
            continue
        for dims in re.findall(r"\w+\[([\d,]*)\]", out_type):
            if tuple(int(d) for d in dims.split(",") if d) in pools:
                moved.append(f"{name} = {op} {dims}")
    assert not moved, moved


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_paged_step_temp_below_one_pool_for_v5e(topo, kind):
    compiled, _ = _step_program(topo, kind, BIG_POOL)
    pool_bytes = STEP_LAYERS * BIG_POOL * PAGE * KVH * D * 2       # bf16
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes
