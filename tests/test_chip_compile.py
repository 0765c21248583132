"""Compile-only checks of the paged Pallas kernels for a TPU v5e.

The TPU compiler compiles for a chip that is described, not attached, so
these run on any host that has libtpu: each kernel is lowered and compiled
(``interpret=False``) at qwen3-32b's published widths (64 query heads, 8 KV
heads, head_dim 128, page 16, chunk 256, bf16) and must come out as a
Mosaic kernel (``tpu_custom_call``) — a BlockSpec the TPU lowering refuses
fails here, at no chip time. Nothing runs, so results are covered by the
interpret-mode oracle tests and by ``chip_smoke.py`` on the chip.

The topology is described inside a module fixture, never at import time:
only one process may hold libtpu, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import paged_decode as PD
from repro.kernels import paged_prefill as PP

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
