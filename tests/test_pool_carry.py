"""The paged layer scan carries the stacked page pools.

``lm_forward``'s paged modes thread the cache's stacked pools
(``[n_periods, (tp,) num_pages, page_size, KVH, hd]``, plus scale pages and
SSM slot state) through the layer scan's carry: each layer scatters its new
rows in place at ``[layer, page, slot]`` and gathers its pages at
``[layer, block_table]``. As scan ``xs``/``ys`` each layer's pool would be
sliced out of the stack and restacked, a copy of the whole pool per step.

Two checks per configuration, on the CPU: the scan's ``xs`` and ``ys``
hold no cache leaf, stacked or per layer, and its carry holds every one;
and one decode tick / prefill chunk gives bit for bit the logits (hidden
states for prefill) and pools of a reference scan that passes each layer
its own pool as ``xs`` and restacks the results as ``ys``.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import REDUCED
from repro.models import model as M
from repro.models import transformer as T
from repro.models.layers import embed, rmsnorm
from repro.models.rope import rope_cos_sin
from repro.parallel.context import ShardGroup
from repro.serving import paged_cache as PC

PAGES, PS, SLOTS, N_PG, CHUNK = 12, 4, 3, 3, 5

CASES = {
    "qwen3": ("qwen3-32b", {}, 1),                    # period 1
    "gemma2": ("gemma2-2b", {}, 1),                   # period 2, local layer
    "int8": ("qwen3-32b", {"cache_quant": "int8"}, 1),
    "fp8": ("qwen3-32b", {"cache_quant": "fp8"}, 1),
    "tp2": ("qwen3-32b", {}, 2),                      # shard-group loop path
    "jamba": ("jamba-v0.1-52b", {"n_layers": 16}, 1),  # SSM slots, period 8
}
STEPS = [(c, "decode") for c in CASES] + [
    (c, "prefill") for c in CASES if c != "jamba"]   # SSM: sequential path


def _setup(case):
    arch, over, tp = CASES[case]
    cfg = dataclasses.replace(REDUCED[arch], dtype="float32", **over)
    params = M.init(cfg, jax.random.PRNGKey(0))
    cache = PC.init_paged_cache(cfg, PAGES, PS, SLOTS, tp=tp)
    # random pool contents (positive scales), so every gathered page counts
    paths, tree = jax.tree_util.tree_flatten_with_path(cache)
    leaves = []
    for i, (path, x) in enumerate(paths):
        r = jax.random.normal(jax.random.PRNGKey(i + 1), x.shape)
        if "scale" in jax.tree_util.keystr(path):
            r = jnp.abs(r)
        leaves.append(r.astype(x.dtype))
    cache = jax.tree.unflatten(tree, leaves)
    bt = jnp.arange(1, 1 + SLOTS * N_PG, dtype=jnp.int32).reshape(SLOTS, N_PG)
    shard = ShardGroup(tp) if tp > 1 else None
    return cfg, params, cache, bt, shard


def _step(cfg, params, cache, bt, shard, kind):
    """One decode tick or one prefill chunk through the program's step."""
    if kind == "decode":
        toks = jnp.asarray([[3], [7], [11]], jnp.int32)
        lens = jnp.asarray([5, 0, 9], jnp.int32)
        return (lambda c: M.paged_decode_step(cfg, params, c, toks, lens, bt,
                                              shard=shard)), (toks, lens, None)
    toks = jnp.arange(SLOTS * CHUNK, dtype=jnp.int32).reshape(SLOTS, CHUNK)
    start = jnp.asarray([2, 0, 4], jnp.int32)
    live = jnp.asarray([5, 3, 0], jnp.int32)
    return (lambda c: M.paged_prefill_step(cfg, params, c, toks, start, live,
                                           bt, shard=shard)), (toks, start,
                                                               live)


def _per_layer_reference(cfg, params, cache, bt, shard, kind, toks, lens,
                         live):
    """The layer scan with the pools as its xs and ys: each layer steps its
    own pool, sliced out of the stack, and the scan restacks the results."""
    mode = "paged_decode" if kind == "decode" else "paged_prefill"
    S = toks.shape[1]
    pos = lens[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    cos, sin = rope_cos_sin(cfg, pos)
    prefix, period, _ = T.depth_plan(cfg)
    assert prefix == 0

    def body(x, layer):
        ps, cs = layer
        out = {}
        for p in range(period):
            x, _, out[str(p)] = T.block_apply(
                cfg, ps[str(p)], x, p, cos, sin, mode, cs[str(p)], lens, bt,
                shard, chunk_len=live)
        return x, out

    x, stack = jax.lax.scan(body, embed(cfg, params["embed"], toks),
                            (params["stack"], cache["stack"]))
    x = rmsnorm(x, params["final_ln"], cfg.rms_eps)
    res = M.final_logits(cfg, params, x) if kind == "decode" else x
    return res, {"stack": stack}


def _layer_scan(jaxpr):
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1, [e.primitive.name for e in jaxpr.eqns]
    return scans[0]


@pytest.mark.parametrize("case,kind", STEPS)
def test_layer_scan_carries_pools(case, kind):
    cfg, params, cache, bt, shard = _setup(case)
    fn, _ = _step(cfg, params, cache, bt, shard, kind)
    scan = _layer_scan(jax.make_jaxpr(fn)(cache).jaxpr)
    nc, nk = scan.params["num_consts"], scan.params["num_carry"]
    avals = lambda vs: [(tuple(v.aval.shape), v.aval.dtype) for v in vs]  # noqa: E731
    carry = avals(scan.invars[nc:nc + nk])
    xs = avals(scan.invars[nc + nk:])
    ys = avals(scan.outvars[nk:])
    stacked = [(tuple(a.shape), a.dtype)
               for a in jax.tree.leaves(cache["stack"])]
    per_layer = [(s[1:], d) for s, d in stacked]
    for leaf in stacked:
        assert leaf in carry, (leaf, carry)
    for leaf in stacked + per_layer:
        assert leaf not in xs, (leaf, xs)
        assert leaf not in ys, (leaf, ys)
    assert avals(scan.outvars[:nk]) == carry


@pytest.mark.parametrize("case,kind", STEPS)
def test_layer_scan_matches_per_layer_pools(case, kind):
    cfg, params, cache, bt, shard = _setup(case)
    fn, (toks, lens, live) = _step(cfg, params, cache, bt, shard, kind)
    got, got_cache = jax.jit(fn)(cache)
    want, want_cache = jax.jit(lambda c: _per_layer_reference(
        cfg, params, c, bt, shard, kind, toks, lens, live))(cache)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for g, w in zip(jax.tree.leaves(got_cache), jax.tree.leaves(want_cache)):
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))
    # the step wrote something: at least one pool leaf moved
    assert any(not np.array_equal(np.asarray(g, np.float32),
                                  np.asarray(c, np.float32))
               for g, c in zip(jax.tree.leaves(got_cache),
                               jax.tree.leaves(cache)))
