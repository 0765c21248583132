"""Subprocess SPMD checks — run with 8 fake CPU devices (or as many as
``XLA_FLAGS`` asks for, when it is set).

Executed by tests/test_spmd.py via subprocess so the main pytest process
keeps its single-device view. Each check prints 'PASS <name>' on success.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import REDUCED
from repro.checkpoint.manager import CheckpointManager
from repro.core.blueprint import suggest_plan
from repro.configs.base import ShapeConfig
from repro.launch.mesh import make_mesh_for
from repro.models import model as M
from repro.models.schema import abstract_params, partition_specs
from repro.optim.adamw import OptimConfig
from repro.train.steps import init_train_state, make_train_step


def check_sharded_train_step_matches_single_device():
    """Same batch, same init: a (2 data x 2 model)-sharded train step must
    reproduce the single-device loss."""
    cfg = REDUCED["qwen3-32b"]
    ocfg = OptimConfig(warmup_steps=1, total_steps=10)
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (8, 32), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}

    state = init_train_state(cfg, key)
    step_1d = jax.jit(make_train_step(cfg, ocfg))
    _, m1 = step_1d(jax.tree.map(jnp.copy, state), batch)

    mesh = make_mesh_for(2, 2)
    shape = ShapeConfig("t", 32, 8, "train")
    plan = suggest_plan(cfg, shape, mesh)
    specs = partition_specs(M.schema(cfg), mesh, plan.param_rules)
    shard_state = {
        "params": jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            state["params"], specs),
        "m": jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            state["m"], specs),
        "v": jax.tree.map(
            lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
            state["v"], specs),
        "step": jax.device_put(state["step"], NamedSharding(mesh, P())),
    }
    sb = {k: jax.device_put(v, NamedSharding(mesh, P(("data",))))
          for k, v in batch.items()}
    step_2d = jax.jit(make_train_step(cfg, ocfg, mesh=mesh,
                                      act_rules=plan.act_rules))
    with mesh:
        _, m2 = step_2d(shard_state, sb)
    l1, l2 = float(m1["loss"]), float(m2["loss"])
    assert abs(l1 - l2) / abs(l1) < 2e-3, (l1, l2)
    print("PASS sharded_train_step_matches_single_device")


def check_elastic_reshard_resume():
    """Checkpoint on a (4 data x 2 model) mesh, restore on (2 data x 2
    model) — loss trajectory continues identically (elastic resize)."""
    cfg = REDUCED["gemma2-2b"]
    ocfg = OptimConfig(warmup_steps=1, total_steps=50)
    key = jax.random.PRNGKey(1)
    tokens = np.asarray(jax.random.randint(key, (8, 32), 0, cfg.vocab_size))
    batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(tokens)}

    def run_steps(mesh, state, n):
        plan = suggest_plan(cfg, ShapeConfig("t", 32, 8, "train"), mesh)
        step = jax.jit(make_train_step(cfg, ocfg, mesh=mesh,
                                       act_rules=plan.act_rules))
        losses = []
        with mesh:
            for _ in range(n):
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
        return state, losses

    def place(state, mesh):
        plan = suggest_plan(cfg, ShapeConfig("t", 32, 8, "train"), mesh)
        specs = partition_specs(M.schema(cfg), mesh, plan.param_rules)
        out = {}
        for k in ("params", "m", "v"):
            out[k] = jax.tree.map(
                lambda a, s: jax.device_put(np.asarray(a),
                                            NamedSharding(mesh, s)),
                state[k], specs)
        out["step"] = jax.device_put(np.asarray(state["step"]),
                                     NamedSharding(mesh, P()))
        return out

    state0 = init_train_state(cfg, key)

    # reference: 6 uninterrupted steps on the big mesh
    mesh_big = make_mesh_for(4, 2)
    ref_state = place(state0, mesh_big)
    _, ref_losses = run_steps(mesh_big, ref_state, 6)

    # elastic: 3 steps on big mesh -> checkpoint -> restore on small mesh
    state_a = place(state0, mesh_big)
    state_a, losses_a = run_steps(mesh_big, state_a, 3)
    with tempfile.TemporaryDirectory() as d:
        ck = CheckpointManager(d, async_writes=False)
        ck.save(state_a, 3, blocking=True)
        mesh_small = make_mesh_for(2, 2)
        template = place(init_train_state(cfg, key), mesh_small)
        state_b = ck.restore(target=template)
    state_b, losses_b = run_steps(mesh_small, state_b, 3)
    got = losses_a + losses_b
    np.testing.assert_allclose(got, ref_losses, rtol=2e-3)
    print("PASS elastic_reshard_resume")


def check_compressed_psum():
    from repro.parallel.collectives import (compressed_psum,
                                            compression_error_bound,
                                            make_compressed_grad_sync)
    mesh = make_mesh_for(2, 2, 2)   # pod x data x model
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64), jnp.float32)
    xs = jax.device_put(x, NamedSharding(mesh, P("pod")))
    sync = make_compressed_grad_sync(mesh, axis="pod")
    with mesh:
        got = sync({"g": xs})["g"]
    # every pod slice holds the mean over pod shards
    want = jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True), x.shape)
    err = float(jnp.max(jnp.abs(got - want)))
    bound = 2 * compression_error_bound(x)  # sum of 2 quantised operands / 2
    assert err <= bound + 1e-6, (err, bound)
    assert err < 0.05, err
    print("PASS compressed_psum")


def check_decode_cache_stays_sharded():
    """Sequence-sharded decode: lowering keeps the kv cache sharded (no
    all-gather of the cache itself)."""
    import re
    cfg = REDUCED["qwen3-32b"]
    mesh = make_mesh_for(2, 4)
    from repro.core.blueprint import suggest_plan
    from repro.launch.specs import decode_specs
    from repro.train.steps import make_serve_step
    shape = ShapeConfig("d", 4096, 8, "decode")
    plan = suggest_plan(cfg, shape, mesh)
    params, cache, tokens, cur = decode_specs(cfg, shape, mesh, plan)
    step = make_serve_step(cfg, mesh=mesh, act_rules=plan.act_rules)
    with mesh:
        compiled = jax.jit(step).lower(params, cache, tokens, cur).compile()
    hlo = compiled.as_text()
    cache_bytes = 4096 * cfg.n_kv_heads * 128 * 2  # per batch row, bf16
    # no all-gather output as large as a full cache leaf
    big = 0
    for m in re.finditer(r"bf16\[([\d,]+)\][^ ]* all-gather", hlo):
        dims = [int(d) for d in m.group(1).split(",")]
        n = 1
        for d in dims:
            n *= d
        big = max(big, n * 2)
    assert big < cache_bytes, (big, cache_bytes)
    print("PASS decode_cache_stays_sharded")


def check_gpipe_matches_sequential():
    """Pipeline-parallel execution over 4 stages == sequential layer loop."""
    from repro.parallel.pipeline import gpipe_forward, pipeline_bubble_fraction
    L, B, D, F = 8, 8, 32, 64
    key = jax.random.PRNGKey(3)
    w1 = jax.random.normal(key, (L, D, F), jnp.float32) * 0.2
    w2 = jax.random.normal(jax.random.fold_in(key, 1), (L, F, D)) * 0.2
    params = {"w1": w1, "w2": w2}
    x = jax.random.normal(jax.random.fold_in(key, 2), (B, D))

    def body(pl, h):
        return h + jnp.tanh(h @ pl["w1"]) @ pl["w2"]

    ref = x
    for i in range(L):
        ref = body(jax.tree.map(lambda a: a[i], params), ref)

    mesh = jax.make_mesh((4,), ("stage",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    ps = jax.tree.map(
        lambda a: jax.device_put(a, NamedSharding(mesh, P("stage"))), params)
    with mesh:
        out = gpipe_forward(ps, x, body=body, mesh=mesh, axis="stage",
                            n_micro=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert abs(pipeline_bubble_fraction(4, 4) - 3 / 7) < 1e-9
    print("PASS gpipe_matches_sequential")


def check_shard_group_paged_decode():
    """Tensor-parallel shard group under real shard_map (2 devices on the
    "model" axis): per-shard pools + head-sliced weights, one program per
    device, head all_gather on the wire — tokens match the single-device
    tp=1 decode and the in-program unrolled-loop tp=2 path."""
    import dataclasses

    from repro.parallel.context import ShardGroup
    from repro.serving import paged_cache as PC

    cfg = dataclasses.replace(REDUCED["qwen3-32b"], dtype="float32")
    params = M.init(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh_for(4, 2)              # ("data", "model"): model axis 2
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg.vocab_size, size=11).astype(np.int32)
    from repro.models.transformer import lm_forward

    def run(shard):
        tp = 1 if shard is None else shard.tp
        cache = PC.init_paged_cache(cfg, 6, 8, 2, tp=tp)
        _, _, pre = lm_forward(cfg, params, jnp.asarray(prompt[None]),
                               mode="prefill")
        row = np.array([1, 2, 0], np.int32)
        cache = PC.write_prefill(cfg, cache, pre, jnp.asarray(row), 0,
                                 len(prompt), len(prompt), 8, tp=tp)
        bt = np.zeros((2, 3), np.int32)
        bt[0] = row
        lens = np.array([len(prompt), 0], np.int32)
        last = np.array([[3], [0]], np.int32)
        toks = []
        for _ in range(5):
            lg, cache = M.paged_decode_step(
                cfg, params, cache, jnp.asarray(last), jnp.asarray(lens),
                jnp.asarray(bt), shard=shard)
            nxt = int(jnp.argmax(lg[0, -1, :cfg.vocab_size]))
            toks.append(nxt)
            last[0, 0] = nxt
            lens[0] += 1
        return toks

    want = run(None)
    loop = run(ShardGroup(2))
    with mesh:
        spmd = run(ShardGroup(2, mesh=mesh))
    assert want == loop == spmd, (want, loop, spmd)
    print("PASS shard_group_paged_decode")


def check_chunked_prefill_tp2():
    """Chunked prefill composes with a tp=2 shard group under real
    shard_map: per-tick chunk budgets drive the bucketed prefill and
    suffix programs on head-sliced per-shard pools, one control plane —
    tokens match both single-device monolithic serving and the
    in-program unrolled-loop tp=2 path."""
    import dataclasses

    from repro.serving.scheduler import ContinuousBatchingScheduler

    cfg = dataclasses.replace(REDUCED["qwen3-32b"], dtype="float32")
    params = M.init(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh_for(4, 2)              # ("data", "model"): model axis 2
    rng = np.random.RandomState(1)
    trace = [(rng.randint(0, cfg.vocab_size, size=p).astype(np.int32), g)
             for p, g in ((13, 3), (21, 4), (6, 3))]

    def serve(tp, budget, shard_mesh=None):
        s = ContinuousBatchingScheduler(
            cfg, params, max_slots=2, page_size=8, max_seq_len=48,
            prefix_cache=False, tp=tp, shard_mesh=shard_mesh,
            prefill_budget=budget)
        reqs = [s.submit(p, g, arrival_step=i)
                for i, (p, g) in enumerate(trace)]
        s.run()
        assert s.alloc.num_allocated == 0 and s.reserved_pages == 0
        return [list(r.out_tokens) for r in reqs]

    want = serve(1, None)
    assert serve(1, 4) == want              # chunked == monolithic, tp=1
    assert serve(2, 4) == want              # + tp=2 unrolled loop
    with mesh:
        assert serve(2, 4, shard_mesh=mesh) == want   # + real shard_map
    print("PASS chunked_prefill_tp2")


def check_fleet_replica_placement():
    """One device per replica: a 4-replica fleet (reduced config, fp32) on
    a 4-device host puts each replica's params and page pools on its own
    device, and emits byte-identical tokens to the same requests on one
    replica."""
    import dataclasses

    from repro.serving.router import ServingRouter

    assert jax.device_count() == 4, jax.devices()
    cfg = dataclasses.replace(REDUCED["qwen3-32b"], dtype="float32")
    params = M.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(4)
    trace = [(rng.randint(0, cfg.vocab_size, size=p).astype(np.int32), g)
             for p, g in ((13, 5), (21, 4), (6, 6), (30, 3), (17, 5),
                          (9, 4), (25, 6), (11, 3))]

    def serve(k):
        router = ServingRouter(cfg, params, replicas=k, max_slots=2,
                               page_size=8, max_seq_len=48,
                               route_policy="round-robin")
        reqs = [router.submit(p, g, arrival_step=i // 2)
                for i, (p, g) in enumerate(trace)]
        router.run()
        return router, [list(r.out_tokens) for r in reqs]

    fleet, got = serve(4)
    placed = []
    for rep in fleet.replicas.values():
        for tree in (rep.sched.params, rep.sched.cache):
            devs = {d for leaf in jax.tree_util.tree_leaves(tree)
                    for d in leaf.devices()}
            assert len(devs) == 1, (rep.replica_id, devs)
        assert rep.devices() == [str(d) for d in devs], rep.devices()
        placed.append(devs.pop())
    assert sorted(placed, key=str) == sorted(jax.devices(), key=str), placed
    assert fleet.fleet_stats()["tokens_out"] == sum(g for _, g in trace)
    assert all(r.sched.stats["tokens_out"] > 0      # all four served
               for r in fleet.replicas.values())
    _, want = serve(1)
    assert got == want, (got, want)
    print("PASS fleet_replica_placement")


if __name__ == "__main__":
    checks = {name[len("check_"):]: fn
              for name, fn in sorted(globals().items())
              if name.startswith("check_")}
    wanted = sys.argv[1:] or list(checks)
    for name in wanted:
        checks[name]()
    print("ALL_OK")
