#!/usr/bin/env python3
"""Chip smoke: the serving main path on a TPU, at published widths.

Serves qwen3-32b at its published widths (d_model 5120, 64/8 heads,
head_dim 128, d_ff 25600, the whole 151,936-token vocabulary, untied head),
cut only in depth to 2 layers, with float32 params made from a seed
(~10.1 GB), through the launcher: ``repro.launch.serve --engine paged
--layers 2``.

One chip (no arguments), in one process:

  a. 8 requests of 256-512-token prompts and 16-32 generated tokens,
     monolithic prefill;
  b. the same requests with ``--chunked-prefill 256`` (the fused chunk
     program);
  c. the Pallas kernels compiled for the chip (paged prefill write +
     attend, paged decode) against their ``repro.kernels.ref`` oracles.

Phases a and b check that every request finished with its full token
count and ids inside the vocabulary, and hold every request's first two
tokens to a plain dense causal forward (``lm_forward``, no paged cache).

``--chips 4`` runs only the four-replica fleet (``--replicas 4 --layers
2``) and, as its comparison, the same requests on one replica. It checks
that each replica's params and page pool sit on their own chip and that
the tokens equal the one-replica run.

Progress goes to stdout; the last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
Without a TPU, or when any phase fails, the script exits non-zero and
prints no such line. Run it from the checkout root:

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips

JAX's persistent compilation cache goes to ``$JAX_COMPILATION_CACHE_DIR``
when set, else to ``.jax_cache/`` at the checkout root.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
ARCH, LAYERS, SEED = "qwen3-32b", 2, 0
N_REQUESTS = 8
LAUNCH = ["--arch", ARCH, "--engine", "paged", "--layers", str(LAYERS),
          "--seed", str(SEED), "--requests", str(N_REQUESTS),
          "--prompt-len", "512", "--gen", "32", "--batch", "8"]
# A served token agrees with the dense forward when its dense logit is
# within this many standard deviations of the row's logits below the dense
# maximum. Both run bf16 activations but round in different orders (paged
# vs contiguous attention, fused vs unfused programs), which moves a logit
# by well under 0.01 sd at these widths; a wrong token is a draw from
# 151,936 logits and sits ~4 sd below the maximum.
DENSE_TOL_SD = 0.05
# Kernel outputs are bf16 (one ulp is 2^-8 ~ 3.9e-3 at magnitude 1); the
# kernels' page-by-page online softmax sums in another order than the
# oracle's one-shot softmax, and the MXU may round the f32 probabilities
# to bf16 for p @ v. 2e-2 is five bf16 ulps, absolute and relative.
KERNEL_TOL = 2e-2


def log(*parts) -> None:
    print(*parts, flush=True)


class CompileClock:
    """Seconds XLA spends compiling, summed from JAX's own monitoring
    events (a program read back from the persistent cache adds none)."""

    def __init__(self):
        import jax
        self.seconds, self.programs = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1


def peak_bytes() -> int:
    import jax
    return jax.devices()[0].memory_stats()["peak_bytes_in_use"]


def serve_phase(name, extra, params, clock):
    """Serve the workload through the launcher; check counts and ids."""
    from repro.launch import serve
    args = serve.parse_args(LAUNCH + extra)
    cfg = serve.model_config(args)
    s0, p0, t0 = clock.seconds, clock.programs, time.perf_counter()
    out, done = serve.serve(args, params)
    wall = time.perf_counter() - t0
    assert len(done) == N_REQUESTS, (name, len(done))
    for r in done:
        assert len(r.out_tokens) == r.max_new_tokens, (name, r.rid)
        assert all(0 <= t < cfg.vocab_size for t in r.out_tokens), \
            (name, r.rid)
    assert out["tokens_out"] == sum(len(r.out_tokens) for r in done), name
    plens = [r.plen for r in done]
    log(f"[{name}] launcher args: {' '.join(LAUNCH + extra)}")
    log(f"[{name}] requests served {len(done)}, prompt tokens "
        f"{min(plens)}-{max(plens)}, tokens out {out['tokens_out']}, "
        f"wall {wall:.1f} s, of which compile {clock.seconds - s0:.1f} s "
        f"({clock.programs - p0} programs), peak_bytes_in_use "
        f"{peak_bytes()}")
    log(f"[{name}] result: {json.dumps(out)}")
    return cfg, done


def check_against_dense(name, cfg, params, done):
    """Each request's first two tokens vs a plain dense causal forward of
    prompt + first token: the dense logit of each served token must sit
    within DENSE_TOL_SD sd of the dense maximum."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as M
    from repro.models.transformer import lm_forward

    S = -(-(max(r.plen for r in done) + 1) // 128) * 128
    toks = np.zeros((len(done), S), np.int32)
    at = np.zeros((len(done), 2), np.int32)
    for i, r in enumerate(done):
        toks[i, :r.plen] = r.prompt
        toks[i, r.plen] = r.out_tokens[0]
        at[i] = (r.plen - 1, r.plen)

    @jax.jit
    def dense_logits(params, toks, at):
        hidden, _ = lm_forward(cfg, params, toks, mode="train")
        h = jnp.take_along_axis(hidden, at[..., None], axis=1)
        return M.final_logits(cfg, params, h)[..., :cfg.vocab_size]

    lg = np.asarray(dense_logits(params, toks, at))          # (R, 2, V)
    exact, worst = 0, 0.0
    for i, r in enumerate(done):
        for j in range(2):
            row, tok = lg[i, j], r.out_tokens[j]
            gap = float(row.max() - row[tok]) / float(row.std())
            worst = max(worst, gap)
            exact += int(row.argmax() == tok)
            assert gap <= DENSE_TOL_SD, (name, r.rid, j, tok,
                                         int(row.argmax()), gap)
    log(f"[{name}] dense reference: {exact}/{2 * len(done)} tokens equal "
        f"its argmax, worst gap {worst:.4f} sd (limit {DENSE_TOL_SD})")


def kernel_phase(clock):
    """The paged Pallas kernels, compiled for the chip, vs the oracles."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import get_arch
    from repro.kernels import ops, ref, resolve_interpret

    assert not resolve_interpret(), "kernels would run interpreted"
    s0 = clock.seconds
    for arch in ("qwen3-32b", "gemma2-2b"):
        cfg = get_arch(arch)
        H, KVH, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
        B, S, ps, n_pg, P = 2, 256, 16, 24, 64
        softcap, window = cfg.attn_softcap, cfg.sliding_window
        keys = jax.random.split(jax.random.PRNGKey(SEED), 6)

        def rand(k, shape):
            return jax.random.normal(k, shape, jnp.float32).astype(
                jnp.bfloat16)

        q, k_new, v_new = (rand(keys[0], (B, S, H, d)),
                           rand(keys[1], (B, S, KVH, d)),
                           rand(keys[2], (B, S, KVH, d)))
        pool = {"k_pages": rand(keys[3], (P, ps, KVH, d)),
                "v_pages": rand(keys[4], (P, ps, KVH, d))}
        bt = jnp.asarray(np.random.RandomState(SEED).permutation(
            np.arange(1, P))[:B * n_pg].reshape(B, n_pg), jnp.int32)
        start = jnp.asarray([100, 0], jnp.int32)
        lens = jnp.asarray([S, 180], jnp.int32)

        o, new_pool = ops.paged_prefill(q, k_new, v_new, pool, bt, start,
                                        lens, softcap=softcap, window=window)
        want_pool = ref.paged_prefill_write_ref(k_new, v_new, pool, bt,
                                                start, lens)
        for leaf in ("k_pages", "v_pages"):
            assert bool(jnp.array_equal(new_pool[leaf], want_pool[leaf])), \
                (arch, leaf)
        with jax.default_matmul_precision("highest"):
            want = ref.paged_prefill_attention_ref(
                q, want_pool["k_pages"], want_pool["v_pages"], bt, start,
                lens, softcap=softcap, window=window)
        err = 0.0
        for b in range(B):
            n = int(lens[b])
            got_b = np.asarray(o[b, :n], np.float32)
            want_b = np.asarray(want[b, :n], np.float32)
            np.testing.assert_allclose(got_b, want_b, atol=KERNEL_TOL,
                                       rtol=KERNEL_TOL)
            err = max(err, float(np.abs(got_b - want_b).max()))

        q_dec = rand(keys[5], (B, H, d))
        seq_lens = start + lens
        got = ops.paged_decode_attention(
            q_dec, want_pool["k_pages"], want_pool["v_pages"], bt, seq_lens,
            softcap=softcap, window=window)
        with jax.default_matmul_precision("highest"):
            want = ref.paged_decode_attention_ref(
                q_dec, want_pool["k_pages"], want_pool["v_pages"], bt,
                seq_lens, softcap=softcap, window=window)
        got, want = (np.asarray(got, np.float32),
                     np.asarray(want, np.float32))
        np.testing.assert_allclose(got, want, atol=KERNEL_TOL,
                                   rtol=KERNEL_TOL)
        log(f"[kernels] {arch} widths H={H} KVH={KVH} d={d} page={ps} "
            f"chunk={S} bf16: paged_prefill write bit-identical, attend "
            f"max abs err {err:.2e}, paged_decode_attention max abs err "
            f"{float(np.abs(got - want).max()):.2e} (tolerance "
            f"{KERNEL_TOL})")
    log(f"[kernels] compile {clock.seconds - s0:.1f} s, peak_bytes_in_use "
        f"{peak_bytes()}")


def fleet_phase(params, clock):
    """Four replicas, one per chip, vs the same requests on one replica."""
    from repro.launch import serve
    runs = {}
    for name, extra in (("fleet4", ["--replicas", "4"]),
                        ("single", [])):
        args = serve.parse_args(LAUNCH + extra)
        s0, t0 = clock.seconds, time.perf_counter()
        out, done = serve.serve(args, params)
        assert len(done) == N_REQUESTS, (name, len(done))
        for r in done:
            assert len(r.out_tokens) == r.max_new_tokens, (name, r.rid)
        runs[name] = {r.rid: list(r.out_tokens) for r in done}
        log(f"[{name}] launcher args: {' '.join(LAUNCH + extra)}")
        log(f"[{name}] requests served {len(done)}, tokens out "
            f"{out['tokens_out']}, wall {time.perf_counter() - t0:.1f} s, "
            f"of which compile {clock.seconds - s0:.1f} s")
        log(f"[{name}] result: {json.dumps(out)}")
        if name == "fleet4":
            placed = out["replica_devices"]
            log(f"[fleet4] replica devices: {placed}")
            assert len(placed) == 4, placed
            assert all(len(devs) == 1 for devs in placed.values()), placed
            assert len({devs[0] for devs in placed.values()}) == 4, placed
        del out, done
        gc.collect()
    for rid in sorted(runs["single"]):
        a, b = runs["fleet4"][rid], runs["single"][rid]
        if a != b:
            first = next(i for i, (x, y) in enumerate(zip(a, b)) if x != y)
            raise AssertionError(
                f"request {rid}: fleet and one-replica tokens differ from "
                f"position {first}: {a[first:first + 8]} vs "
                f"{b[first:first + 8]}")
    log(f"[fleet4] tokens of all {len(runs['single'])} requests equal the "
        "one-replica run")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases a-c on one chip; 4: the four-replica "
                    "fleet against one replica, on four chips")
    opts = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found "
                 f"{devices[0].platform!r}")
    if len(devices) < opts.chips:
        sys.exit(f"chip_smoke: --chips {opts.chips} needs {opts.chips} "
                 f"TPU devices; JAX found {len(devices)}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch import serve
    from repro.models import model as M

    log(f"device: {devices[0].device_kind} x {len(devices)}; compile "
        f"cache: {use_compile_cache()}")
    clock = CompileClock()
    cfg = serve.model_config(serve.parse_args(LAUNCH))
    t0 = time.perf_counter()
    params = M.init(cfg, jax.random.PRNGKey(SEED))
    jax.block_until_ready(params)
    n_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    log(f"config: {cfg.name} at published widths, cut to {cfg.n_layers} of "
        f"64 layers (d_model {cfg.d_model}, heads {cfg.n_heads}/"
        f"{cfg.n_kv_heads}, head_dim {cfg.head_dim}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab_size}, untied head, params "
        f"{cfg.param_dtype}, activations {cfg.dtype})")
    log(f"params: {n_bytes} bytes, made from seed {SEED} in "
        f"{time.perf_counter() - t0:.1f} s")

    if opts.chips == 4:
        fleet_phase(params, clock)
    else:
        for name, extra in (("a-monolithic", []),
                            ("b-chunked", ["--chunked-prefill", "256"])):
            cfg, done = serve_phase(name, extra, params, clock)
            check_against_dense(name, cfg, params, done)
        del params
        gc.collect()
        kernel_phase(clock)
    log(f"total compile {clock.seconds:.1f} s over {clock.programs} "
        f"programs, peak_bytes_in_use {peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
