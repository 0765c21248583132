"""The device decides, in one place each: Pallas ``interpret`` follows JAX's
backend, device peaks come from one table keyed by ``device_kind``, the
launcher's compile cache sits where ``JAX_COMPILATION_CACHE_DIR`` says or
at a fixed checkout path, and ``--layers`` serves published widths."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs.registry import REDUCED, get_arch
from repro.kernels import resolve_interpret
from repro.launch import compile_cache, serve
from repro.models import model as M
from repro.obs.profile import KernelProfiler, device_peaks
from repro.serving.scheduler import ContinuousBatchingScheduler


def test_interpret_follows_backend_and_kernel_path_matches_xla():
    """On the CPU backend the kernels run interpreted without anyone
    asking, and the scheduler's Pallas prefill path (no ``interpret``
    passed anywhere) emits the XLA path's tokens."""
    assert jax.default_backend() == "cpu"
    assert resolve_interpret() is True
    assert resolve_interpret(False) is False
    cfg = dataclasses.replace(REDUCED["qwen3-32b"], dtype="float32")
    params = M.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.RandomState(3)
    work = [(rng.randint(0, cfg.vocab_size, size=n), g)
            for n, g in ((21, 5), (9, 6), (30, 4))]

    def tokens(kernel):
        sched = ContinuousBatchingScheduler(
            cfg, params, max_slots=2, max_seq_len=64, prefill_budget=8,
            prefill_kernel=kernel)
        for prompt, gen in work:
            sched.submit(prompt, gen)
        return {r.rid: r.out_tokens for r in sched.run()}

    assert tokens(True) == tokens(False)


class _Device:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def test_unknown_device_kind_raises():
    v5e = device_peaks("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bw) == (197e12, 819e9)
    assert "TPU v5e" in v5e.source
    with pytest.raises(KeyError, match="no published peaks"):
        device_peaks("TPU v99")
    with pytest.raises(KeyError, match="no published peaks"):
        KernelProfiler(device=_Device("TPU v99"))
    assert KernelProfiler(device=_Device("TPU v5 lite")).peaks is v5e
    assert KernelProfiler().peaks is None            # the CPU has none


def test_compile_cache_directory(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.use_compile_cache()
        assert path == str(compile_cache.CHECKOUT_ROOT / ".jax_cache")
        assert (compile_cache.CHECKOUT_ROOT / "src" / "repro").is_dir()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_layers_serves_published_widths():
    base = ["--arch", "qwen3-32b", "--engine", "paged"]
    assert serve.model_config(serve.parse_args(base)) == REDUCED["qwen3-32b"]
    cfg = serve.model_config(serve.parse_args(base + ["--layers", "2"]))
    assert cfg == dataclasses.replace(get_arch("qwen3-32b"), n_layers=2)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
            cfg.vocab_size, cfg.param_dtype) == (5120, 64, 8, 25600,
                                                 151936, "float32")
    # gemma2-2b alternates local/global layers: depth cuts whole periods
    for bad in (["--arch", "gemma2-2b", "--layers", "3"],
                base + ["--layers", "0"], base + ["--layers", "65"]):
        with pytest.raises(SystemExit):
            serve.parse_args(bad)
