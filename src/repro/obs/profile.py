"""Opt-in kernel profiling: wall time + modeled bytes/FLOPs per dispatch.

``KernelProfiler`` records every paged-decode / prefill dispatch the
serving stack makes: per-call wall time (after ``jax.block_until_ready``
so async dispatch doesn't under-report), plus *modeled* work — FLOPs
from the active-parameter count and bytes-moved from the weight +
paged-KV traffic the call implies. Dividing modeled work by the machine
peaks gives a roofline-utilization fraction per kernel kind:

    frac = max(flops / peak_flops, bytes / hbm_bw) / wall_seconds

i.e. how close the call came to the speed-of-light time its heavier
bottleneck allows (1.0 = on the roofline). The peaks are the published
figures of the device the process runs on, looked up by its
``device_kind`` in ``DEVICE_PEAKS``; a kind missing from the table
raises. The CPU backend has no such peaks, so there the fraction is None.

Two attachment styles:

* scheduler-level — ``ContinuousBatchingScheduler.enable_profiling()``
  times whole dispatches with token/context detail (decode batch size,
  prefill chunk length);
* ops-level — ``repro.kernels.ops.set_profile_hook(profiler.hook())``
  times individual kernel entry points with byte counts taken from the
  actual array arguments.

Profiling is read-only: it never touches model state, so profiled runs
emit byte-identical tokens (same contract as tracing).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional

import jax

__all__ = ["KernelProfiler", "DevicePeaks", "DEVICE_PEAKS", "device_peaks",
           "PLAN_DEVICE_KIND", "PEAK_FLOPS", "HBM_BW", "PCIE_BW",
           "PCIE_LATENCY"]


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one accelerator kind."""
    bf16_flops: float        # FLOP/s
    hbm_bw: float            # HBM bytes/s
    ici_bw: float            # chip-to-chip interconnect bytes/s, all links
    source: str


# The one table of device peaks, keyed by ``jax.Device.device_kind``. The
# profiler, the dry-run roofline and the modeled cost terms all read it.
DEVICE_PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12, hbm_bw=819e9, ici_bw=1600e9 / 8,
        source='Google Cloud documentation, "TPU v5e": per chip 197 '
               'TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, '
               '1,600 Gbit/s interchip interconnect'),
}


def device_peaks(device_kind: str) -> DevicePeaks:
    """Peaks of ``device_kind``. A kind missing from ``DEVICE_PEAKS`` is
    an error, never a default: a number divided by the wrong chip's peak
    would read as a real utilization."""
    if device_kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            f"DEVICE_PEAKS with its source (known: {sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[device_kind]


# The chip that modeled (not measured) cost terms plan for: the dry-run
# roofline, the host tier's recompute-vs-transfer model and the benches'
# roofline models. A TPU v5e.
PLAN_DEVICE_KIND = "TPU v5 lite"
PEAK_FLOPS = device_peaks(PLAN_DEVICE_KIND).bf16_flops
HBM_BW = device_peaks(PLAN_DEVICE_KIND).hbm_bw
# Modeled host<->device interconnect (PCIe gen4 x16-class): sustained
# bytes/s plus a fixed per-transfer setup cost (DMA programming, host
# pinning, completion interrupt). The serving host-RAM KV tier's
# recompute-vs-transfer cost model compares a swap-in against re-running
# the prefill at PEAK_FLOPS — the fixed latency term is what makes short
# chains cheaper to recompute and long chains cheaper to move.
PCIE_BW = 32e9
PCIE_LATENCY = 100e-6


class KernelProfiler:
    """Accumulates per-kind dispatch timings and modeled work.

    ``cfg`` (a model config) enables the modeled-bytes/FLOPs defaults:
    2 * active_params FLOPs per generated token, weight bytes + paged-KV
    bytes per token of attended context. Without ``cfg`` only wall time
    and explicitly-passed work are recorded.

    ``device`` (default ``jax.devices()[0]``) is the chip whose peaks the
    roofline fraction divides by; a tp shard group counts ``tp`` of them.
    """

    def __init__(self, cfg: Any = None, *, tp: int = 1,
                 dtype_bytes: int = 2, device: Any = None) -> None:
        self.cfg = cfg
        self.tp = max(1, int(tp))
        dev = device if device is not None else jax.devices()[0]
        self.peaks = (None if dev.platform == "cpu"
                      else device_peaks(dev.device_kind))
        self.enabled = True
        self._param_bytes = 0.0
        self._active_params = 0.0
        self._kv_bytes_per_token = 0.0
        if cfg is not None:
            # late import keeps `import repro.obs` free of serving deps
            from repro.serving import paged_cache as PC
            self._active_params = float(cfg.active_param_count())
            self._param_bytes = self._active_params * dtype_bytes
            self._kv_bytes_per_token = float(PC.page_bytes_per_token(cfg))
        self.records: Dict[str, Dict[str, float]] = {}

    # ------------------------------------------------------------ record --
    def _bucket(self, kind: str) -> Dict[str, float]:
        return self.records.setdefault(kind, {
            "calls": 0.0, "wall_s": 0.0,
            "modeled_flops": 0.0, "modeled_bytes": 0.0,
        })

    def record(self, kind: str, wall_s: float, *, tokens: int = 0,
               ctx_tokens: int = 0, flops: Optional[float] = None,
               bytes_moved: Optional[float] = None) -> None:
        """One dispatch: ``tokens`` generated/processed, ``ctx_tokens`` of
        KV context attended. FLOPs/bytes default to the cfg-derived model
        and can be overridden per call."""
        if not self.enabled:
            return
        if flops is None:
            flops = 2.0 * self._active_params * tokens
        if bytes_moved is None:
            bytes_moved = (self._param_bytes
                           + self._kv_bytes_per_token * (tokens + ctx_tokens))
        b = self._bucket(kind)
        b["calls"] += 1
        b["wall_s"] += float(wall_s)
        b["modeled_flops"] += float(flops)
        b["modeled_bytes"] += float(bytes_moved)

    def record_op(self, kind: str, wall_s: float, args: Any) -> None:
        """Ops-level record: bytes = actual array traffic (sum of argument
        buffer sizes), no FLOP model."""
        if not self.enabled:
            return
        nbytes = sum(getattr(leaf, "nbytes", 0)
                     for leaf in jax.tree_util.tree_leaves(args))
        self.record(kind, wall_s, flops=0.0, bytes_moved=float(nbytes))

    def hook(self) -> Callable[[str, float, Any], None]:
        """Adapter for ``repro.kernels.ops.set_profile_hook``."""
        return self.record_op

    def timed(self, kind: str, fn: Callable[..., Any], *args: Any,
              tokens: int = 0, ctx_tokens: int = 0, **kw: Any) -> Any:
        """Call ``fn``, block on its outputs, record the wall time."""
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        out = jax.block_until_ready(out)
        self.record(kind, time.perf_counter() - t0,
                    tokens=tokens, ctx_tokens=ctx_tokens)
        return out

    # ----------------------------------------------------------- report --
    def summary(self) -> Dict[str, Dict[str, float]]:
        """Per-kind totals plus the roofline-utilization fraction:
        modeled speed-of-light time (max of compute- and bandwidth-bound
        times) over measured wall time; None on the CPU backend, which
        has no published peaks."""
        out: Dict[str, Dict[str, Any]] = {}
        for kind, b in sorted(self.records.items()):
            wall = b["wall_s"]
            frac = None
            if self.peaks is not None:
                sol = max(b["modeled_flops"]
                          / (self.peaks.bf16_flops * self.tp),
                          b["modeled_bytes"] / (self.peaks.hbm_bw * self.tp))
                frac = (sol / wall) if wall > 0 else 0.0
            out[kind] = {
                "calls": int(b["calls"]),
                "wall_s": wall,
                "modeled_flops": b["modeled_flops"],
                "modeled_bytes": b["modeled_bytes"],
                "roofline_frac": frac,
            }
        return out

    def reset(self) -> None:
        self.records.clear()
