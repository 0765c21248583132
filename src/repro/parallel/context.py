"""Parallel context: activation-sharding constraints usable from model code.

Model code names *logical* activation axes; the active ``ParallelCtx`` (set
by the train/serve step builders) maps them to mesh axes. With no context
(single-device smoke tests) constraints are no-ops, so model code never
needs to know whether it is distributed.

``ShardGroup`` is the serving fabric's unit of tensor parallelism: one
logical replica spanning ``tp`` devices on a model-parallel mesh axis.
Everything shard-aware — the head-sharded paged-decode path in
``repro.models.attention``, the per-shard page pools in
``repro.serving.paged_cache``, the per-shard budgets in
``repro.core.blueprint.serving_page_plan``, and the shard-group node
placement in ``repro.core.services`` / ``repro.autoscale.fleet`` — is
parameterised by one of these.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding

from repro.models.schema import resolve_pspec

# default logical activation-axis rules (planner may override per blueprint)
ACT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "act_seq": ("data",),        # sequence sharding (long-context decode)
    "heads_act": ("model",),
    "ff_act": ("model",),
    "experts_act": ("model",),
    "vocab_act": ("model",),
    "cache_seq": ("model",),     # decode-cache sequence dim
    "kv_heads": ("model",),
}

_STATE = threading.local()


@dataclass
class ParallelCtx:
    mesh: Mesh
    rules: Dict[str, Tuple[str, ...]] = field(default_factory=lambda: dict(ACT_RULES))


def current() -> Optional[ParallelCtx]:
    return getattr(_STATE, "ctx", None)


@contextlib.contextmanager
def use_parallel(mesh: Mesh, rules: Optional[Dict[str, Tuple[str, ...]]] = None):
    prev = current()
    _STATE.ctx = ParallelCtx(mesh, {**ACT_RULES, **(rules or {})})
    try:
        yield _STATE.ctx
    finally:
        _STATE.ctx = prev


@dataclass(frozen=True)
class ShardGroup:
    """``tp`` devices on one model-parallel mesh axis acting as one logical
    serving replica.

    The group is the fabric's scale-*up* unit: a replica's page pools,
    attention heads, and MoE experts split ``tp`` ways across the group's
    members while the block table, allocator refcounts, and prefix index
    stay a single (logical) control plane — see docs/sharding.md.

    ``mesh`` is optional. With a mesh whose ``axis`` has size ``tp``, the
    sharded decode step runs under ``jax.shard_map`` (one program per
    device, the head-axis ``all_gather`` on the wire). Without one, the
    same per-shard body runs as an unrolled loop inside a single program —
    semantically identical, which is what makes the tp>1 vs tp=1
    byte-identity gate testable on any host.
    """
    tp: int = 1
    axis: str = "model"
    mesh: Optional[Mesh] = None

    def __post_init__(self):
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {self.tp}")
        if self.mesh is not None:
            sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
            if self.axis not in sizes:
                raise ValueError(
                    f"mesh has no {self.axis!r} axis (axes: "
                    f"{tuple(self.mesh.axis_names)})")
            if sizes[self.axis] != self.tp:
                raise ValueError(
                    f"mesh {self.axis!r} axis has size {sizes[self.axis]}, "
                    f"shard group needs {self.tp}")

    @property
    def is_sharded(self) -> bool:
        return self.tp > 1

    @property
    def use_shard_map(self) -> bool:
        """True when the group should run one program per device."""
        return self.mesh is not None and self.tp > 1

    def validate_model(self, cfg) -> None:
        """Raise if ``cfg`` cannot split ``tp`` ways (head/expert counts)."""
        if self.tp == 1:
            return
        if cfg.attn_impl == "mla":
            raise ValueError(
                f"{cfg.name}: MLA decode keeps the dense absorbed path; "
                "shard groups cover GQA/SSM/MoE paged serving")
        problems = []
        if cfg.n_heads % self.tp:
            problems.append(f"n_heads {cfg.n_heads}")
        if cfg.n_kv_heads % self.tp:
            problems.append(f"n_kv_heads {cfg.n_kv_heads}")
        if cfg.n_routed_experts and cfg.n_routed_experts % self.tp:
            problems.append(f"n_routed_experts {cfg.n_routed_experts}")
        if problems:
            raise ValueError(
                f"{cfg.name}: tp={self.tp} must divide "
                + ", ".join(problems))

    def shard_heads(self, n: int) -> int:
        """Heads (query, kv, or expert count) one shard owns."""
        assert n % self.tp == 0, (n, self.tp)
        return n // self.tp


def constrain(x: jax.Array, axes: Tuple[Optional[str], ...]) -> jax.Array:
    """Apply with_sharding_constraint mapping logical axes via the context."""
    ctx = current()
    if ctx is None:
        return x
    pspec = resolve_pspec(tuple(axes), tuple(x.shape), ctx.rules, ctx.mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, pspec))
