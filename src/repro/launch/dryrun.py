import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: sharding
mismatches, compile-time OOM and unsupported collectives all surface here.
Per cell it records ``memory_analysis()`` (fits-in-HBM proof),
``cost_analysis()`` (FLOPs/bytes for the roofline) and the collective
schedule parsed from the compiled HLO.

NOTE: the XLA_FLAGS line above must run before any other import — jax locks
the device count on first init. Smoke tests and benches (which want 1
device) must never import this module first.
"""
import argparse
import json
import pathlib
import re
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES, cell_is_runnable
from repro.configs.registry import ARCHS, get_arch, get_shape
from repro.core.blueprint import suggest_plan
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs
from repro.obs.profile import PLAN_DEVICE_KIND, device_peaks
from repro.optim.adamw import OptimConfig
from repro.train import steps as steps_mod

# ---------------------------------------------------------------------------
# roofline hardware constants: the planned chip's row of the one peaks table
# ---------------------------------------------------------------------------
_PEAKS = device_peaks(PLAN_DEVICE_KIND)
PEAK_FLOPS = _PEAKS.bf16_flops       # bf16 FLOP/s per chip
HBM_BW = _PEAKS.hbm_bw               # B/s per chip
LINK_BW = _PEAKS.ici_bw / 4          # B/s per ICI link (4 per chip, 2-D torus)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo: str) -> Dict[str, Any]:
    """Per-device collective byte totals from post-SPMD HLO text."""
    per_op: Dict[str, Dict[str, float]] = {}
    total_operand = 0.0
    total_wire = 0.0
    for line in hlo.splitlines():
        if " = " not in line:
            continue
        _, rhs = line.split(" = ", 1)
        # rhs looks like "f32[16,1024]{1,0} all-reduce(%x), ..." (shapes
        # first, then the op) — instruction *names* on the lhs also contain
        # the op token, so only match in the rhs after the output shape.
        m = _COLL_RE.search(rhs)
        if not m or m.group(2) == "-done":
            continue
        op = m.group(1)
        # output shapes: everything before the op token in the rhs
        head = rhs[:m.start()]
        out_bytes = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(head))
        if out_bytes == 0:
            continue
        g = 1
        gm = _GROUPS_LIST_RE.search(line)
        if gm:
            g = len(gm.group(1).split(","))
        else:
            gm = _GROUPS_IOTA_RE.search(line)
            if gm:
                g = int(gm.group(2))
        if g <= 1:
            continue
        if op == "all-gather":
            operand = out_bytes / g
            wire = out_bytes * (g - 1) / g
        elif op == "all-reduce":
            operand = out_bytes
            wire = 2 * out_bytes * (g - 1) / g
        elif op == "reduce-scatter":
            operand = out_bytes * g
            wire = out_bytes * (g - 1)
        elif op == "all-to-all":
            operand = out_bytes
            wire = out_bytes * (g - 1) / g
        else:  # collective-permute
            operand = out_bytes
            wire = out_bytes
        rec = per_op.setdefault(op, {"count": 0, "operand_bytes": 0.0,
                                     "wire_bytes": 0.0})
        rec["count"] += 1
        rec["operand_bytes"] += operand
        rec["wire_bytes"] += wire
        total_operand += operand
        total_wire += wire
    return {"per_op": per_op, "operand_bytes": total_operand,
            "wire_bytes": total_wire}


def _lin_extrap(c1, c2, n_periods: int):
    """Leafwise linear extrapolation: cost(n) = c1 + (n-1)*(c2-c1)."""
    if isinstance(c1, dict) or isinstance(c2, dict):
        c1 = c1 if isinstance(c1, dict) else {}
        c2 = c2 if isinstance(c2, dict) else {}
        return {k: _lin_extrap(c1.get(k, 0.0), c2.get(k, 0.0), n_periods)
                for k in set(c1) | set(c2)}
    return max(0.0, float(c1) + (n_periods - 1) * (float(c2) - float(c1)))


def _extract_costs(compiled) -> Dict[str, Any]:
    cost = compiled.cost_analysis()
    colls = parse_collectives(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "transcendentals": float(cost.get("transcendentals", 0.0)),
        "coll_operand": float(colls["operand_bytes"]),
        "coll_wire": float(colls["wire_bytes"]),
        "coll_per_op": {k: dict(v) for k, v in colls["per_op"].items()},
    }


def measure_costs(cfg, shape, mesh, plan) -> Dict[str, Any]:
    """Accurate per-device FLOP/byte/collective accounting.

    XLA cost_analysis counts while-loop bodies once, so the scanned
    full-depth compile undercounts. We compile *unrolled* 1-period and
    2-period depth variants (internal scans also unrolled via the
    ``use_unrolled_scans`` flag) and extrapolate linearly over periods —
    exact for homogeneous periods.
    """
    import dataclasses as dc

    from repro.models.flags import use_unrolled_scans
    from repro.models.transformer import depth_plan

    with use_unrolled_scans():
        if cfg.is_encdec:
            fn, args = build_lowerable(cfg, shape, mesh, plan)
            with mesh:
                c = _extract_costs(fn.lower(*args).compile())
            c["method"] = "direct-unrolled"
            return c
        prefix, period, n_periods = depth_plan(cfg)
        out = []
        for k in (1, 2):
            cfg_k = dc.replace(cfg, n_layers=prefix + k * period)
            fn, args = build_lowerable(cfg_k, shape, mesh, plan)
            with mesh:
                out.append(_extract_costs(fn.lower(*args).compile()))
    c = _lin_extrap(out[0], out[1], n_periods)
    c["method"] = f"extrapolated(p={period},n={n_periods})"
    return c


def model_flops(cfg, shape) -> float:
    """6*N_active*D (train) / 2*N_active*D (prefill) / 2*N_active*B (decode),
    global per step."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch


def build_lowerable(cfg, shape, mesh, plan):
    """-> (jitted_fn, kwargs_of_SDS) ready for .lower()."""
    specs = input_specs(cfg, shape, mesh, plan)
    shardings = jax.tree.map(lambda s: s.sharding, specs,
                             is_leaf=lambda x: isinstance(x,
                                                          jax.ShapeDtypeStruct))
    if shape.kind == "train":
        step = steps_mod.make_train_step(cfg, OptimConfig(), remat=plan.remat,
                                         mesh=mesh, act_rules=plan.act_rules)
        fn = jax.jit(step,
                     in_shardings=(shardings["state"], shardings["batch"]),
                     donate_argnums=(0,))
        args = (specs["state"], specs["batch"])
    elif shape.kind == "prefill":
        step = steps_mod.make_prefill_step(cfg, mesh=mesh,
                                           act_rules=plan.act_rules)
        fn = jax.jit(step,
                     in_shardings=(shardings["params"], shardings["batch"]))
        args = (specs["params"], specs["batch"])
    else:
        step = steps_mod.make_serve_step(cfg, mesh=mesh,
                                         act_rules=plan.act_rules)
        fn = jax.jit(step,
                     in_shardings=(shardings["params"], shardings["cache"],
                                   shardings["tokens"], shardings["cur_len"]),
                     donate_argnums=(1,))
        args = (specs["params"], specs["cache"], specs["tokens"],
                specs["cur_len"])
    return fn, args


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             overrides: Optional[dict] = None, save_hlo: Optional[str] = None,
             cfg_overrides: Optional[dict] = None) -> Dict[str, Any]:
    import dataclasses as _dc
    cfg = get_arch(arch)
    if cfg_overrides:
        cfg = _dc.replace(cfg, **cfg_overrides)
    shape = get_shape(shape_name)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name}
    if not cell_is_runnable(arch, shape_name):
        rec["status"] = "skipped"
        rec["reason"] = ("full-attention arch: 500k decode requires "
                         "sub-quadratic attention (DESIGN.md)")
        return rec
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    plan = suggest_plan(cfg, shape, mesh, overrides=overrides)
    rec["plan"] = {"remat": plan.remat, "notes": list(plan.notes),
                   "param_rules": {k: list(v) for k, v in
                                   plan.param_rules.items()},
                   "act_rules": {k: list(v) for k, v in
                                 plan.act_rules.items()},
                   "est": plan.est}
    t0 = time.time()
    fn, args = build_lowerable(cfg, shape, mesh, plan)
    with mesh:
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    colls_scanned = parse_collectives(hlo)
    if save_hlo:
        pathlib.Path(save_hlo).write_text(hlo)

    t1 = time.time()
    try:
        meas = measure_costs(cfg, shape, mesh, plan)
    except Exception as e:  # noqa: BLE001 - fall back to scanned numbers
        cost = compiled.cost_analysis()
        meas = {
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "transcendentals": float(cost.get("transcendentals", 0.0)),
            "coll_operand": float(colls_scanned["operand_bytes"]),
            "coll_wire": float(colls_scanned["wire_bytes"]),
            "coll_per_op": colls_scanned["per_op"],
            "method": f"scanned-fallback ({type(e).__name__}: {e})",
        }
    t_measure = time.time() - t1

    flops_dev = meas["flops"]
    bytes_dev = meas["bytes"]
    coll_dev = meas["coll_operand"]
    mf = model_flops(cfg, shape)
    terms = {
        "compute_s": flops_dev / PEAK_FLOPS,
        "memory_s": bytes_dev / HBM_BW,
        "collective_s": coll_dev / LINK_BW,
        "collective_wire_s": float(meas["coll_wire"]) / LINK_BW,
    }
    dominant = max(("compute_s", "memory_s", "collective_s"),
                   key=lambda k: terms[k])
    rec.update({
        "status": "ok",
        "n_devices": n_dev,
        "timings_s": {"lower": round(t_lower, 2),
                      "compile": round(t_compile, 2),
                      "measure": round(t_measure, 2)},
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": mem.peak_memory_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "cost": {"flops_per_device": flops_dev,
                 "bytes_per_device": bytes_dev,
                 "transcendentals": meas["transcendentals"],
                 "method": meas["method"]},
        "collectives": {"per_op": meas["coll_per_op"],
                        "operand_bytes": coll_dev,
                        "wire_bytes": meas["coll_wire"]},
        "collectives_scanned_hlo": {
            "per_op": colls_scanned["per_op"],
            "operand_bytes": colls_scanned["operand_bytes"],
            "wire_bytes": colls_scanned["wire_bytes"]},
        "model_flops_global": mf,
        "model_flops_per_device": mf / n_dev,
        "useful_flop_ratio": (mf / n_dev) / flops_dev if flops_dev else 0.0,
        "roofline": terms,
        "dominant": dominant,
        "bound_s": max(terms["compute_s"], terms["memory_s"],
                       terms["collective_s"]),
        "roofline_fraction": (terms["compute_s"]
                              / max(terms["compute_s"], terms["memory_s"],
                                    terms["collective_s"])
                              * ((mf / n_dev) / flops_dev)
                              if flops_dev else 0.0),
    })
    return rec


def autotune(arch: str, shape_name: str, multi_pod: bool,
             candidates: Dict[str, Dict[str, Any]],
             out_path: Optional[str] = None) -> Dict[str, Any]:
    """Blueprint configuration search (paper §2.2 'advanced CPS
    requirements': configuration optimization w.r.t. cost/performance).

    Each candidate = {"plan": <plan overrides>, "cfg": <ModelConfig
    overrides>}; every candidate is lowered + compiled and scored by its
    dominant roofline term. Returns {name: record} with the winner marked.
    """
    results: Dict[str, Any] = {}
    for name, cand in candidates.items():
        print(f"[autotune] {arch} x {shape_name} :: {name}", flush=True)
        try:
            rec = run_cell(arch, shape_name, multi_pod,
                           overrides=cand.get("plan"),
                           cfg_overrides=cand.get("cfg"))
        except Exception as e:  # noqa: BLE001
            rec = {"status": "error", "error": f"{type(e).__name__}: {e}"}
        rec["candidate"] = name
        results[name] = rec
        if rec.get("status") == "ok":
            r = rec["roofline"]
            print(f"  bound={rec['bound_s']:.3f}s dom={rec['dominant']} "
                  f"(comp={r['compute_s']:.3f} mem={r['memory_s']:.3f} "
                  f"coll={r['collective_s']:.3f})", flush=True)
    ok = {k: v for k, v in results.items() if v.get("status") == "ok"}
    if ok:
        winner = min(ok, key=lambda k: ok[k]["bound_s"])
        results["_winner"] = winner
    if out_path:
        pathlib.Path(out_path).parent.mkdir(parents=True, exist_ok=True)
        pathlib.Path(out_path).write_text(json.dumps(results, indent=1))
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--out", default="benchmarks/results/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--save-hlo", default=None,
                    help="directory to dump compiled HLO text")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    outdir = pathlib.Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                path = outdir / f"{arch}__{shape}__{mesh_name}.json"
                if path.exists() and not args.force:
                    print(f"[skip-cached] {path.name}")
                    continue
                print(f"[dryrun] {arch} x {shape} x {mesh_name} ...",
                      flush=True)
                hlo_path = None
                if args.save_hlo:
                    pathlib.Path(args.save_hlo).mkdir(parents=True,
                                                      exist_ok=True)
                    hlo_path = str(pathlib.Path(args.save_hlo) /
                                   f"{arch}__{shape}__{mesh_name}.hlo")
                try:
                    rec = run_cell(arch, shape, mp, save_hlo=hlo_path)
                except Exception as e:  # noqa: BLE001 - report, keep sweeping
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "status": "error", "error": f"{type(e).__name__}: {e}"}
                    failures.append(path.name)
                path.write_text(json.dumps(rec, indent=1))
                status = rec["status"]
                extra = ""
                if status == "ok":
                    r = rec["roofline"]
                    extra = (f" dom={rec['dominant'][:-2]}"
                             f" comp={r['compute_s']:.3f}s"
                             f" mem={r['memory_s']:.3f}s"
                             f" coll={r['collective_s']:.3f}s"
                             f" peakGiB={rec['memory']['peak_bytes']/2**30:.2f}")
                print(f"  -> {status}{extra}", flush=True)
    if failures:
        print(f"FAILURES: {failures}")
        raise SystemExit(1)
    print("dry-run sweep complete")


if __name__ == "__main__":
    main()
