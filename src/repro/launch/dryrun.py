"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and record memory / cost / collective analysis.

MUST be the process entry point (``python -m repro.launch.dryrun``): the
XLA device-count override below precedes ANY jax import.  Smoke tests and
benchmarks import repro normally and see the host's single device.

Usage:
  python -m repro.launch.dryrun                    # all cells, both meshes
  python -m repro.launch.dryrun --arch llama3-405b --shape train_4k --mesh single
  python -m repro.launch.dryrun --list             # enumerate cells
Results: one JSON per cell under experiments/dryrun/.
"""
import os

os.environ["XLA_FLAGS"] = (os.environ.get("_REPRO_EXTRA_XLA", "") +
                           " --xla_force_host_platform_device_count=512")
# a CPU-only tool: this process and every JAX child it spawns (they inherit
# the environment) stay off the accelerator, which belongs to one process
os.environ["JAX_PLATFORMS"] = "cpu"

# ---- nothing above this line may import jax ----
import argparse
import json
import re
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.registry import get_config, list_archs
from repro.configs.shapes import SHAPES, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import input_specs
from repro.models.transformer import LM
from repro.sharding.rules import spec_for_axes, tree_pspecs, cache_axes_tree
from repro.train.steps import (make_train_step, abstract_train_state,
                               train_state_axes)

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1,
                "f8e5m2": 1, "s64": 8, "u64": 8, "s32": 4, "u32": 4,
                "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
                "c64": 8, "c128": 16}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


# ops that materialize HBM tensors on TPU (elementwise chains — convert /
# broadcast / add / mul / select / exp ... — fuse into their consumers, so
# the CPU backend's per-op "bytes accessed" overstates TPU traffic ~20x;
# measured on llama3-405b: 2.1 TB of `convert` outputs alone)
_MATERIALIZING = {"dot", "convolution", "gather", "scatter",
                  "dynamic-update-slice", "dynamic-slice", "sort",
                  "fusion", "copy", "transpose", "reduce", "rng",
                  "all-gather", "all-reduce", "reduce-scatter",
                  "all-to-all", "collective-permute",
                  "all-gather-start", "all-reduce-start"}

# `%name = <type(s)> <opname>(` — opname taken at the op position only
# (metadata strings like op_name="...transpose(jvp..." must not match)
_OP_RE = re.compile(r" = ((?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^ ]*)) "
                    r"([a-z][a-z0-9-]*)\(")


def hbm_bytes_estimate(hlo_text: str) -> float:
    """TPU HBM-traffic model: 2x (write+read) the output bytes of every
    materializing op; fusable elementwise ops are free (they fuse).
    Ops INSIDE fusion/reduction sub-computations are skipped (the fusion's
    own output already accounts for the materialization); entry parameters
    are accounted separately via memory_analysis.argument_size."""
    total = 0
    skipping = False
    for line in hlo_text.splitlines():
        ls = line.rstrip()
        if ls.endswith("{") and ("fused_computation" in ls or
                                 "region_" in ls or
                                 ls.lstrip().startswith("%wrapped")):
            skipping = True
            continue
        if skipping:
            if ls.strip() == "}":
                skipping = False
            continue
        m = _OP_RE.search(line)
        if m and m.group(2) in _MATERIALIZING:
            total += _shape_bytes(m.group(1))
    return 2.0 * total


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in post-SPMD HLO.

    HLO line: ``%x = bf16[8,128]{1,0} all-gather(...)`` (possibly tuple
    results).  `-start` variants (async) are counted; `-done` are not
    (same op, avoids double counting).
    """
    out = {c: {"count": 0, "bytes": 0} for c in _COLLECTIVES}
    for line in hlo_text.splitlines():
        for c in _COLLECTIVES:
            marker = f" {c}("
            start_marker = f" {c}-start("
            if marker in line or start_marker in line:
                lhs = line.split(f"{c}(")[0].split(f"{c}-start(")[0]
                lhs = lhs.split(" = ")[-1] if " = " in lhs else lhs
                out[c]["count"] += 1
                out[c]["bytes"] += _shape_bytes(lhs)
                break
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def _memory_analysis_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    if ma is None:
        return {}
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes",
                 "host_argument_size_in_bytes",
                 "host_output_size_in_bytes", "host_temp_size_in_bytes"):
        v = getattr(ma, attr, None)
        if v is not None:
            out[attr] = int(v)
    if not out:
        out["repr"] = str(ma)
    return out


def _cost_analysis_dict(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return {k: float(v) for k, v in dict(ca).items()
            if isinstance(v, (int, float))}


def _batch_shardings(batch_abs, mesh):
    return jax.tree.map(
        lambda sds: NamedSharding(
            mesh, spec_for_axes(("act_batch",) + (None,) * (len(sds.shape) - 1),
                                sds.shape, mesh)),
        batch_abs)


def _ns_tree(pspec_tree, mesh):
    return jax.tree.map(lambda p: NamedSharding(mesh, p), pspec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape_name: str, mesh_kind: str, variant: str,
               *, microbatch: int = 0, remat: str = "block",
               probe_layers: int = 0, attn_mode: str | None = None,
               act_overrides: dict | None = None,
               extra: dict | None = None) -> dict:
    """Lower + compile one cell; returns the record dict.

    probe_layers > 0 lowers a COST PROBE: the same architecture truncated
    to that many layers with the layer loop UNROLLED and microbatch=1, so
    cost_analysis counts every layer (XLA counts while-loop bodies once).
    The roofline harness reconstructs full-depth totals from the deltas of
    two probes (see repro.launch.roofline).
    """
    import dataclasses as _dc
    cfg = get_config(arch, variant)
    scan_layers = True
    if probe_layers:
        cfg = _dc.replace(cfg, n_layers=probe_layers)
        scan_layers = False
        # cost probes run at microbatch=1 unless the caller probes the
        # microbatch scaling itself (param-collective separation)
        microbatch = microbatch or 1
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": reason}

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = mesh.devices.size
    lm = LM(cfg).use_mesh(mesh, act_overrides=act_overrides)
    if attn_mode is not None:
        lm.attn_mode = attn_mode
    specs = input_specs(lm, shape)
    param_axes = lm.logical_axes()
    param_abs = lm.abstract_params()
    t0 = time.time()

    with mesh:
        if shape.kind == "train":
            mb = microbatch or max(1, shape.global_batch // 32)
            state_abs = abstract_train_state(lm)
            state_shardings = _ns_tree(
                tree_pspecs(train_state_axes(lm), state_abs, mesh), mesh)
            batch_abs = specs["batch"]
            batch_sh = _batch_shardings(batch_abs, mesh)
            step_fn = make_train_step(lm, remat=remat, microbatch=mb,
                                      scan_layers=scan_layers,
                                      scan_microbatches=not probe_layers)
            jitted = jax.jit(step_fn,
                             in_shardings=(state_shardings, batch_sh),
                             out_shardings=(state_shardings, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_abs, batch_abs)
        elif shape.kind == "prefill":
            params_sh = _ns_tree(tree_pspecs(param_axes, param_abs, mesh), mesh)
            batch_abs = specs["batch"]
            batch_sh = _batch_shardings(batch_abs, mesh)

            def prefill(params, batch):
                logits, _ = lm.apply(params, batch["tokens"], remat=remat,
                                     scan_layers=scan_layers)
                return logits

            jitted = jax.jit(prefill, in_shardings=(params_sh, batch_sh))
            lowered = jitted.lower(param_abs, batch_abs)
        else:  # decode
            params_sh = _ns_tree(tree_pspecs(param_axes, param_abs, mesh), mesh)
            tokens_abs, cache_abs = specs["tokens"], specs["cache"]
            cache_sh = _ns_tree(tree_pspecs(cache_axes_tree(cache_abs),
                                            cache_abs, mesh), mesh)
            tok_sh = _batch_shardings(tokens_abs, mesh)

            def serve_step(params, tokens, cache):
                return lm.decode_step(params, tokens, cache,
                                      scan_layers=scan_layers)

            jitted = jax.jit(serve_step,
                             in_shardings=(params_sh, tok_sh, cache_sh),
                             out_shardings=(None, cache_sh),
                             donate_argnums=(2,))
            lowered = jitted.lower(param_abs, tokens_abs, cache_abs)

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    mem = _memory_analysis_dict(compiled)
    # op traffic + one read of the live inputs (params/optimizer/caches)
    hbm_est = hbm_bytes_estimate(hlo) + mem.get("argument_size_in_bytes", 0)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "variant": variant, "status": "ok",
        "devices": n_dev, "microbatch": microbatch, "remat": remat,
        "probe_layers": probe_layers,
        "n_layers": cfg.n_layers, "n_dense_prefix": cfg.n_dense_prefix,
        "global_batch": shape.global_batch, "seq_len": shape.seq_len,
        "kind": shape.kind, "block": cfg.block, "dtype": cfg.dtype,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory_analysis": mem,
        "cost_analysis": _cost_analysis_dict(compiled),
        "hbm_bytes_est": hbm_est,
        "collectives": coll,
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
        "hlo_lines": len(hlo.splitlines()),
    }
    if extra:
        rec.update(extra)
    return rec


def run_cell_subprocess(arch, shape, mesh_kind, variant, out_path: Path,
                        timeout=3600) -> bool:
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--mesh", mesh_kind, "--variant", variant,
           "--out", str(out_path)]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(Path(__file__).resolve().parents[2])
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=timeout)
    if r.returncode != 0:
        err = {"arch": arch, "shape": shape, "mesh": mesh_kind,
               "status": "error", "stderr": r.stderr[-4000:]}
        out_path.write_text(json.dumps(err, indent=1))
        return False
    return True


def all_cells(meshes=("single", "multi")):
    for arch in list_archs():
        for shape in SHAPES:
            for mesh_kind in meshes:
                yield arch, shape, mesh_kind


def probe_pair(arch: str):
    """(L1, L2) probe depths: MoE dense prefixes stay in the prefix term."""
    cfg = get_config(arch, "full")
    base = cfg.n_dense_prefix + 1
    return base, base + 1


def run_probes(force: bool = False):
    """Cost probes for every runnable single-pod cell (roofline input).

    Train cells get FOUR probes (L1/L2 x mb1/mb2): the mb delta separates
    parameter collectives (FSDP gathers/grad reductions, which re-run per
    microbatch in production) from activation collectives (whose total is
    microbatch-invariant)."""
    failures = 0
    for arch in list_archs():
        l1, l2 = probe_pair(arch)
        # enumerate (probe_layers, microbatch) points
        for shape in SHAPES:
            cfg = get_config(arch, "full")
            if not shape_applicable(cfg, SHAPES[shape])[0]:
                continue
            points = [(l1, 1), (l2, 1)]
            if SHAPES[shape].kind == "train":
                points += [(l1, 2), (l2, 2)]
            for pl, mb in points:
                suffix = f"probe{pl}" + (f"mb{mb}" if mb > 1 else "")
                out = OUT_DIR / f"{arch}__{shape}__single__{suffix}.json"
                if out.exists() and not force:
                    rec = json.loads(out.read_text())
                    if rec.get("status") == "ok":
                        continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--mesh", "single",
                       "--probe-layers", str(pl),
                       "--probe-microbatch", str(mb), "--out", str(out)]
                env = dict(os.environ)
                env["PYTHONPATH"] = str(Path(__file__).resolve().parents[2])
                t0 = time.time()
                r = subprocess.run(cmd, env=env, capture_output=True,
                                   text=True, timeout=3600)
                if r.returncode != 0:
                    out.write_text(json.dumps(
                        {"arch": arch, "shape": shape, "mesh": "single",
                         "probe_layers": pl, "status": "error",
                         "stderr": r.stderr[-4000:]}))
                    failures += 1
                    status = "error"
                else:
                    status = json.loads(out.read_text()).get("status")
                print(f"probe {arch:24s} {shape:12s} L={pl} mb={mb} "
                      f"{status:8s} {time.time()-t0:6.1f}s", flush=True)
    print(f"probes done; {failures} failures")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--variant", default="full")
    ap.add_argument("--out")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--probe-layers", type=int, default=0,
                    help="cost probe: truncate to N layers, unroll, mb=1")
    ap.add_argument("--probe-microbatch", type=int, default=0,
                    help="probe microbatch (param-collective separation)")
    ap.add_argument("--probes", action="store_true",
                    help="driver: run the two cost probes for every "
                         "single-pod cell (for the roofline)")
    args = ap.parse_args()

    if args.list:
        for cell in all_cells():
            print(*cell)
        return

    OUT_DIR.mkdir(parents=True, exist_ok=True)
    if args.probes:
        run_probes(force=args.force)
        return
    if args.arch and args.shape:
        # single cell, in-process (the subprocess worker path)
        try:
            rec = lower_cell(args.arch, args.shape, args.mesh, args.variant,
                             probe_layers=args.probe_layers,
                             microbatch=args.probe_microbatch)
        except Exception:
            rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
                   "status": "error", "traceback": traceback.format_exc()}
        suffix = (f"__probe{args.probe_layers}"
                  + (f"mb{args.probe_microbatch}"
                     if args.probe_microbatch > 1 else "")
                  ) if args.probe_layers else ""
        out = Path(args.out) if args.out else (
            OUT_DIR / f"{args.arch}__{args.shape}__{args.mesh}{suffix}.json")
        out.write_text(json.dumps(rec, indent=1))
        print(json.dumps({k: rec.get(k) for k in
                          ("arch", "shape", "mesh", "status", "compile_s")}))
        if rec["status"] == "error":
            print(rec.get("traceback", rec.get("reason", ""))[-2000:],
                  file=sys.stderr)
            sys.exit(1)
        return

    # driver mode: every cell in its own subprocess (resumable)
    failures = 0
    for arch, shape, mesh_kind in all_cells():
        out = OUT_DIR / f"{arch}__{shape}__{mesh_kind}.json"
        if out.exists() and not args.force:
            rec = json.loads(out.read_text())
            if rec.get("status") in ("ok", "skipped"):
                continue
        t0 = time.time()
        ok = run_cell_subprocess(arch, shape, mesh_kind, "full", out)
        rec = json.loads(out.read_text())
        status = rec.get("status")
        print(f"{arch:24s} {shape:12s} {mesh_kind:6s} {status:8s} "
              f"{time.time()-t0:7.1f}s", flush=True)
        failures += (status == "error")
    print(f"done; {failures} failures")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
