"""Block-shape autotuner for the chip-batched IRC kernel.

`irc_mvm_chips` is tiled by (bm, bn, bk) and the best block shape depends on
the problem geometry (chips, M, N, K) and the backend — on TPU the sweet
spot trades VMEM footprint against MXU utilization; on CPU the kernel runs
in interpret mode and (today) always loses to the vmapped jnp path.  Rather
than guess, `sweep()` times every candidate block shape against the
reference path (`repro.mc.ensemble_apply` on a sampled ensemble — the
exact code the detector falls back to) and commits the winners to
`tuning.json` next to this module.

The dispatch side is two lookups against that committed table:

  kernel_wins(C, M, N, K)   True iff a tuned entry for this backend and
                            problem says the kernel beat the reference path
                            (absent entry -> False: untuned problems stay on
                            the reference path, never a silent slow path)
  best_blocks(C, M, N, K)   the winning (bm, bn, bk), or the defaults

Table keys are `{backend}/c{C}_m{M}_n{N}_k{K}` — exact-match on the
problem, so a geometry change re-tunes rather than inheriting a stale
winner.  Re-run the sweep with:

  PYTHONPATH=src python -m repro.kernels.autotune --write \
      [--chips 8 --batch 2 --network detector]

`benchmarks/mc_bench.py` records the same sweep as roofline rows in
`BENCH_mc.json` (us + achieved GFLOP/s per candidate).
"""
from __future__ import annotations

import argparse
import functools
import json
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

TUNING_JSON = Path(__file__).resolve().parent / "tuning.json"

DEFAULT_BLOCKS: Tuple[int, int, int] = (8, 128, 256)

# sublane/lane/ir-block aligned candidates (bm % 8, bn % 128, bk % 32 == 0);
# small enough that the VMEM scratch stays under budget at detector shapes
DEFAULT_CANDIDATES: Tuple[Tuple[int, int, int], ...] = (
    (8, 128, 256),
    (8, 128, 512),
    (16, 128, 256),
    (32, 128, 128),
)


def problem_key(C: int, M: int, N: int, K: int,
                backend: Optional[str] = None) -> str:
    from repro.kernels.ops import target_platform
    backend = backend or target_platform()
    return f"{backend}/c{C}_m{M}_n{N}_k{K}"


@functools.lru_cache(maxsize=1)
def load_table() -> Dict[str, dict]:
    """The committed tuning table (cached; `sweep(write=True)` invalidates)."""
    if not TUNING_JSON.exists():
        return {}
    try:
        return json.loads(TUNING_JSON.read_text())
    except json.JSONDecodeError:
        return {}


def lookup(C: int, M: int, N: int, K: int) -> Optional[dict]:
    return load_table().get(problem_key(C, M, N, K))


def kernel_wins(C: int, M: int, N: int, K: int) -> bool:
    """The auto-dispatch rule: route the kernel only where a committed sweep
    for THIS backend measured it faster than the reference path."""
    entry = lookup(C, M, N, K)
    return bool(entry and entry.get("use_kernel"))


def best_blocks(C: int, M: int, N: int, K: int) -> Tuple[int, int, int]:
    entry = lookup(C, M, N, K)
    if entry:
        return (int(entry["bm"]), int(entry["bn"]), int(entry["bk"]))
    return DEFAULT_BLOCKS


# ------------------------------------------------------------------ sweeping

def _median_us(fn, reps: int = 3) -> float:
    """Wall time of `fn()` (blocked): one warmup call, then the median."""
    jax.block_until_ready(fn())
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return sorted(times)[len(times) // 2] * 1e6


def _problem(C: int, M: int, N: int, K: int, seed: int = 0):
    """A synthetic ensemble problem of the given geometry: K-row ternary-ish
    placement planes (no bias rows — K IS the padded row count the kernel
    sees), a shared M-row word-line batch, and a C-chip sampled ensemble."""
    from repro.core.mapping import MappedLayer
    from repro.core import nonideal as ni
    from repro.mc.ensemble import sample_ensemble

    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(seed), 3)
    gp = (jax.random.uniform(k0, (K, N)) > 0.7).astype(jnp.float32)
    gn = (jax.random.uniform(k1, (K, N)) > 0.7).astype(jnp.float32) * (1 - gp)
    mapped = MappedLayer(g_pos=gp, g_neg=gn, bias_rows=0, scheme="ternary",
                         fan_in=K)
    x = (jax.random.uniform(k2, (M, K)) > 0.5).astype(jnp.float32)
    cfg = ni.NonidealConfig.all()
    ens = sample_ensemble(jax.random.PRNGKey(seed + 1), mapped, C, cfg=cfg)
    return ens, x, cfg


def autotune_problem(C: int, M: int, N: int, K: int, *,
                     candidates: Sequence[Tuple[int, int, int]]
                     = DEFAULT_CANDIDATES,
                     seed: int = 0) -> Tuple[dict, List[dict]]:
    """Time every candidate block shape and the reference path on one
    problem; returns (winner record, per-candidate roofline rows).

    FLOP accounting for the roofline rows: 4 MVM planes (ep/en currents +
    gp/gn counts) at 2*M*N*K flops each, per chip.
    """
    from repro.mc.engine import ensemble_apply, ensemble_apply_kernel

    ens, x, cfg = _problem(C, M, N, K, seed=seed)
    flops = 4 * 2.0 * C * M * N * K

    ref_us = _median_us(lambda: ensemble_apply(ens, x, cfg=cfg))
    rows = [{"impl": "ref", "bm": 0, "bn": 0, "bk": 0, "us": ref_us,
             "gflops": flops / ref_us * 1e-3}]

    best = None
    for bm, bn, bk in candidates:
        assert bm % 8 == 0 and bn % 128 == 0 and bk % 32 == 0, (bm, bn, bk)
        us = _median_us(lambda: ensemble_apply_kernel(
            ens, x, cfg=cfg, bm=bm, bn=bn, bk=bk))
        rows.append({"impl": "kernel", "bm": bm, "bn": bn, "bk": bk,
                     "us": us, "gflops": flops / us * 1e-3})
        if best is None or us < best["kernel_us"]:
            best = {"bm": bm, "bn": bn, "bk": bk, "kernel_us": us}

    record = dict(best, ref_us=ref_us,
                  use_kernel=best["kernel_us"] < ref_us,
                  backend=jax.default_backend(),
                  interpret=jax.default_backend() == "cpu")
    return record, rows


def sweep(problems: Sequence[Tuple[int, int, int, int]], *,
          candidates: Sequence[Tuple[int, int, int]] = DEFAULT_CANDIDATES,
          write: bool = False) -> Dict[str, dict]:
    """Autotune each (C, M, N, K) problem; with `write`, merge the winners
    into the committed `tuning.json` (other backends' entries are kept)."""
    table = dict(load_table())
    out: Dict[str, dict] = {}
    for C, M, N, K in problems:
        record, _ = autotune_problem(C, M, N, K, candidates=candidates)
        out[problem_key(C, M, N, K)] = record
    if write:
        table.update(out)
        TUNING_JSON.write_text(json.dumps(table, indent=1, sort_keys=True))
        load_table.cache_clear()
    return out


def detector_problems(det_cfg, batch: int, chips: int
                      ) -> List[Tuple[int, int, int, int]]:
    """The distinct (C, M, N, K) kernel problems of one detector config:
    every group crossbar of layer s{s}b{b} shares N = group columns and
    K = bias_rows + 9*group rows; M = batch * H_s * W_s shrinks with the
    stage's pooling."""
    probs = set()
    H = det_cfg.img_hw[0] // 2
    W = det_cfg.img_hw[1] // 2
    K = det_cfg.bias_rows + 9 * det_cfg.group
    for s, nb in enumerate(det_cfg.blocks_per_stage):
        for _ in range(nb):
            probs.add((chips, batch * H * W, det_cfg.group, K))
        H, W = H // 2, W // 2
    return sorted(probs)


def main() -> None:
    ap = argparse.ArgumentParser(
        description="(bm, bn, bk) block-shape sweep for irc_mvm_chips")
    ap.add_argument("--chips", type=int, default=8)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--network", default="detector", choices=["detector"])
    ap.add_argument("--write", action="store_true",
                    help="merge winners into the committed tuning.json")
    args = ap.parse_args()

    from repro.configs import yolo_irc
    problems = detector_problems(yolo_irc.smoke("ternary"), args.batch,
                                 args.chips)
    print(f"# backend={jax.default_backend()} problems={problems}")
    results = sweep(problems, write=args.write)
    for key, rec in results.items():
        print(f"{key}: bm={rec['bm']} bn={rec['bn']} bk={rec['bk']} "
              f"kernel={rec['kernel_us']:.0f}us ref={rec['ref_us']:.0f}us "
              f"use_kernel={rec['use_kernel']}")
    if args.write:
        print(f"# wrote {TUNING_JSON}")


if __name__ == "__main__":
    main()
