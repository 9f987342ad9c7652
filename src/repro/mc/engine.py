"""Chip-ensemble Monte Carlo engine: one jitted computation, many chips.

`ensemble_apply` vmaps the deterministic `crossbar_apply` over the ensemble's
leading chips axis (or dispatches the chip-batched Pallas kernel), so a whole
population of sampled dies is a single XLA program instead of a Python loop
of structural sims.  `run_mc` streams an arbitrarily large ensemble through
it in fixed-size chunks, folding per-chip metrics into Welford/quantile
accumulators so memory stays bounded by `chunk_size`, and `run_ablation`
sweeps the Table-II effect toggles to produce mean±std columns.

Chunking is statistically invisible: chip `c` is keyed by `fold_in(key, c)`
regardless of which chunk evaluates it, so `chunk_size` only trades memory
for launch count (tests assert identical per-chip metrics across chunkings).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.macro import MacroSpec, DEFAULT_MACRO
from repro.core import nonideal as ni
from repro.core.crossbar import crossbar_apply, _block_reduce, _accumulate
from repro.mc.ensemble import ChipEnsemble, sample_ensemble, \
    calibrate_ensemble_bias, shard_ensemble
from repro.mc.stats import StreamingMoments, DEFAULT_QUANTILES
from repro.obs import ConvergenceMonitor, PhaseTimer, RunLog, as_runlog


# ------------------------------------------------------------------ forward

def _extend(x_bits: jax.Array, lead_rows: int) -> jax.Array:
    x = x_bits.astype(jnp.float32)
    if lead_rows == 0:
        return x
    ones = jnp.ones(x.shape[:-1] + (lead_rows,), jnp.float32)
    return jnp.concatenate([ones, x], axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg", "spec", "accumulation",
                                             "partial_rows", "sa_extra_units",
                                             "output", "per_chip_x", "device"))
def ensemble_apply(ens: ChipEnsemble, x_bits: jax.Array, *,
                   cfg: ni.NonidealConfig, spec: MacroSpec = DEFAULT_MACRO,
                   accumulation: str = "single_shot", partial_rows: int = 256,
                   sa_extra_units: float = 0.0,
                   output: str = "binary",
                   per_chip_x: bool = False, device=None) -> jax.Array:
    """Evaluate every chip on a shared input batch: [chips, batch, n_out].

    Chip `c`'s slice equals `crossbar_forward(fold_in(key, c), x, mapped, ...)`
    bit-for-bit (same key-split discipline; tests/test_mc.py pins this).

    When the LRS placement planes are shared by all chips, the activated-count
    block dots are hoisted OUT of the chips vmap — counts are sums of {0,1}
    products, exact in f32 at any summation order, so sharing them across the
    ensemble halves the matmul work without changing a single output bit.

    With `per_chip_x`, x_bits carries a leading chips axis ([chips, batch,
    fan_in]) — how network-level MC feeds chip-diverged activations from one
    IRC layer into the next.  Counts then depend on each chip's own inputs,
    so nothing hoists, but the placement planes still pass through as ONE
    shared [rows, n_out] array.

    `device` is the `repro.device` backend for the PERIPHERY terms (SA
    offset sigma, IR drop); it must match the backend the ensemble's planes
    were sampled with.  Device models are frozen hashable dataclasses, so
    passing one as a static argument reuses the jit cache across calls.
    """
    x_ext = _extend(x_bits, ens.lead_rows)
    if per_chip_x:
        assert x_bits.ndim >= 3 and x_bits.shape[0] == ens.n_chips, (
            f"per_chip_x needs [chips={ens.n_chips}, ..., fan_in] inputs, "
            f"got {x_bits.shape}")
        in_g = 0 if ens.planes_per_chip() else None
        fwd = lambda k, xc, ep, en, gp, gn: crossbar_apply(
            k, xc, ep, en, gp, gn, cfg=cfg, spec=spec,
            accumulation=accumulation, partial_rows=partial_rows,
            sa_extra_units=sa_extra_units, output=output, device=device)
        return jax.vmap(fwd, in_axes=(0, 0, 0, 0, in_g, in_g))(
            ens.sa_keys, x_ext, ens.ep, ens.en, ens.gp, ens.gn)
    if ens.planes_per_chip():
        fwd = lambda k, ep, en, gp, gn: crossbar_apply(
            k, x_ext, ep, en, gp, gn, cfg=cfg, spec=spec,
            accumulation=accumulation, partial_rows=partial_rows,
            sa_extra_units=sa_extra_units, output=output, device=device)
        return jax.vmap(fwd)(ens.sa_keys, ens.ep, ens.en, ens.gp, ens.gn)

    blk = spec.ir_block
    counts_p = _block_reduce(x_ext, ens.gp, blk)      # chip-independent
    counts_n = _block_reduce(x_ext, ens.gn, blk)

    def fwd(k_sa, ep, en):
        """One chip's forward against the SHARED placement-plane counts."""
        i_pos, p_pos = _accumulate(_block_reduce(x_ext, ep, blk), counts_p,
                                   cfg, spec, accumulation, partial_rows,
                                   device)
        i_neg, p_neg = _accumulate(_block_reduce(x_ext, en, blk), counts_n,
                                   cfg, spec, accumulation, partial_rows,
                                   device)
        if output == "diff":
            return i_pos - i_neg
        if output == "sensed_diff":
            return ni.sensed_diff(k_sa, i_pos, i_neg, p_pos + p_neg, cfg,
                                  spec, sa_extra_units, device)
        return ni.resolve_sa(k_sa, i_pos, i_neg, p_pos + p_neg, cfg, spec,
                             sa_extra_units, device)

    return jax.vmap(fwd)(ens.sa_keys, ens.ep, ens.en)


@functools.partial(jax.jit, static_argnames=("cfg", "spec", "sa_extra_units",
                                             "output", "per_chip_x", "impl",
                                             "bm", "bn", "bk", "device"))
def ensemble_apply_kernel(ens: ChipEnsemble, x_bits: jax.Array, *,
                          cfg: ni.NonidealConfig,
                          spec: MacroSpec = DEFAULT_MACRO,
                          sa_extra_units: float = 0.0, output: str = "binary",
                          per_chip_x: bool = False, impl: str = "pallas",
                          bm: int = 8, bn: int = 128, bk: int = 256,
                          device=None) -> jax.Array:
    """Chip-batched Pallas path: ONE kernel launch services all chips.

    Single-shot accumulation only (the kernel's fused epilogue).  The
    per-read stochastic terms are pre-sampled here from each chip's `sa_keys`
    with the `irc_mvm_from_mapped` key discipline, so chip `c` matches a loop
    of single-chip kernel calls exactly.

    With `per_chip_x`, x_bits carries a leading chips axis ([chips, batch,
    fan_in]) — chip-diverged activations downstream of the first IRC layer;
    the kernel walks a per-chip word-line block instead of reusing one
    shared tile.  `impl` selects the pallas kernel ("pallas", interpret mode
    on CPU) or its pure-jnp oracle ("ref") — the oracle IS the kernel's
    bit-exactness contract (tests pin pallas == ref through the whole
    detector), so routing through it gives kernel-semantics outputs where
    interpret mode would be too slow.
    """
    from repro.kernels.ops import irc_mvm_chips
    from repro.kernels.ref import IrcEpilogueParams, irc_mvm_chips_ref
    if device is not None and not device.analytic_periphery:
        # the Pallas epilogue bakes the ANALYTIC periphery closed forms
        # (g(p) polynomial, linear IR drop) into scalar params; a backend
        # with its own periphery model cannot be expressed in them
        raise NotImplementedError(
            f"device model {device.name!r} has a non-analytic periphery; "
            "the chip-batched kernel supports analytic-periphery backends "
            "only — use the jnp engine (backend='jnp')")
    if per_chip_x:
        assert x_bits.ndim == 3 and x_bits.shape[0] == ens.n_chips, (
            f"per_chip_x needs [chips={ens.n_chips}, batch, fan_in] inputs, "
            f"got {x_bits.shape}")
    x_ext = _extend(x_bits, ens.lead_rows)
    B, N = x_ext.shape[-2], ens.n_out

    def periphery(k_sa):
        """Per-chip SA offsets + comparator tie-break draws (key-split once)."""
        k_off, k_rng = jax.random.split(k_sa)
        return (jax.random.normal(k_off, (B, N), jnp.float32),
                jax.random.bernoulli(k_rng, 0.5, (B, N)).astype(jnp.float32))

    eps_sa, rnd = jax.vmap(periphery)(ens.sa_keys)
    # shared placement planes pass through as [R, N]: the kernel's count
    # BlockSpec ignores the chip coordinate, so one HBM copy serves all chips
    gp, gn = ens.gp, ens.gn
    params = IrcEpilogueParams.from_macro(
        spec, sa_extra=sa_extra_units, output=output,
        apply_nonlinearity=cfg.nonlinearity, apply_ir=cfg.ir_drop,
        apply_sa=cfg.sa_variation, apply_range=cfg.sensing_range)
    if impl == "ref":
        return irc_mvm_chips_ref(x_ext, ens.ep, ens.en, gp, gn, eps_sa, rnd,
                                 params)
    return irc_mvm_chips(x_ext, ens.ep, ens.en, gp, gn, eps_sa, rnd, params,
                         bm=bm, bn=bn, bk=bk)


@functools.partial(jax.jit, static_argnames=("scheme", "fan_in", "cfg",
                                             "spec", "accumulation",
                                             "partial_rows", "sa_extra_units",
                                             "backend", "device"),
                   donate_argnums=(0, 1, 2))
def _ensemble_apply_donated(ep, en, sa_keys, chip_ids, gp, gn, bias_units,
                            x_bits, *, scheme, fan_in, cfg, spec,
                            accumulation, partial_rows, sa_extra_units,
                            backend, device=None):
    """Per-chunk forward with the chunk's THROWAWAY sampled state donated.

    `run_mc` samples fresh ep/en/sa_keys every chunk and never touches them
    after the forward, so donating them lets XLA reuse those buffers for the
    chunk's activations instead of allocating a second ensemble-sized block
    — on accelerators this halves the peak footprint of the streaming loop
    (CPU accepts the donation too).  The placement planes and word-line bits
    are NOT donated: `mapped.g_pos` / `x_bits` are shared by every chunk.
    """
    ens = ChipEnsemble(ep=ep, en=en, gp=gp, gn=gn, sa_keys=sa_keys,
                       chip_ids=chip_ids, bias_units=bias_units,
                       scheme=scheme, fan_in=fan_in)
    if backend == "kernel":
        return ensemble_apply_kernel(ens, x_bits, cfg=cfg, spec=spec,
                                     sa_extra_units=sa_extra_units,
                                     device=device)
    return ensemble_apply(ens, x_bits, cfg=cfg, spec=spec,
                          accumulation=accumulation,
                          partial_rows=partial_rows,
                          sa_extra_units=sa_extra_units, device=device)


# ------------------------------------------------------------------ metrics

MetricFn = Callable[[jax.Array], jax.Array]   # [chips, B, N] -> [chips]


def bit_agreement_metric(ref_bits: jax.Array) -> MetricFn:
    """Fraction of SA decisions agreeing with the ideal digital output —
    the accuracy/mAP-drop proxy used across the benchmark suite."""
    ref = (ref_bits > 0.5).astype(jnp.float32)
    return lambda out: jnp.mean((out > 0.5).astype(jnp.float32) == ref,
                                axis=(-2, -1))


def ones_fraction_metric() -> MetricFn:
    """Per-chip fraction of 1-bits in the output — a cheap drift indicator
    (a chip whose comparators saturate shows up before accuracy is scored)."""
    return lambda out: jnp.mean(out, axis=(-2, -1))


@functools.partial(jax.jit, static_argnames=("scheme", "fan_in", "cfg",
                                             "spec", "accumulation",
                                             "partial_rows", "sa_extra_units",
                                             "device"))
def _fused_chunk_metrics(key, ids, x_bits, gp, gn, ref_bits, *, scheme,
                         fan_in, cfg, spec, accumulation, partial_rows,
                         sa_extra_units, device=None):
    """sample -> forward -> per-chip metrics as one cached jitted program
    (module-level so repeated `run_mc` calls reuse the compilation; eager
    per-chunk sampling and op-by-op metric reductions otherwise cost as much
    as the forward itself on small chunks)."""
    from repro.core.mapping import MappedLayer
    mapped = MappedLayer(g_pos=gp, g_neg=gn,
                         bias_rows=gp.shape[0] - fan_in, scheme=scheme,
                         fan_in=fan_in)
    ens = sample_ensemble(key, mapped, chip_ids=ids, cfg=cfg, spec=spec,
                          device=device)
    out = ensemble_apply(ens, x_bits, cfg=cfg, spec=spec,
                         accumulation=accumulation,
                         partial_rows=partial_rows,
                         sa_extra_units=sa_extra_units, device=device)
    metrics = {"ones_fraction": ones_fraction_metric()(out)}
    if ref_bits is not None:
        metrics["bit_agreement"] = bit_agreement_metric(ref_bits)(out)
    return metrics


# ------------------------------------------------------------------ MC sweep

@dataclasses.dataclass(frozen=True)
class McConfig:
    """One ensemble sweep: population size, chunking, effect toggles.

    `device` is the `repro.device` backend chips are sampled from and the
    periphery statistics come from (None: analytic — the paper's closed
    forms, bit-identical to the pre-seam engine); build named/aged backends
    with `repro.device.get_device_model`.
    """
    n_chips: int = 64
    chunk_size: int = 32
    cfg: ni.NonidealConfig = ni.NonidealConfig.all()
    accumulation: str = "single_shot"
    partial_rows: int = 256
    sa_extra_units: float = 0.0
    backend: str = "jnp"                 # "jnp" | "kernel"
    calibrate: bool = False              # per-chip bias calibration
    quantiles: Tuple[float, ...] = DEFAULT_QUANTILES
    device: Optional[object] = None      # repro.device.DeviceModel


@dataclasses.dataclass
class McResult:
    """Ensemble statistics for one sweep.

    `wall_s` is the whole sweep including the first chunk's trace/compile;
    `compile_s` is that first-chunk wall alone, and `chips_per_sec` is the
    STEADY-STATE rate over the remaining chunks (total-based when the sweep
    ran a single chunk) — at small `n_chips` the old conflated rate was
    dominated by compilation and meaningless as a throughput number.
    With `stderr_target` early stop, `n_chips` is the count actually
    evaluated (a prefix of the requested population).

    `device_s`/`host_s` split the loop body: time BLOCKED waiting on device
    results vs. host-side metric work (mAP matching, numpy transfers).  In a
    pipelined sweep the next chunk runs on device DURING the host slice, so
    blocked time collapses; `1 - device_s / wall_s` measures the realized
    overlap (serial loop ~= host fraction; -> 1.0 as device waits are fully
    hidden behind host scoring).  Both are host-clock times: `device_s` is
    how long the host was blocked on the device, not the device's own
    compute time, which only a profiler trace gives.
    """
    n_chips: int
    metrics: Dict[str, Dict[str, float]]      # name -> {mean,std,qXX,...}
    per_chip: Dict[str, np.ndarray]           # name -> [n_chips]
    wall_s: float
    chips_per_sec: float
    compile_s: float = 0.0
    bias_units: Optional[np.ndarray] = None   # per-chip calibrated bias
    device_s: float = 0.0                     # host blocked on device
    host_s: float = 0.0                       # host-side metric wall

    def summary_line(self, metric: str = "bit_agreement") -> str:
        """One-line mean±std + quantile report for `metric`, as printed by
        the CLI and the benchmark rows."""
        m = self.metrics[metric]
        qs = ";".join(f"{k}={v:.4f}" for k, v in sorted(m.items())
                      if k.startswith("q"))
        return (f"{metric}={m['mean']:.4f}±{m['std']:.4f} "
                f"({qs}) over {self.n_chips} chips "
                f"[{self.chips_per_sec:.1f} chips/s steady, "
                f"compile {self.compile_s:.2f}s]")


HostMetricFn = Callable[[np.ndarray], np.ndarray]   # [chips,B,N] -> [chips]


def run_mc(key: jax.Array, mapped, x_bits: jax.Array, *,
           ref_bits: Optional[jax.Array] = None,
           mc: McConfig = McConfig(), spec: MacroSpec = DEFAULT_MACRO,
           metric_fns: Optional[Dict[str, MetricFn]] = None,
           host_metric_fns: Optional[Dict[str, HostMetricFn]] = None,
           x_calib_bits: Optional[jax.Array] = None, mesh=None,
           obs: Optional[RunLog] = None,
           stderr_target: Optional[float] = None,
           stderr_metric: Optional[str] = None) -> McResult:
    """Stream an ensemble of `mc.n_chips` sampled chips over `x_bits`.

    Chips are sampled chunk-by-chunk (never materializing more than
    `chunk_size` chips of [rows, n_out] planes or [chunk, B, n_out]
    activations) and their per-chip metrics fold into streaming accumulators.
    `ref_bits` ([B, n_out] ideal binary output) enables the default
    `bit_agreement` metric; pass `metric_fns` for custom on-device
    reductions, or `host_metric_fns` for callbacks that need the chunk's
    outputs on the host (e.g. `evaluate_map` — NMS/AP are not array
    programs); host values fold into the same Welford/quantile accumulators.
    With `mesh`, each chunk's chips axis shards over the data-parallel axes
    (the "chips" rule) — the workload is embarrassingly parallel per chip.

    Observability: pass `obs` (a `repro.obs.RunLog`) to stream per-chunk
    events — raw per-chip metric values (replayable to the reported mean±std
    bit-for-bit) and running count/mean/stderr — into the run directory.
    `stderr_target` stops the sweep at the first chunk boundary where the
    standard error of the mean of every tracked metric (or just
    `stderr_metric`) is at or under the target; because chip `c` is keyed by
    `fold_in(key, c)` regardless of chunking, the early-stopped moments are
    bit-identical to the same-length prefix of the full run.
    """
    obs = as_runlog(obs)
    fns: Dict[str, MetricFn] = {}
    if ref_bits is not None:
        fns["bit_agreement"] = bit_agreement_metric(ref_bits)
    fns["ones_fraction"] = ones_fraction_metric()
    if metric_fns:
        fns.update(metric_fns)
    host_fns: Dict[str, HostMetricFn] = dict(host_metric_fns or {})
    moments = {name: StreamingMoments(mc.quantiles)
               for name in (*fns, *host_fns)}
    bias_chunks: List[np.ndarray] = []

    if mc.backend == "kernel" and mc.accumulation != "single_shot":
        raise ValueError("kernel backend fuses the single-shot path only")

    # Fast path: default metrics, no calibration/sharding -> the cached
    # fused chunk program.  Calibration (host loop), explicit sharding,
    # custom/host metrics and the kernel backend keep the step-by-step path.
    use_fused = (not mc.calibrate and mesh is None and mc.backend == "jnp"
                 and not metric_fns and not host_fns)

    monitor = ConvergenceMonitor(moments, stderr_target=stderr_target,
                                 stderr_metric=stderr_metric, runlog=obs)
    timer = PhaseTimer("mc_chunks", unit="chips")
    obs.log_event("mc_start", n_chips=mc.n_chips, chunk_size=mc.chunk_size,
                  backend=mc.backend, calibrate=mc.calibrate,
                  fused=use_fused, stderr_target=stderr_target,
                  device_model=(mc.device.name if mc.device is not None
                                else "analytic"))

    n_done = 0
    for chunk_i, lo in enumerate(range(0, mc.n_chips, mc.chunk_size)):
        ids = jnp.arange(lo, min(lo + mc.chunk_size, mc.n_chips),
                         dtype=jnp.uint32)
        with timer.lap(items=int(ids.shape[0])):
            if use_fused:
                chunk_vals = dict(jax.block_until_ready(_fused_chunk_metrics(
                    key, ids, x_bits, mapped.g_pos, mapped.g_neg, ref_bits,
                    scheme=mapped.scheme, fan_in=mapped.fan_in, cfg=mc.cfg,
                    spec=spec, accumulation=mc.accumulation,
                    partial_rows=mc.partial_rows,
                    sa_extra_units=mc.sa_extra_units, device=mc.device)))
            else:
                ens = sample_ensemble(key, mapped, chip_ids=ids, cfg=mc.cfg,
                                      spec=spec, device=mc.device)
                if mc.calibrate:
                    ens = calibrate_ensemble_bias(
                        ens, x_bits if x_calib_bits is None else x_calib_bits,
                        spec, device=mc.device)
                    bias_chunks.append(np.asarray(ens.bias_units))
                if mesh is not None:
                    ens = shard_ensemble(ens, mesh)
                # ep/en/sa_keys are this chunk's throwaway sampled state —
                # donated so the forward can recycle their buffers
                out = _ensemble_apply_donated(
                    ens.ep, ens.en, ens.sa_keys, ens.chip_ids, ens.gp,
                    ens.gn, ens.bias_units, x_bits, scheme=ens.scheme,
                    fan_in=ens.fan_in, cfg=mc.cfg, spec=spec,
                    accumulation=mc.accumulation,
                    partial_rows=mc.partial_rows,
                    sa_extra_units=mc.sa_extra_units, backend=mc.backend,
                    device=mc.device)
                out = jax.block_until_ready(out)
                chunk_vals = {name: fn(out) for name, fn in fns.items()}
                if host_fns:
                    out_np = np.asarray(out)
                    for name, fn in host_fns.items():
                        chunk_vals[name] = jnp.asarray(fn(out_np))
        n_done += int(ids.shape[0])
        for name, v in chunk_vals.items():
            moments[name].update(v)
        # the raw per-chip values are the replay evidence: folding them back
        # through StreamingMoments in file order reproduces the reported
        # mean±std bit-for-bit (tests/test_obs.py)
        obs.log_event("chunk", phase="mc", chunk=chunk_i, chip_lo=lo,
                      chips=n_done, wall_s=timer.last_s,
                      values={name: np.asarray(jnp.ravel(v))
                              for name, v in chunk_vals.items()})
        if monitor.after_chunk(chunk_i, n_done):
            obs.log_event("early_stop", chips=n_done, requested=mc.n_chips,
                          stderr_target=stderr_target)
            break

    res = McResult(
        n_chips=n_done,
        metrics={name: m.summary() for name, m in moments.items()},
        per_chip={name: m.per_chip for name, m in moments.items()},
        wall_s=timer.total_s, chips_per_sec=timer.rate(),
        compile_s=timer.compile_s,
        bias_units=(np.concatenate(bias_chunks) if bias_chunks else None))
    obs.log_event("mc_result", chips=n_done, requested=mc.n_chips,
                  wall_s=res.wall_s, compile_s=res.compile_s,
                  chips_per_sec=res.chips_per_sec, metrics=res.metrics)
    return res


# ------------------------------------------------------------------ ablation

# Table II columns: effects switch on cumulatively, plus the all-on row.
TABLE2_ABLATION: Tuple[Tuple[str, ni.NonidealConfig], ...] = (
    ("ideal", ni.NonidealConfig.none()),
    ("devvar", ni.NonidealConfig(device_variation=True)),
    ("devvar+nl", ni.NonidealConfig(device_variation=True, nonlinearity=True)),
    ("devvar+nl+peri", ni.NonidealConfig(device_variation=True,
                                         nonlinearity=True, sa_variation=True,
                                         sensing_range=True)),
    ("all", ni.NonidealConfig.all()),
)


def run_ablation(key: jax.Array, mapped, x_bits: jax.Array, *,
                 ref_bits: jax.Array,
                 ablations: Sequence[Tuple[str, ni.NonidealConfig]]
                 = TABLE2_ABLATION,
                 mc: McConfig = McConfig(), spec: MacroSpec = DEFAULT_MACRO,
                 host_metric_fns: Optional[Dict[str, HostMetricFn]] = None,
                 obs: Optional[RunLog] = None,
                 stderr_target: Optional[float] = None
                 ) -> Dict[str, McResult]:
    """Per-effect ensemble sweep: one `run_mc` per Table-II column, same
    chip key stream (each effect set resamples the same dies' variation)."""
    obs = as_runlog(obs)
    results = {}
    for name, cfg in ablations:
        obs.log_event("ablation_column", phase="mc", column=name)
        results[name] = run_mc(key, mapped, x_bits, ref_bits=ref_bits,
                               mc=dataclasses.replace(mc, cfg=cfg), spec=spec,
                               host_metric_fns=host_metric_fns, obs=obs,
                               stderr_target=stderr_target)
    return results
