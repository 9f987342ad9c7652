"""Population Monte Carlo: the window drives whole `run_mc_detector` calls.

Traffic parameters (`traffic/<mix>.json`, kind "population"):

  effects          "all", "none" or {effect: bool} (Table II columns)
  device_model     "analytic" (default) or another `repro.device` backend
  t_days           deployment age of the device backend (default 0)
  dies_per_call    population of each call, a whole number of chunks
  chunk            dies per jitted chunk program
  images_per_die   evaluation images every die sees
  calib_images     images `calibrate_bn` sees in set-up
  check            {"dies": dies compared, "head_cells_off": limit}

Call `i` of the window samples its population under
`fold_in(fold_in(seed_key, 1), i)`; the window ends at the first call
boundary after `--seconds`.  `dies_per_s` is every die scored in the
window over all of the window's time.  Afterwards a sample of the window's
dies, drawn from the seed, is run through the plain reference, and the
head predictions the window produced for them are compared with it.
"""
from __future__ import annotations

import dataclasses
import sys
import time
from typing import Dict, List

import numpy as np

import flops
import harness
import images
import reference


@dataclasses.dataclass
class Population:
    """Set-up state of a population cell."""
    conf: Dict
    traffic: Dict
    seed: int
    det: object
    raw_params: object            # the benchmark's weights
    params: object                # the same, with calibrate_bn's statistics
    calib: Dict
    evals: Dict
    mc: object
    key: object
    captured: List[List[np.ndarray]]

    def call_key(self, i: int):
        import jax
        return jax.random.fold_in(jax.random.fold_in(self.key, 1), i)


def _capture_scoring(captured: List[List[np.ndarray]]) -> None:
    """Keep every chunk's head predictions as `run_mc_detector` hands them
    to host scoring, so the check compares what the window produced."""
    import repro.train.det_loss as det_loss
    score = getattr(det_loss.evaluate_map_per_chip, "__wrapped__",
                    det_loss.evaluate_map_per_chip)

    def scoring(preds, *args, **kwargs):
        preds = np.asarray(preds)
        if captured:
            captured[-1].append(preds)
        return score(preds, *args, **kwargs)

    scoring.__wrapped__ = score
    det_loss.evaluate_map_per_chip = scoring


def setup(conf: Dict, traffic: Dict, seed: int) -> Population:
    import jax
    import jax.numpy as jnp
    from repro.mc import McConfig, run_mc_detector

    net = conf["network"]
    if traffic["dies_per_call"] % traffic["chunk"]:
        raise ValueError("dies_per_call must be a whole number of chunks")
    det = harness.detector(conf)
    key = harness.seed_key(seed)
    raw = harness.init_params(conf, jax.random.fold_in(key, 0))
    render = lambda n, stream: images.render_batch(
        tuple(net["img_hw"]), n, net["n_classes"], net["n_anchors"],
        det.cfg.strides, seed=(seed, stream))
    calib = render(traffic["calib_images"], 0)
    evals = render(traffic["images_per_die"], 1)
    params = det.calibrate_bn(raw, jnp.asarray(calib["images"]))
    evals["device_images"] = jnp.asarray(evals["images"])
    mc = McConfig(n_chips=traffic["dies_per_call"],
                  chunk_size=traffic["chunk"],
                  cfg=harness.nonideal(harness.effects(traffic)),
                  device=harness.device_model(traffic))
    captured: List[List[np.ndarray]] = []
    _capture_scoring(captured)
    pop = Population(conf, traffic, seed, det, raw, params, calib, evals,
                     mc, key, captured)
    # two chunks compile and exercise every program a call runs
    warm = dataclasses.replace(mc, n_chips=2 * mc.chunk_size)
    captured.append([])
    run_mc_detector(jax.random.fold_in(key, 2), det, params,
                    evals["device_images"], evals["boxes"],
                    evals["classes"], mc=warm)
    captured.clear()
    return pop


def call(pop: Population, i: int):
    """Call `i` of the window: one whole population through the program."""
    import jax
    from repro.mc import run_mc_detector
    pop.captured.append([])
    with jax.profiler.TraceAnnotation("bench.run_mc_detector"):
        return run_mc_detector(pop.call_key(i), pop.det, pop.params,
                               pop.evals["device_images"],
                               pop.evals["boxes"], pop.evals["classes"],
                               mc=pop.mc)


def sample_dies(pop: Population, n_calls: int, n: int):
    """(call, die) pairs to compare, drawn from the seed among the
    window's dies."""
    dies = pop.traffic["dies_per_call"]
    rng = np.random.default_rng((pop.seed, 7))
    picks = rng.choice(n_calls * dies, size=min(n, n_calls * dies),
                       replace=False)
    return [(int(p) // dies, int(p) % dies) for p in sorted(picks)]


def head_cells_off(pop: Population, pairs, products: str = "exact"):
    """Per sampled die, the share of head cells at which the reference
    (at `products` precision) and the window's predictions differ."""
    import jax.numpy as jnp
    phys = reference.Physics(pop.conf)
    flags = harness.effects(pop.traffic)
    if pop.traffic.get("device_model", "analytic") != "analytic":
        raise NotImplementedError("the reference draws analytic dies only")
    params = reference.calibrated(phys, pop.raw_params, pop.calib["images"])
    x0 = reference.stem_bits(phys, params, pop.evals["images"])
    chunk = pop.traffic["chunk"]
    offs = []
    for call_i, die in pairs:
        ref = reference.die_predictions(
            params, x0, pop.call_key(call_i), jnp.uint32(die),
            phys=phys, effects=reference.effects_tuple(flags),
            products=products)
        try:
            got = pop.captured[call_i][die // chunk][die % chunk]
        except IndexError:          # the window never produced this die
            offs.append(1.0)
            continue
        offs.append(reference.cells_off(got, ref))
    return offs


def run(run) -> Dict:
    pop = setup(run.conf, run.traffic, run.seed)
    run.setup_done()
    dies = calls = failed = 0
    host_s = 0.0
    ends, hosts = [], []
    with run.window():
        t0 = time.perf_counter()
        while True:
            res = call(pop, calls)
            ends.append(time.perf_counter() - t0)
            calls += 1
            dies += res.n_chips
            host_s += res.host_s
            hosts.append(res.host_s)
            failed += int(np.sum(~np.isfinite(res.per_chip["map50"])))
            if time.perf_counter() - t0 >= run.seconds:
                break
        elapsed = time.perf_counter() - t0
    run.read_memory()
    print("call seconds:", np.diff(ends, prepend=0.0).tolist(),
          "host scoring seconds:", hosts, file=sys.stderr)
    del pop.params, res
    check = run.traffic["check"]
    offs = head_cells_off(pop, sample_dies(pop, calls, check["dies"]))
    print("head_cells_off per sampled die:", offs, file=sys.stderr)
    run.check("head_cells_off", max(offs), check["head_cells_off"])
    run.counters.update(dies=dies, calls=calls, host_s=host_s,
                        window_s=elapsed,
                        die_flops=flops.die_flops(run.conf)
                        * run.traffic["images_per_die"])
    return {"attempted": dies, "failed": failed,
            "e2e": {"dies_per_s": dies / elapsed}}


def readings(conf: Dict, traffic: Dict, seed: int, control: bool) -> Dict:
    """The numbers compared, for setting limits: one call of the window's
    size on `seed` against the reference, and with `control` the
    reference at the precision below the configuration's against it."""
    pop = setup(conf, traffic, seed)
    call(pop, 0)
    pairs = sample_dies(pop, 1, traffic["check"]["dies"])
    out = {"head_cells_off": max(head_cells_off(pop, pairs))}
    if control:
        out["control.head_cells_off"] = max(
            head_cells_off(pop, pairs, products="three_pass"))
    return out
