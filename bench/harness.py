"""What every cell shares: finding its files by name, the chip check, the
compile cache, seeds, weights, and the program's detector built from a
configuration file.

A cell of `BENCHMARK.json` names a configuration and a traffic mix.  The
configuration is `configs/<config>.json`; the traffic is
`traffic/<traffic>.json`, whose `kind` names the runner
`kinds/<kind>.py`; each per-layer metric is read by `metrics/<name>.py`.
Nothing here knows a cell, a traffic mix or a metric by name.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
SPEC_FILE = CHECKOUT / "BENCHMARK.json"
OUT_DIR = CHECKOUT / ".bench_out"            # traces of --trace 1 runs

EFFECTS = ("device_variation", "nonlinearity", "sa_variation",
           "sensing_range", "ir_drop")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path) -> ModuleType:
    """Import a runner or a metric reader by its file path."""
    name = "bench_" + path.parent.name + "_" + path.stem.replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def resolve(spec: Dict, workload: str, bench: Path = BENCH) -> Dict:
    """A cell's entry, configuration file, traffic file and runner path,
    found by name under `bench`."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    conf = load_json(bench.parent / configs[cell["config"]]["file"])
    traffic = load_json(bench / "traffic" / f"{cell['traffic']}.json")
    runner = bench / "kinds" / f"{traffic['kind']}.py"
    if not runner.is_file():
        raise FileNotFoundError(f"no runner {runner} for kind "
                                f"{traffic['kind']!r}")
    return {"cell": cell, "conf": conf, "traffic": traffic, "runner": runner}


def metrics_for(spec: Dict, workload: str, section: str):
    """The entries of `section` ("end_to_end" or "per_layer") this cell
    reports.  A per-layer metric without `workloads` is reported wherever
    the end-to-end metric it moves is."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    if section == "end_to_end":
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def device_check(chips: int) -> Dict:
    """The devices JAX sees; anything but `chips` or more TPUs raises."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {d.platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def enable_compile_cache() -> str:
    """The program's persistent compile cache (`repro.launch.compile_cache`:
    `JAX_COMPILATION_CACHE_DIR` if set, else the checkout's fixed
    `.jax_cache`), with every program cached, however quick to compile, so
    that only a cell's first run in a checkout compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def import_program():
    """Put the program under test (`<checkout>/src`) on the import path."""
    src = CHECKOUT / "src"
    if not (src / "repro").is_dir():
        raise FileNotFoundError(f"the program is not at {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def seed_key(seed: int):
    """A PRNG key that keeps every bit of a seed up to 64 bits."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def effects(traffic: Dict) -> Dict[str, bool]:
    """The traffic's effect set: "all", "none" or a mapping of flags."""
    e = traffic["effects"]
    if e == "all":
        return {k: True for k in EFFECTS}
    if e == "none":
        return {k: False for k in EFFECTS}
    unknown = set(e) - set(EFFECTS)
    if unknown:
        raise ValueError(f"unknown effects {sorted(unknown)}")
    return {k: bool(e.get(k, False)) for k in EFFECTS}


def nonideal(flags: Dict[str, bool]):
    from repro.core import NonidealConfig
    return NonidealConfig(**flags)


def device_model(traffic: Dict):
    """The traffic's device backend (`device_model`, `t_days`); None for
    the analytic one, which the program takes as its default."""
    name = traffic.get("device_model", "analytic")
    t_days = traffic.get("t_days", 0)
    if name == "analytic" and not t_days:
        return None
    from repro.device import get_device_model
    return get_device_model(name, t_days=t_days)


def detector(conf: Dict):
    """The program's detector at the configuration file's network."""
    from repro.models import IRCDetector
    from repro.models.detector import DetectorConfig
    net = {k: tuple(v) if isinstance(v, list) else v
           for k, v in conf["network"].items()}
    return IRCDetector(DetectorConfig(**net))


def param_shapes(conf: Dict) -> Dict:
    """Shapes of the detector's weights, from the configuration alone."""
    net = conf["network"]
    g, stages = net["group"], net["stage_channels"]
    ho = net["n_anchors"] * (5 + net["n_classes"])
    vec = lambda n: {"gamma": ("ones", (n,)), "beta": ("zeros", (n,)),
                     "mean": ("zeros", (n,)), "var": ("ones", (n,))}
    shapes = {"stem": ("normal", (3, 3, 3, stages[0])),
              "stem_bn": vec(stages[0]),
              "head": ("normal", (stages[-1], ho)),
              "head_b": ("prior", (ho,))}
    for s, (ch, nb) in enumerate(zip(stages, net["blocks_per_stage"])):
        for b in range(nb):
            blk = {"w": ("normal", (9 * g, g, ch // g))}
            if net["use_bn"]:
                blk["bn"] = vec(ch)
            shapes[f"s{s}b{b}"] = blk
    return shapes


def init_params(conf: Dict, key):
    """Random weights from `key`, made on the device in one jitted call:
    normal with std 1/sqrt(fan-in) (the second-to-last axis), BN scales 1,
    shifts and statistics 0/1, and the head's objectness biases at the
    configuration's prior probability, log(p / (1 - p)), as a detector is
    initialised, so that few random boxes clear the decode threshold."""
    import math

    import jax
    import jax.numpy as jnp

    shapes = param_shapes(conf)
    is_leaf = lambda x: isinstance(x, tuple) and isinstance(x[0], str)
    leaves, tree = jax.tree.flatten(shapes, is_leaf=is_leaf)
    net = conf["network"]
    prior = conf["weights"]["objectness_prior"]
    head_b = jnp.zeros((net["n_anchors"], 5 + net["n_classes"]))
    head_b = head_b.at[:, 4].set(math.log(prior / (1.0 - prior))).ravel()

    def make(key):
        out = []
        for i, (init, shape) in enumerate(leaves):
            if init == "zeros":
                out.append(jnp.zeros(shape, jnp.float32))
            elif init == "ones":
                out.append(jnp.ones(shape, jnp.float32))
            elif init == "prior":
                out.append(head_b)
            else:
                std = 1.0 / (shape[-2] if len(shape) >= 2 else shape[-1]) ** .5
                out.append(std * jax.random.normal(jax.random.fold_in(key, i),
                                                   shape, jnp.float32))
        return jax.tree.unflatten(tree, out)

    return jax.jit(make)(key)


def peak(view: Dict, name: str) -> float:
    """A published peak of the run's chip; a chip not in `peaks.json` is
    an error, not a default."""
    kinds = {k: v for k, v in view["peaks"].items() if k != "source"}
    if view["device_kind"] not in kinds:
        raise KeyError(f"no peaks for device kind {view['device_kind']!r} "
                       f"in peaks.json (known: {sorted(kinds)})")
    return float(kinds[view["device_kind"]][name])


def idle_share(view: Dict):
    """Percent of the traced window in which no op ran on the chips."""
    t = view["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def memory_peak_bytes() -> int:
    """Peak device memory of the fullest chip this process used: the peak of
    the allocator's live buffers plus the peak of the pool that the TPU
    runtime reserves for the programs' temporaries, as `memory_stats()`
    reports each.  The whole statistics go to standard error."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    print("memory_stats:", stats, file=sys.stderr)
    return int(max(s.get("peak_bytes_in_use", 0)
                   + s.get("peak_bytes_reserved", 0) for s in stats))
