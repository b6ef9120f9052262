"""What every cell shares: the benchmark file, the cell's configuration
and traffic files found by name, the accelerator check, the compile
cache and compile counter, the device facts, the traced window and its
per-layer readers, and the result line.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "chipbench")
CACHE_DIR = os.path.join(HERE, ".jax_cache")
TRACE_DIR = os.path.join(HERE, ".trace")


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, bench_path: str = None) -> dict:
    """The workload's entry, configuration, traffic and limits, each
    read from the file its name leads to."""
    bench = load_json(bench_path or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {
        "bench": bench, "cell": cell,
        "config": load_json(os.path.join(ROOT, conf["file"])),
        "traffic": load_json(os.path.join(HERE, "traffic",
                                          cell["traffic"] + ".json")),
        "limits": load_json(os.path.join(HERE, "limits",
                                         workload + ".json")),
    }


def metrics_for(bench: dict, workload: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports:
    those that list it, and those that list no cells."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


def family(cfg: dict):
    """The module ``families/<family>.py`` a configuration file names."""
    return importlib.import_module("chipbench.families." + cfg["family"])


SEED_SPACE = 2 ** 31 - 2 ** 17     # the program adds offsets to its seed


def sub_seeds(seed: int, names=("weights", "data", "agent",
                                "sample")) -> dict:
    """Independent 31-bit seeds for each use, from any whole ``seed``."""
    import numpy as np
    s = np.random.SeedSequence(int(seed)).generate_state(len(names))
    return {k: int(v) % SEED_SPACE for k, v in zip(names, s)}


def require_devices(n: int):
    """The chips this cell needs, or exit non-zero: a CPU run measures
    nothing this benchmark reports."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu" or len(devs) < n:
        sys.exit(f"chipbench: needs {n} accelerator chip(s); JAX sees "
                 f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:n]


class CompileCounter:
    """Counts JAX compilation events (tracing, lowering, compiling) and
    their seconds; a cell's window must see none."""

    def __init__(self):
        self.events = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.events += 1
            self.seconds += duration


def enable_cache():
    """JAX's persistent compilation cache at one fixed path inside the
    checkout, for every program this process compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def device_facts(devs) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r}")
    return table[kind]


class TraceWindow:
    """The profiler around part of the window, and the spans the
    benchmark marks on the host timeline."""

    def __init__(self, workload: str, on: bool):
        self.on = on
        self.dir = os.path.join(TRACE_DIR, workload)
        self.active = False

    def start(self):
        if self.on:
            import jax
            shutil.rmtree(self.dir, ignore_errors=True)
            os.makedirs(self.dir, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host spans, no per-call trace
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.active = True

    def stop(self):
        if self.active:
            import jax
            jax.profiler.stop_trace()
            self.active = False

    @staticmethod
    def span(name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)


def read_metric(name: str, ctx) -> float:
    """Run the per-layer reader ``metrics/<name>.py``; None when it finds
    nothing to read."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def compare(numbers: dict, limits: dict, names=None) -> tuple:
    """(correct, checks): each compared number beside its limit; a
    number over its limit, missing, or not finite is not correct.
    ``names`` restricts the comparison to those limits (a control reads
    only the numbers its references compute)."""
    import math
    checks, ok = {}, True
    for name, lim in limits["limits"].items():
        if names is not None and name not in names:
            continue
        v = numbers.get(name)
        good = v is not None and math.isfinite(v) and v <= lim
        ok = ok and good
        checks[name] = {"value": v, "limit": lim}
    return ok, checks


def emit(result: dict, checks: dict):
    """Print the compared numbers as the last lines of standard error,
    and the result as the last line of standard output."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = checks
    print(json.dumps(result), flush=True)


def now() -> float:
    return time.perf_counter()
