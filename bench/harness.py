"""The benchmark harness: runs one cell of ``BENCHMARK.json`` once.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by the name in ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the model configuration as it is run;
* ``bench/traffic/<traffic>.json``: the traffic mix's parameters, with
  the name of the generator (``driver``) that reads them;
* ``bench/drivers/<driver>.py``: one general generator per kind of
  traffic, with ``setup``, ``unit``, ``check``, ``verify`` and
  ``end_to_end``;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric, whose
  ``read(run)`` returns a number, or None where it finds nothing to read.

A run: set-up (untimed by the window), then units of work until their
time adds up to ``--seconds`` (the unit in flight then is finished and
counted). Right after each unit, and outside its time, the driver's
``check`` compares what the unit produced with the plain reference and
keeps only the readings, so no unit's output outlives it (a restored
image would otherwise hold HBM through the later units). After the
window, the driver's ``verify`` adds the readings up, with any check of
its own, into the numbers that decide ``correct``. With ``--trace 1``
the run is under the JAX profiler and the per-layer metrics are printed
in place of the end-to-end ones; each unit is one ``bench.window`` span.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    root: Path
    config: dict
    traffic: dict
    chips: int
    end_to_end: list
    per_layer: list


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise ValueError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    bench = root / "bench"
    return Cell(
        name=name, root=root,
        config=json.loads((bench / "configs" / f"{w['config']}.json")
                          .read_text()),
        traffic=json.loads((bench / "traffic" / f"{w['traffic']}.json")
                           .read_text()),
        chips=int(w["chips"]), end_to_end=e2e, per_layer=per_layer)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver_of(cell: Cell):
    return load_module(cell.root / "bench" / "drivers"
                       / f"{cell.traffic['driver']}.py")


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when the environment sets it, else the fixed ``.jax_cache/`` in the
    checkout. Every program is cached, however short its compile."""
    import jax
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return cache


def require_chips(chips: int) -> list:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


ROUTE_PREFIX = "kernel.route."


def kernel_launches(before: dict, after: dict) -> int:
    """Kernel launches between two ``COUNTERS`` snapshots: the sum of the
    ``kernel.route.<kernel>.<route>`` deltas."""
    return int(sum(v - before.get(k, 0) for k, v in after.items()
                   if k.startswith(ROUTE_PREFIX)))


def memory_peak_bytes(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclass
class Context:
    """What a driver gets: the cell, the seed and where to write."""
    cell: Cell
    seed: int
    seconds: float
    workdir: Path
    platform: str = "tpu"           # where the restored weights must sit
    log: object = print


@dataclass
class RunData:
    """What a per-layer metric reader gets."""
    cell: Cell
    records: list
    trace: object = None            # bench.trace.Trace, or None
    device_kind: str = ""


class CompileCounter:
    """Counts the programs JAX builds while active, and how many of them
    came from the persistent compilation cache (JAX's monitoring
    events): ``built - cache_hits`` are compilations."""

    BUILT = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.built = self.cache_hits = 0

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_built)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_built)
        jax.monitoring.unregister_event_listener(self._on_event)

    def _on_built(self, event, duration, **kw):
        if event == self.BUILT:
            self.built += 1

    def _on_event(self, event, **kw):
        if event == self.HIT:
            self.cache_hits += 1

    @property
    def compiled(self) -> int:
        return self.built - self.cache_hits


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             workdir: Path, t_start: float, devices: list,
             log=print) -> dict:
    """One run of `cell`; returns the result object (not yet printed)."""
    import jax

    from bench import trace as tracing

    driver = driver_of(cell)
    ctx = Context(cell, seed, seconds, Path(workdir), devices[0].platform,
                  log)
    state = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f}s")

    capture = tracing.Capture(Path(workdir) / "trace") if trace else None
    records = []
    window_s = 0.0
    if capture:
        capture.start()
    with CompileCounter() as compiles:
        while window_s < seconds:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.window"):
                rec = driver.unit(ctx, state, len(records))
            window_s += time.perf_counter() - t0
            with jax.profiler.TraceAnnotation("bench.verify"):
                rec["readings"] = driver.check(ctx, state, rec)
            records.append(rec)
    if capture:
        capture.stop()
    log(f"window {window_s:.3f}s: {len(records)} units; in it and its "
        f"checks {compiles.compiled} programs compiled, "
        f"{compiles.cache_hits} loaded from the persistent cache")

    mem = memory_peak_bytes(devices)
    with jax.profiler.TraceAnnotation("bench.verify"):
        checks, failed = driver.verify(ctx, state, records)
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem}
    breakdown = None
    if trace:
        reduced = capture.reduce(len(devices))
        run = RunData(cell, records, reduced, devices[0].device_kind)
        metrics = {}
        for m in cell.per_layer:
            reader = load_module(cell.root / "bench" / "metrics"
                                 / f"{m['name']}.py")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if reduced is not None and reduced.busy_s() is not None:
            device["busy_s"] = reduced.busy_s()
            device["window_s"] = reduced.window_s()
            breakdown = reduced.breakdown()
    else:
        values = dict(driver.end_to_end(records), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    result = {"correct": correct, "attempted": len(records),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    cell = load_cell(args.workload)
    try:
        devices = require_chips(cell.chips)
    except NoChip as e:
        log(f"bench: {e}; no result")
        return 2
    log(f"compile cache: {enable_compile_cache()}")
    with tempfile.TemporaryDirectory(prefix="bench-") as workdir:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          workdir=Path(workdir), t_start=t_start,
                          devices=devices, log=log)
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0
