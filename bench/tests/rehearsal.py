"""Helpers of the CPU rehearsal: a copy of the benchmark in a temporary
checkout whose configurations are cut to the program's ``reduced()`` size,
and one run of a cell there without the harness's look for a chip."""
from __future__ import annotations

import dataclasses
import json
import shutil
import tempfile
import time
from pathlib import Path

from bench import harness

REPO = Path(__file__).resolve().parents[2]


def reduced_sizes(arch: str) -> dict:
    """The sizes ``ModelConfig.reduced()`` changes, for a config file."""
    from repro.configs import get_config
    full = get_config(arch)
    small = full.reduced()
    return {f.name: getattr(small, f.name) for f in dataclasses.fields(full)
            if f.name != "name" and getattr(small, f.name)
            != getattr(full, f.name)}


def checkout(tmp: Path) -> Path:
    """A copy of BENCHMARK.json and bench/ under `tmp`, every
    configuration cut to its reduced size (and its image left unstated)."""
    root = Path(tmp) / "checkout"
    root.mkdir()
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for path in (root / "bench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg["sizes"] = reduced_sizes(cfg["arch"])
        cfg.pop("image", None)
        path.write_text(json.dumps(cfg))
    return root


def run(root: Path, cell_name: str, *, seed: int = 2**33 + 5,
        seconds: float = 0.5, trace: bool = False) -> dict:
    """One run of the cell on the CPU; the result object."""
    import jax
    cell = harness.load_cell(cell_name, root)
    work = Path(tempfile.mkdtemp(prefix="work-", dir=root))
    return harness.run_cell(cell, seed, seconds, trace, workdir=work,
                            t_start=time.perf_counter(),
                            devices=jax.devices()[:cell.chips],
                            log=lambda msg: None)
