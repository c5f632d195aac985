"""The control of ``correct``: the plain reference put in the program's
place, computed one precision below the configuration's (float32 ->
bfloat16), and held to the same comparison. It has to read as not
correct. The benchmark's own runs never run it.

* cold-start cells: the seed tree rounded through bfloat16 and placed on
  the device stands in for the restored parameters;
* publish cells: the seed tree rounded through bfloat16 is published by
  the reference implementation of the image format
  (``reference.publish``) into a store of its own, and checked as the
  program's images are.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

prints one JSON line per seed with the readings and the cell's limits.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import data, reference  # noqa: E402
from bench.harness import load_cell  # noqa: E402


def bf16_round(tree: dict) -> dict:
    import jax.numpy as jnp
    return {p: np.asarray(jnp.asarray(a).astype(jnp.bfloat16)
                          .astype(a.dtype)) for p, a in tree.items()}


def readings(cell, seed: int, config: dict | None = None) -> dict:
    """The control's readings in `cell` for `seed`. `config` replaces the
    cell's configuration (a smaller one, in the tests)."""
    import jax

    from bench.model import build

    _, template = build(config or cell.config)
    make = data.tree_maker(template)
    key = data.seed_key(seed)
    traffic = cell.traffic
    if traffic["driver"] == "coldstart":
        want = data.flat(data.host_tree(make, key, 0))
        placed = jax.device_put(bf16_round(want))
        platform = jax.devices()[0].platform
        return reference.restore_readings(
            placed, want, {p: platform for p in placed}, platform)
    want = data.flat(data.host_tree(make, key, 1))
    store: dict = {}
    tenant_key = bytes.fromhex(traffic["tenant_key_hex"])
    chunk_size = int(traffic["chunk_bytes"])
    blob = reference.publish(bf16_round(want), tenant_key=tenant_key,
                             root="R1", epoch=int(traffic["salt_epoch"]),
                             chunk_size=chunk_size,
                             put_chunk=store.__setitem__, image_id="control")
    return reference.publish_readings(
        blob, want, tenant_key=tenant_key, root="R1",
        epoch=int(traffic["salt_epoch"]), chunk_size=chunk_size,
        get_chunk=store.__getitem__)


def main(argv) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    args = p.parse_args(argv)
    cell = load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "readings": readings(cell, seed)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
    sys.exit(main(sys.argv[1:]))
