"""A configuration file (``bench/configs/<name>.json``) to the program's
model object and parameter template.

The file names the program's registry entry (``arch``) and holds every
size it is run at under ``sizes``; the sizes replace the registry's, so
the file is what runs. Where the file states the image (``image``: leaf
count and bytes), the template has to match it: a program whose
parameter tree drifts from the configuration is refused, not measured.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def build(config: dict) -> tuple:
    import jax

    from repro.configs import get_config
    from repro.models import build_model

    cfg = dataclasses.replace(get_config(config["arch"]), **config["sizes"])
    model = build_model(cfg)
    template = model.param_shapes()
    image = config.get("image")
    if image is not None:
        leaves = jax.tree.leaves(template)
        got = {"leaves": len(leaves),
               "bytes": sum(int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
                            for s in leaves)}
        if got != {k: image[k] for k in got}:
            raise ValueError(f"{config['name']}: the program's parameter "
                             f"tree is {got}, the configuration states "
                             f"{image}")
    return model, template
