"""The share and warm-L1 cold-start cells on the CPU: the granite cell
with a replica that holds half of the experts restores its share and
reads the share's metrics; planted faults read ``correct`` false (a
wrong expert range, a Mamba-2 mixer's output zeroed, the shared expert
dropped, the held experts' part dropped or read at the wrong local
index, weights rounded below bfloat16, origin touched from a warm L1);
the control, the reference one precision below bfloat16, reads not
correct; and ``publish_leaves`` lays the image ``reference.py`` checks."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import data, harness, reference
from bench.tests import rehearsal

SHARE = "granite-4.0-h-small.coldstart_share"
_DRIVER = harness.load_module(
    rehearsal.REPO / "bench" / "drivers" / "coldstart_share.py")
LIMIT, HELD_LIMIT = _DRIVER.LOGITS_LIMIT, _DRIVER.HELD_LIMIT


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The rehearsal's checkout, the granite replica holding experts
    [0, 2) of the reduced 4 (rank 0 of 2), at d_model 1024 with the
    published expert widths: at the rehearsal's d_model 64, weights of
    spread 0.02 leave an expert's or the shared expert's part of a layer
    too small for the logits to tell it from rounding."""
    root = rehearsal.checkout(tmp_path_factory.mktemp("bench"))
    path = root / "bench" / "configs" / "granite-4.0-h-small.json"
    cfg = json.loads(path.read_text())
    cfg["sizes"].update(ep_size=2, ep_rank=0, d_model=1024, head_dim=128,
                        d_ff=768, shared_experts=2)
    path.write_text(json.dumps(cfg))
    return root


def driver(root):
    return harness.driver_of(harness.load_cell(SHARE, root))


def test_share_cell_restores_the_share(root):
    res = rehearsal.run(root, SHARE, trace=True)
    assert res["correct"] is True, res["checks"]
    assert 0 < res["checks"]["logits_rel_err"]["value"] < LIMIT / 2
    assert 0 < res["checks"]["held_experts_rel_err"]["value"] < HELD_LIMIT / 2
    # small leaves fill a chunk each at this size, so the ratio is well
    # above the published image's 1.009
    assert res["metrics"]["share_fetch_ratio"]["value"] >= 1.0
    assert "share_restore_roofline_pct" not in res["metrics"]   # no chip


def _wrap_cold_start(monkeypatch, change_params=None, change_share=None):
    from repro.serve import coldstart
    orig = coldstart.cold_start

    def broken(*args, **kw):
        if change_share:
            kw["share"] = change_share(kw["share"])
        engine, stats = orig(*args, **kw)
        if change_params:
            flat = jax.tree_util.tree_flatten_with_path(engine.params)
            leaves = [change_params(data.path_str(p), a) for p, a in flat[0]]
            engine.params = jax.device_put(
                jax.tree_util.tree_unflatten(flat[1], leaves))
        return engine, stats
    monkeypatch.setattr(coldstart, "cold_start", broken)


def _other_rank(share):
    return {p: sl and [(hi, 2 * hi - lo) if (lo, hi) == (0, 2) else (lo, hi)
                       for lo, hi in sl] for p, sl in share.items()}


def _fp8(path, a):
    return jnp.asarray(a).astype(jnp.float8_e4m3fn).astype(a.dtype)


def test_wrong_expert_range(root, monkeypatch):
    _wrap_cold_start(monkeypatch, change_share=_other_rank)
    res = rehearsal.run(root, SHARE)
    assert res["correct"] is False
    assert res["checks"]["bytes_differing"]["value"] > 0


def test_weights_below_bf16(root, monkeypatch):
    _wrap_cold_start(monkeypatch, change_params=_fp8)
    res = rehearsal.run(root, SHARE)
    assert res["correct"] is False
    assert res["checks"]["bytes_differing"]["value"] > 0


def test_mamba2_out_proj_zeroed(root, monkeypatch):
    """The restored bytes are right; the mixer's output projection is
    zeros where the program computes: only the logits show it."""
    from repro.models import mamba2
    orig = mamba2._gated_out

    def zeroed(p, y, z, cfg, dtype):
        return orig(dict(p, out_proj=jnp.zeros_like(p["out_proj"])), y, z,
                    cfg, dtype)
    monkeypatch.setattr(mamba2, "_gated_out", zeroed)
    res = rehearsal.run(root, SHARE)
    assert res["correct"] is False
    assert res["checks"]["bytes_differing"]["value"] == 0
    assert res["checks"]["logits_rel_err"]["value"] > LIMIT


def test_shared_expert_dropped(root, monkeypatch):
    from repro.models import moe
    monkeypatch.setattr(moe, "mlp_apply",
                        lambda p, x, kind, dtype: jnp.zeros_like(x))
    res = rehearsal.run(root, SHARE)
    assert res["correct"] is False
    assert res["checks"]["bytes_differing"]["value"] == 0
    assert res["checks"]["logits_rel_err"]["value"] > LIMIT


def test_held_experts_dropped(root, monkeypatch):
    """The held experts' part of every MoE layer left out where the
    program computes it; the restored bytes are right."""
    from repro.models import moe
    monkeypatch.setattr(moe, "_expert_ffn",
                        lambda p, x, kind, dtype: jnp.zeros_like(x))
    res = rehearsal.run(root, SHARE)
    assert res["correct"] is False
    assert res["checks"]["bytes_differing"]["value"] == 0
    assert res["checks"]["held_experts_rel_err"]["value"] > HELD_LIMIT


class _ShiftedHeld:
    """A configuration whose held range reads one expert on: the local
    index of expert e comes out as e - lo - 1."""

    def __init__(self, cfg):
        self._cfg = cfg

    def __getattr__(self, name):
        return getattr(self._cfg, name)

    @property
    def held_experts(self):
        lo, hi = self._cfg.held_experts
        return lo + 1, hi + 1


def test_held_experts_misindexed(root, monkeypatch):
    from repro.models import moe
    orig = moe._moe_sort
    monkeypatch.setattr(
        moe, "_moe_sort", lambda p, x, cfg, dtype, dropless=False: orig(
            p, x, _ShiftedHeld(cfg), dtype, dropless))
    res = rehearsal.run(root, SHARE)
    assert res["correct"] is False
    assert res["checks"]["bytes_differing"]["value"] == 0
    assert res["checks"]["held_experts_rel_err"]["value"] > HELD_LIMIT


def test_control_reads_not_correct(root, tmp_path):
    cell = harness.load_cell(SHARE, root)
    ctx = harness.Context(cell, 2**33 + 7, 0.5, tmp_path, "cpu",
                          lambda msg: None)
    got = driver(root).control(ctx)
    assert got["bytes_differing"] > 0
    assert got["leaves_not_in_hbm"] == 0


def test_publish_leaves_lays_the_reference_image(root):
    rng = np.random.default_rng(3)
    tree = {"a/w": rng.standard_normal((300, 7)).astype(np.float32),
            "b": np.zeros((5,), np.float32),
            "c/bf": jnp.asarray(rng.standard_normal((2, 1000)),
                                jnp.bfloat16)}
    tree = {p: np.asarray(a) for p, a in tree.items()}
    store: dict = {}
    blob, kept = driver(root).publish_leaves(
        {p: jax.ShapeDtypeStruct(a.shape, a.dtype) for p, a in tree.items()},
        tree.__getitem__, lambda p, a: a[:1], tenant_key=b"K" * 32,
        root="R1", epoch=0, chunk_size=4096, put_chunk=store.__setitem__)
    assert reference.publish_readings(
        blob, tree, tenant_key=b"K" * 32, root="R1", epoch=0,
        chunk_size=4096, get_chunk=store.__getitem__) == {
        "chunks_wrong": 0, "manifest_wrong": 0}
    assert all(np.array_equal(kept[p], a[:1]) for p, a in tree.items())


def test_origin_touched_from_a_warm_l1(tmp_path, monkeypatch):
    from repro.core.cache.local import LocalCache
    root = rehearsal.checkout(tmp_path)
    for name in ("get", "peek"):
        monkeypatch.setattr(LocalCache, name, lambda self, key: None)
    res = rehearsal.run(root, "whisper-base.coldstart_l1warm")
    assert res["correct"] is False
    assert res["checks"]["origin_fetches"]["value"] > 0
    assert res["checks"]["bytes_differing"]["value"] == 0
