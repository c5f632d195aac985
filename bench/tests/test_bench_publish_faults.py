"""``correct`` comes out false when the publish path is broken
underneath a run (a ciphertext byte altered, half of the chunks never
stored, the checkpoint published in bfloat16), and the reference
implementation of the image format agrees with itself."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, harness, reference
from bench.tests import rehearsal


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal.checkout(tmp_path_factory.mktemp("bench"))


def _bf16(a):
    return np.asarray(jnp.asarray(a).astype(jnp.bfloat16).astype(a.dtype))


def _flip_first_byte(a):
    a = np.array(a)
    a.reshape(-1).view(np.uint8)[0] ^= 1
    return a


def _store_fault(kind):
    from repro.core.store import ChunkStore
    orig = ChunkStore.put_if_absent
    calls = []

    def broken(self, root, name, data):
        calls.append(name)
        if kind == "byte":
            data = _flip_first_byte(np.frombuffer(data, np.uint8)).tobytes()
        if kind == "half" and len(calls) % 2:
            return True                     # acknowledged, never stored
        return orig(self, root, name, data)
    return "put_if_absent", ChunkStore, broken


def _tree_fault():
    from repro.core.service import ImageService
    orig = ImageService.publish

    def broken(self, tree, **kw):
        return orig(self, jax.tree.map(_bf16, tree), **kw)
    return "publish", ImageService, broken


PUBLISH_FAULTS = {
    "a ciphertext byte altered on upload": lambda: _store_fault("byte"),
    "half of the chunks never stored": lambda: _store_fault("half"),
    "checkpoint published in bfloat16": _tree_fault,
}


@pytest.mark.parametrize("fault", sorted(PUBLISH_FAULTS))
def test_publish_fault_makes_correct_false(root, monkeypatch, fault):
    name, cls, broken = PUBLISH_FAULTS[fault]()
    monkeypatch.setattr(cls, name, broken)
    res = rehearsal.run(root, "whisper-base.publish")
    assert res["correct"] is False
    assert res["checks"]["chunks_wrong"]["value"] > 0


def test_control_reads_above_every_limit(root):
    c = harness.load_cell("whisper-base.publish", root)
    for seed in (1, 2**33 + 1, 2**31 + 12345):
        got = control.readings(c, seed)
        assert got["chunks_wrong"] > 0, (seed, got)


def test_reference_publish_reads_correct_against_itself(root):
    c = harness.load_cell("whisper-base.publish", root)
    from bench import data
    from bench.model import build
    _, template = build(c.config)
    tree = data.flat(data.host_tree(data.tree_maker(template),
                                    data.seed_key(7), 1))
    store = {}
    key = bytes.fromhex(c.traffic["tenant_key_hex"])
    blob = reference.publish(tree, tenant_key=key, root="R1", epoch=0,
                             chunk_size=c.traffic["chunk_bytes"],
                             put_chunk=store.__setitem__)
    got = reference.publish_readings(blob, tree, tenant_key=key, root="R1",
                                     epoch=0,
                                     chunk_size=c.traffic["chunk_bytes"],
                                     get_chunk=store.__getitem__)
    assert got == {"chunks_wrong": 0, "manifest_wrong": 0}
