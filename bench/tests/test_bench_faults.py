"""``correct`` comes out false when the cold-start path is broken
underneath a run (a byte altered where it is produced, half of the
chunks left out, the weights placed in a lower precision, the chunks'
digests no longer compared), and the control — the reference computed
in bfloat16 in the program's place — reads above the limit."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, harness
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


def _decrypt_fault(kind):
    from repro.core.crypto import convergent
    orig = convergent.decrypt_chunks
    seen = [0]

    def broken(*args, **kw):
        plains = orig(*args, **kw)
        if kind == "byte":
            return [_flip_first_byte(np.frombuffer(plains[0], np.uint8))
                    .tobytes()] + plains[1:]
        out = []                    # every other chunk decoded as zeros
        for p in plains:
            seen[0] += 1
            out.append(bytes(len(p)) if seen[0] % 2 else p)
        return out
    return "decrypt_chunks", convergent, broken


def _placed_fault(kind):
    from repro.serve import coldstart
    orig = coldstart.cold_start
    change = _bf16 if kind == "bf16" else _flip_first_byte

    def broken(*args, **kw):
        engine, stats = orig(*args, **kw)
        leaves, treedef = jax.tree.flatten(engine.params)
        if kind == "bf16":
            leaves = [change(a) for a in leaves]
        else:
            leaves = [change(leaves[0])] + leaves[1:]
        engine.params = jax.device_put(jax.tree.unflatten(treedef, leaves))
        return engine, stats
    return "cold_start", coldstart, broken


COLDSTART_FAULTS = {
    "byte altered in decode": lambda: _decrypt_fault("byte"),
    "half of the chunks left out": lambda: _decrypt_fault("half"),
    "one byte of one placed leaf": lambda: _placed_fault("byte"),
    "placed in bfloat16": lambda: _placed_fault("bf16"),
}


@pytest.mark.parametrize("fault", sorted(COLDSTART_FAULTS))
def test_coldstart_fault_makes_correct_false(root, monkeypatch, fault):
    name, module, broken = COLDSTART_FAULTS[fault]()
    monkeypatch.setattr(module, name, broken)
    res = rehearsal.run(root, "xlstm-350m.coldstart")
    assert res["correct"] is False
    assert res["checks"]["bytes_differing"]["value"] > 0
    assert res["failed"] == res["attempted"]


def test_unverified_restore_makes_correct_false(root, monkeypatch):
    """Decode that still computes each chunk's digest but no longer holds
    it against the manifest's: the restored bytes are right, and only the
    tampered restore after the window shows the fault."""
    from repro.core.crypto import convergent
    orig = convergent.decrypt_chunks

    def unverified(cts, keys, expect, **kw):
        return orig(cts, keys, [hashlib.sha256(c).digest() for c in cts],
                    **kw)
    monkeypatch.setattr(convergent, "decrypt_chunks", unverified)
    res = rehearsal.run(root, "xlstm-350m.coldstart")
    assert res["correct"] is False
    assert res["checks"]["tampered_restores_accepted"]["value"] == 1
    assert res["checks"]["bytes_differing"]["value"] == 0


def test_control_reads_above_every_limit(root):
    c = harness.load_cell("xlstm-350m.coldstart", root)
    for seed in (1, 2**33 + 1, 2**31 + 12345):
        got = control.readings(c, seed)
        assert got["bytes_differing"] > 0, (seed, got)
