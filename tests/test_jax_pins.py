"""Pinned upstream-bug regression tests.

``_moe_sort`` (models/moe.py) carries a workaround for a jax 0.4.37 CPU
SPMD miscompile: a gather whose sharded operand has a non-divisible
leading dim — the (E*cap + 1)-row overflow buffer of the original MoE
dispatch — returns WRONG VALUES under XLA's padded-gather partitioning.
The fix keeps the buffer exactly E*cap rows and routes dropped slots
through ``mode="drop"`` scatter + a clamped gather.

This test pins the bug itself: it rebuilds the pre-fix overflow-row
formulation and asserts it still miscompiles under the same mesh the
real impl runs on (and that the fixed impl matches the oracle). When a
jax upgrade makes the overflow formulation MATCH, this test FAILS — the
signal that the upstream bug is fixed and the ``_moe_sort`` workaround
(and the ROADMAP note) can be dropped.
"""
import pathlib
import subprocess
import sys
import textwrap

_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_config
    from repro.models.moe import _expert_ffn, mlp_apply, moe_apply, moe_init
    from repro.sharding.constrain import use_policy, logical_constraint
    from repro.sharding.rules import ShardingPolicy

    cfg = get_config("kimi-k2-1t-a32b").reduced(
        num_experts=8, experts_per_token=2, d_model=32, d_ff=64,
        capacity_factor=8.0, shared_experts=1, first_dense_layers=0)
    p, _ = moe_init(jax.random.key(0), "m", cfg)
    x = jax.random.normal(jax.random.key(1), (4, 8, 32), jnp.float32)

    def moe_sort_overflow(p, x, cfg, dtype):
        # the PRE-FIX _moe_sort dispatch: an (E*cap + 1)-row buffer
        # whose last row absorbs dropped assignments, gathered straight
        # through its non-divisible leading dim
        B, S, D = x.shape
        E, K = cfg.num_experts, cfg.experts_per_token
        T = B * S
        xf = x.reshape(T, D)
        logits = (xf @ p["router"].astype(dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, experts = jax.lax.top_k(probs, K)
        gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
        cap = max(1, int(T * K * cfg.capacity_factor / E))
        cap = min(cap, T)
        if cap >= 128:
            cap = ((cap + 127) // 128) * 128
        flat_e = experts.reshape(T * K)
        order = jnp.argsort(flat_e)
        sorted_e = flat_e[order]
        counts = jnp.bincount(sorted_e, length=E)
        starts = jnp.cumsum(counts) - counts
        slot = jnp.arange(T * K) - starts[sorted_e]
        keep = slot < cap
        token_of = order // K
        buf_idx = jnp.where(keep, sorted_e * cap + slot, E * cap)
        buf = jnp.zeros((E * cap + 1, D), dtype)
        buf = buf.at[buf_idx].add(xf[token_of].astype(dtype))
        ebuf = buf[:E * cap].reshape(E, cap, D)
        ebuf = logical_constraint(ebuf, ("expert", "fsdp", None))
        out_buf = _expert_ffn(p, ebuf, cfg.mlp_type, dtype)
        out_buf = logical_constraint(out_buf, ("expert", "fsdp", None))
        out_flat = jnp.concatenate(
            [out_buf.reshape(E * cap, D), jnp.zeros((1, D), dtype)])
        gathered = out_flat[buf_idx]
        w = (gates.reshape(T * K)[order] * keep).astype(dtype)
        y = jnp.zeros((T, D), dtype).at[token_of].add(gathered * w[:, None])
        if cfg.shared_experts:
            y = y + mlp_apply(p["shared"], xf, cfg.mlp_type, dtype)
        return y.reshape(B, S, D)

    # eager single-device oracles (both formulations agree off-mesh)
    oracle_over = np.asarray(moe_sort_overflow(p, x, cfg, jnp.float32))
    oracle_cur = np.asarray(moe_apply(p, x, cfg, jnp.float32, impl="sort"))
    assert np.allclose(oracle_over, oracle_cur, atol=1e-5), \\
        "formulations diverge even off-mesh: test is broken"

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with use_policy(mesh, ShardingPolicy()):
        got_over = np.asarray(jax.jit(
            lambda p, x: moe_sort_overflow(p, x, cfg, jnp.float32))(p, x))
        got_cur = np.asarray(jax.jit(
            lambda p, x: moe_apply(p, x, cfg, jnp.float32,
                                   impl="sort"))(p, x))
    print("FIXED_IMPL", "MATCH" if np.allclose(got_cur, oracle_cur,
                                               atol=1e-5) else "MISCOMPILE")
    print("OVERFLOW_IMPL", "MATCH" if np.allclose(got_over, oracle_over,
                                                  atol=1e-5)
          else "MISCOMPILE")
""")


def test_jax_spmd_padded_gather_miscompile_still_present():
    """jax 0.4.37 pin: the overflow-row MoE dispatch must still
    miscompile under CPU SPMD (and the workaround impl must not)."""
    out = subprocess.run(
        [sys.executable, "-c", _PROG], capture_output=True, text=True,
        timeout=300,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=pathlib.Path(__file__).parent.parent)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FIXED_IMPL MATCH" in out.stdout, (
        "the workaround _moe_sort impl no longer matches its oracle "
        "under SPMD — a real regression:\n" + out.stdout)
    assert "OVERFLOW_IMPL MISCOMPILE" in out.stdout, (
        "the (E*cap + 1)-row overflow gather now MATCHES under CPU "
        "SPMD: jax has fixed the padded-gather partitioning bug this "
        "pin tracks. Drop the workaround in models/moe.py _moe_sort "
        "(restore the simpler overflow-row dispatch if preferred) and "
        "the ROADMAP note, then delete this test.\n" + out.stdout)
