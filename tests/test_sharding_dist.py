"""Sharding rules translation + multi-(host-)device distributed tests.

Multi-device tests run in subprocesses with XLA_FLAGS device-count
overrides so the main pytest process keeps its single CPU device."""
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.sharding.rules import DEFAULT_RULES, ShardingPolicy, logical_to_pspec


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_logical_to_pspec_basic():
    mesh = FakeMesh({"data": 16, "model": 16})
    pol = ShardingPolicy()
    p = logical_to_pspec(("fsdp", "tp"), (1024, 4096), mesh, pol)
    assert p == jax.sharding.PartitionSpec("data", "model")


def test_non_divisible_dim_dropped():
    mesh = FakeMesh({"data": 16, "model": 16})
    pol = ShardingPolicy()
    # 15 heads on 16-way model axis: constraint dropped (smollm case)
    p = logical_to_pspec(("batch", None, "heads", None), (256, 32, 15, 64),
                         mesh, pol)
    assert p == jax.sharding.PartitionSpec("data")
    # kv_heads=8 not divisible by 16 either
    p2 = logical_to_pspec((None, "kv_heads"), (10, 8), mesh, pol)
    assert p2 == jax.sharding.PartitionSpec()


def test_pod_axis_filtered_on_single_pod():
    mesh = FakeMesh({"data": 16, "model": 16})
    p = logical_to_pspec(("batch", None), (256, 128), mesh, ShardingPolicy())
    assert p == jax.sharding.PartitionSpec("data")
    mesh2 = FakeMesh({"pod": 2, "data": 16, "model": 16})
    p2 = logical_to_pspec(("batch", None), (256, 128), mesh2, ShardingPolicy())
    assert p2 == jax.sharding.PartitionSpec(("pod", "data"))


def test_policy_override():
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    pol = ShardingPolicy().with_rules("fsdp_pods", fsdp=("pod", "data"))
    p = logical_to_pspec(("fsdp",), (64,), mesh, pol)
    assert p == jax.sharding.PartitionSpec(("pod", "data"))


def _run_subprocess(code: str, devices: int = 8) -> str:
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
        {textwrap.indent(textwrap.dedent(code), '        ').strip()}
    """)
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=300,
                         env={**__import__('os').environ,
                              "PYTHONPATH": "src"},
                         cwd=__import__('pathlib').Path(__file__).parent.parent)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_compressed_psum_matches_exact():
    stdout = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.sharding.collectives import compressed_psum

        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        x = jax.random.normal(jax.random.key(0), (8, 1024))

        exact = jnp.mean(x, axis=0)
        f = jax.shard_map(lambda xs: compressed_psum(xs[0], "data"),
                          mesh=mesh, in_specs=P("data", None), out_specs=P())
        approx = f(x)
        err = float(jnp.max(jnp.abs(exact - approx)))
        rel = err / float(jnp.max(jnp.abs(exact)) + 1e-9)
        print("REL", rel)
        assert rel < 0.02, rel
    """)
    assert "REL" in stdout


def test_small_mesh_train_step_shards():
    """A 2x2 (data, model) mesh end-to-end train step with real sharded
    params on 4 host devices; loss finite and params update."""
    _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import build_model
        from repro.models.lm import RunFlags
        from repro.sharding.constrain import use_policy
        from repro.sharding.rules import ShardingPolicy, specs_to_shardings
        from repro.train.optimizer import OptConfig, init_opt_state, opt_state_specs
        from repro.train.step import make_train_step, init_train_state

        cfg = get_config("tinyllama-1.1b").reduced(num_layers=2, d_model=64,
                                                   num_heads=4, num_kv_heads=2,
                                                   d_ff=128, vocab_size=256)
        mesh = jax.make_mesh((2, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        policy = ShardingPolicy()
        model = build_model(cfg, RunFlags())
        opt = OptConfig()
        with use_policy(mesh, policy):
            state = init_train_state(model, jax.random.key(0), opt)
            pshapes = jax.eval_shape(lambda: state["params"])
            pspecs = model.param_specs()
            psh = specs_to_shardings(pspecs, pshapes, mesh, policy)
            state = {"params": jax.device_put(state["params"], psh),
                     "opt": state["opt"]}
            step = jax.jit(make_train_step(model, opt), donate_argnums=(0,))
            batch = {"tokens": jnp.ones((4, 16), jnp.int32),
                     "labels": jnp.ones((4, 16), jnp.int32)}
            state, metrics = step(state, batch)
            state, metrics = step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        print("OK", float(metrics["loss"]))
    """, devices=4)


def test_sp_flash_matches_plain():
    """Sequence-parallel shard_map attention == single-device flash, on an
    arch whose head count doesn't divide the model axis (arctic: 56/4!=int
    in the reduced config we force heads=6 over model=4)."""
    _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import build_model
        from repro.sharding.constrain import use_policy
        from repro.sharding.rules import ShardingPolicy

        cfg = get_config("arctic-480b").reduced(
            num_heads=6, num_kv_heads=2, head_dim=16, d_model=96, d_ff=128,
            dense_d_ff=128)
        m = build_model(cfg)
        params = m.init(jax.random.key(0))
        batch = {"tokens": jax.random.randint(jax.random.key(1), (4, 32), 0,
                                              cfg.vocab_size),
                 "labels": jax.random.randint(jax.random.key(1), (4, 32), 0,
                                              cfg.vocab_size)}
        plain = float(m.loss(params, batch))
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        pol = ShardingPolicy().with_rules("sp", seq=("model",))
        with use_policy(mesh, pol):
            sp = float(jax.jit(lambda p, b: m.loss(p, b))(params, batch))
        assert abs(plain - sp) < 2e-3, (plain, sp)
        print("OK", plain, sp)
    """, devices=8)


def test_moe_shard_map_grad_matches_sort():
    """EP all-to-all dispatch: loss AND grads match the sort impl."""
    _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models.moe import moe_apply, moe_init
        from repro.sharding.constrain import use_policy
        from repro.sharding.rules import ShardingPolicy

        cfg = get_config("kimi-k2-1t-a32b").reduced(
            num_experts=8, experts_per_token=2, d_model=32, d_ff=64,
            capacity_factor=8.0, shared_experts=1, first_dense_layers=0)
        p, _ = moe_init(jax.random.key(0), "m", cfg)
        x = jax.random.normal(jax.random.key(1), (4, 8, 32), jnp.float32)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        pol = ShardingPolicy()
        with use_policy(mesh, pol):
            f_sort = jax.jit(lambda p: jnp.sum(
                jnp.sin(moe_apply(p, x, cfg, jnp.float32, impl="sort"))))
            f_sm = jax.jit(lambda p: jnp.sum(
                jnp.sin(moe_apply(p, x, cfg, jnp.float32, impl="shard_map"))))
            l1, g1 = jax.value_and_grad(f_sort)(p)
            l2, g2 = jax.value_and_grad(f_sm)(p)
        assert abs(float(l1) - float(l2)) < 1e-4
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-5)
        print("OK")
    """, devices=8)


def test_flash_vjp_grads_match_ad():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.attention import flash_attn, flash_attn_vjp
    q = jax.random.normal(jax.random.key(0), (2, 32, 4, 16))
    k = jax.random.normal(jax.random.key(1), (2, 32, 2, 16))
    v = jax.random.normal(jax.random.key(2), (2, 32, 2, 16))
    f1 = lambda *a: jnp.sum(jnp.sin(flash_attn(*a, causal=True, q_block=8,
                                               kv_block=16)))
    f2 = lambda *a: jnp.sum(jnp.sin(flash_attn_vjp(*a, causal=True, q_block=8,
                                                   kv_block=16)))
    g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=2e-4)


def test_quantize_roundtrip_error_small():
    from repro.sharding.collectives import quantize_roundtrip
    x = jax.random.normal(jax.random.key(0), (4096,))
    y = quantize_roundtrip(x)
    rel = float(jax.numpy.max(jax.numpy.abs(x - y))) / float(jax.numpy.max(jax.numpy.abs(x)))
    assert rel < 0.02


def test_error_feedback_convergence():
    """EF-compressed SGD reaches the same optimum on a quadratic."""
    import jax.numpy as jnp
    from repro.sharding.collectives import ef_correct, quantize_roundtrip
    key = jax.random.key(0)
    A = jax.random.normal(key, (32, 16))
    target = jax.random.normal(jax.random.key(1), (16,))
    b = A @ target

    def loss(w):
        return jnp.mean((A @ w - b) ** 2)

    w = jnp.zeros(16)
    err = jnp.zeros(16)
    for _ in range(300):
        g = jax.grad(loss)(w)
        corrected, new_err_fn = ef_correct(g, err)
        transmitted = quantize_roundtrip(corrected)
        err = new_err_fn(transmitted)
        w = w - 0.05 * transmitted
    assert float(loss(w)) < 1e-3
