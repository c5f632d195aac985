"""Mixture-of-Experts layer: top-k routing with sort-based capacity dispatch.

The dispatch is the sorted/scatter formulation (MaxText-style): assignments
are sorted by expert, each token takes a slot in its expert's capacity
buffer, the expert FFN runs as one batched einsum over (E, C, d), and
results scatter-add back with router gates. Everything is jit-able and
shards: the (E, C, d) buffer carries the ("expert", "fsdp", None) logical
spec so experts land on the `model` mesh axis (EP) and capacity on `data` —
the token->expert exchange lowers to the all-to-all family under SPMD.

Supports Arctic's dense-residual MoE and Kimi/DeepSeek shared experts.

Expert parallelism without the exchange: a replica told that it holds
experts ``cfg.held_experts`` = [lo, hi) keeps only their weights, routes
every token over all ``num_experts`` with top-k, and adds the gated
outputs of its own experts (plus the shared expert, which every replica
holds whole). Assignments to other experts are not this replica's part
of the result; they are never capacity drops. On the decode path
(``dropless``) each held expert has a slot for every token, so no token
is dropped.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import dense_init
from repro.models.mlp import mlp_apply, mlp_init
from repro.sharding.constrain import logical_constraint


def moe_init(key, prefix: str, cfg: ModelConfig):
    lo, hi = cfg.held_experts
    E, D, F = hi - lo, cfg.d_model, cfg.d_ff
    p, s = {}, {}
    p["router"], s["router"] = dense_init(key, f"{prefix}.router", D,
                                          cfg.num_experts, "fsdp", None)
    fold = lambda nm: f"{prefix}.{nm}"

    def expert_stack(nm, a, b, in_ax, out_ax):
        w, _ = dense_init(key, fold(nm), a, b * E, in_ax, out_ax)
        w = w.reshape(a, E, b).transpose(1, 0, 2)
        return w, ("expert", in_ax, out_ax)

    if cfg.mlp_type == "swiglu":
        p["w_gate"], s["w_gate"] = expert_stack("w_gate", D, F, "fsdp", "tp_inner")
        p["w_up"], s["w_up"] = expert_stack("w_up", D, F, "fsdp", "tp_inner")
    else:
        p["w_up"], s["w_up"] = expert_stack("w_up", D, F, "fsdp", "tp_inner")
    p["w_down"], s["w_down"] = expert_stack("w_down", F, D, "tp_inner", "fsdp")

    if cfg.shared_experts:
        p["shared"], s["shared"] = mlp_init(
            key, fold("shared"), D, F * cfg.shared_experts, cfg.mlp_type)
    if cfg.dense_residual:
        p["residual"], s["residual"] = mlp_init(
            key, fold("residual"), D, cfg.dense_d_ff, cfg.mlp_type)
    return p, s


def _expert_ffn(p, x: jnp.ndarray, kind: str, dtype) -> jnp.ndarray:
    """x: (E, C, D) -> (E, C, D), batched over experts."""
    if kind == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", x, p["w_gate"].astype(dtype))
        u = jnp.einsum("ecd,edf->ecf", x, p["w_up"].astype(dtype))
        h = jax.nn.silu(g) * u
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", x, p["w_up"].astype(dtype)))
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(dtype))


def route(logits: jnp.ndarray, k: int) -> tuple:
    """Top-k over the router's logits, softmax over those k (the same
    gates as top-k of the full softmax, renormalized). Returns
    (gates, experts), each (T, k)."""
    top, experts = jax.lax.top_k(logits, k)
    return jax.nn.softmax(top, axis=-1), experts


def moe_apply(p, x: jnp.ndarray, cfg: ModelConfig, dtype,
              impl: str = "sort", dropless: bool = False) -> jnp.ndarray:
    """x: (B, S, D) -> (B, S, D). impl: 'sort' (global sort+scatter under
    SPMD) or 'shard_map' (explicit EP all-to-all; §Perf lever), which
    needs every expert held. `dropless`: a slot for every token."""
    if impl == "shard_map" and x.ndim == 3 and cfg.ep_size == 1:
        from repro.sharding.constrain import active_policy
        act = active_policy()
        if act is not None:
            mesh, policy = act
            ep_axes = tuple(a for a in policy.rules.get("expert", ())
                            if a in mesh.shape)
            ep = 1
            for a in ep_axes:
                ep *= mesh.shape[a]
            if ep > 1 and cfg.num_experts % ep == 0:
                return _moe_shard_map(p, x, cfg, dtype, mesh, policy, ep_axes)
    return _moe_sort(p, x, cfg, dtype, dropless)


def _moe_shard_map(p, x, cfg: ModelConfig, dtype, mesh, policy, ep_axes):
    """Expert-parallel MoE with explicit all-to-all dispatch.

    Per (data-parallel) shard: local top-k routing, one local sort into an
    (E, C, d) send buffer, ``all_to_all`` over the EP axis (split experts /
    concat sources), batched expert FFN on local experts, reverse
    all_to_all, weighted scatter back. Collective volume per layer is
    O(tokens/dp * k * d) instead of the SPMD global-sort fallback's
    all-gathers — the MoE hillclimb lever (§Perf).
    """
    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    ep_axis = ep_axes if len(ep_axes) > 1 else ep_axes[0]
    ep = 1
    for a in ep_axes:
        ep *= mesh.shape[a]
    dp_axes = tuple(a for a in policy.rules.get("batch", ())
                    if a in mesh.shape)
    dp = 1
    for a in dp_axes:
        dp *= mesh.shape[a]
    if B % max(dp, 1):
        return _moe_sort(p, x, cfg, dtype)
    # when EP uses a mesh axis that doesn't carry batch (Megatron-style TP),
    # split the sequence across EP ranks inside the shard_map so routing and
    # dispatch aren't replicated ep-fold (the output comes back
    # sequence-sharded — sequence parallelism for the MoE block).
    seq_split = (len(ep_axes) == 1 and ep_axes[0] not in dp_axes
                 and S % ep == 0 and (B // max(dp, 1)) * (S // ep) > 0)
    T_loc = (B // max(dp, 1)) * (S // ep if seq_split else S)
    cap = max(1, int(T_loc * K * cfg.capacity_factor / E))

    def local(xs, router, wg, wu, wd):
        if seq_split:
            ridx = jax.lax.axis_index(ep_axes[0])
            xs = jax.lax.dynamic_slice_in_dim(
                xs, ridx * (xs.shape[1] // ep), xs.shape[1] // ep, axis=1)
        Bl, Sl, _ = xs.shape
        T = Bl * Sl
        xf = xs.reshape(T, D)
        logits = (xf @ router.astype(dtype)).astype(jnp.float32)
        gates, experts = route(logits, K)

        flat_e = experts.reshape(T * K)
        order = jnp.argsort(flat_e)
        sorted_e = flat_e[order]
        counts = jnp.bincount(sorted_e, length=E)
        starts = jnp.cumsum(counts) - counts
        slot = jnp.arange(T * K) - starts[sorted_e]
        keep = slot < cap
        token_of = order // K
        buf_idx = jnp.where(keep, sorted_e * cap + slot, E * cap)
        send = jnp.zeros((E * cap + 1, D), dtype)
        send = send.at[buf_idx].add(xf[token_of].astype(dtype), mode="drop")
        send = send[: E * cap].reshape(E, cap, D)

        # dispatch: split experts across EP ranks, concat source ranks
        recv = jax.lax.all_to_all(send, ep_axis, split_axis=0, concat_axis=1,
                                  tiled=True)                  # (E/ep, ep*cap, D)
        h = _expert_ffn({"w_gate": wg, "w_up": wu, "w_down": wd}
                        if cfg.mlp_type == "swiglu" else
                        {"w_up": wu, "w_down": wd}, recv, cfg.mlp_type, dtype)
        back = jax.lax.all_to_all(h, ep_axis, split_axis=1, concat_axis=0,
                                  tiled=True)                  # (E, cap, D)

        out_flat = jnp.concatenate(
            [back.reshape(E * cap, D), jnp.zeros((1, D), dtype)], axis=0)
        gathered = out_flat[buf_idx]
        w = (gates.reshape(T * K)[order] * keep).astype(dtype)
        y = jnp.zeros((T, D), dtype).at[token_of].add(gathered * w[:, None])
        return y.reshape(Bl, Sl, D)

    batch_spec = dp_axes if len(dp_axes) > 1 else (dp_axes[0] if dp_axes else None)
    wu = p["w_up"]
    wd = p["w_down"]
    wg = p.get("w_gate")
    if wg is None:
        wg = wu  # placeholder with identical sharding; unused for gelu
    ep_spec = ep_axis
    out_spec = P(batch_spec, ep_spec, None) if seq_split \
        else P(batch_spec, None, None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(batch_spec, None, None), P(None, None),
                  P(ep_spec, None, None), P(ep_spec, None, None),
                  P(ep_spec, None, None)),
        out_specs=out_spec,
        check_vma=False)
    y = fn(x, p["router"], wg, wu, wd)

    if cfg.shared_experts:
        y = y + mlp_apply(p["shared"], x.reshape(-1, D), cfg.mlp_type,
                          dtype).reshape(B, S, D)
    if cfg.dense_residual:
        y = y + mlp_apply(p["residual"], x.reshape(-1, D), cfg.mlp_type,
                          dtype).reshape(B, S, D)
    return y


def _moe_sort(p, x: jnp.ndarray, cfg: ModelConfig, dtype,
              dropless: bool = False) -> jnp.ndarray:
    """x: (B, S, D) -> (B, S, D): the held experts' part plus the shared."""
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    lo, hi = cfg.held_experts
    T = B * S
    xf = x.reshape(T, D)

    logits = (xf @ p["router"].astype(dtype)).astype(jnp.float32)   # (T, E)
    gates, experts = route(logits, K)                               # (T, K)

    # capacity per expert, rounded to 128 so the (E, C, D) buffer stays
    # shardable on the data axis (TPU-aligned tile too); a token takes at
    # most one slot of an expert, so T slots drop nothing
    cap = T if dropless else max(1, int(T * K * cfg.capacity_factor / E))
    cap = min(cap, T)
    if cap >= 128:
        cap = ((cap + 127) // 128) * 128

    # held experts by local index; others sort last, as index E
    E = hi - lo
    flat_e = experts.reshape(T * K) - lo
    flat_e = jnp.where((flat_e >= 0) & (flat_e < E), flat_e, E)
    order = jnp.argsort(flat_e)                                     # stable
    sorted_e = flat_e[order]
    # slot of each sorted assignment within its expert
    counts = jnp.bincount(sorted_e, length=E + 1)
    starts = jnp.cumsum(counts) - counts
    slot = jnp.arange(T * K) - starts[sorted_e]
    keep = (slot < cap) & (sorted_e < E)
    token_of = order // K

    # dispatch: (E*cap, D) buffer. Dropped assignments scatter to index
    # E*cap, which is out of bounds and discarded by mode="drop" — no
    # overflow row. Keeping the buffer exactly E*cap matters under SPMD:
    # a (E*cap + 1)-row operand doesn't divide the mesh axes, and XLA's
    # padded-gather partitioning returns wrong values for it (observed on
    # CPU SPMD, jax 0.4.37), which broke this path vs the shard_map impl.
    buf_idx = jnp.where(keep, sorted_e * cap + slot, E * cap)
    buf = jnp.zeros((E * cap, D), dtype)
    buf = buf.at[buf_idx].add(xf[token_of].astype(dtype), mode="drop")
    ebuf = buf.reshape(E, cap, D)
    ebuf = logical_constraint(ebuf, ("expert", "fsdp", None))

    out_buf = _expert_ffn(p, ebuf, cfg.mlp_type, dtype)
    out_buf = logical_constraint(out_buf, ("expert", "fsdp", None))
    out_flat = out_buf.reshape(E * cap, D)

    # dropped slots gather row 0 but are zero-weighted via `keep` below
    gathered = out_flat[jnp.where(keep, buf_idx, 0)]                # (T*K, D)
    w = (gates.reshape(T * K)[order] * keep).astype(dtype)
    y = jnp.zeros((T, D), dtype).at[token_of].add(gathered * w[:, None])

    if cfg.shared_experts:
        y = y + mlp_apply(p["shared"], xf, cfg.mlp_type, dtype)
    if cfg.dense_residual:
        y = y + mlp_apply(p["residual"], xf, cfg.mlp_type, dtype)
    return y.reshape(B, S, D)


def aux_load_balance_loss(logits: jnp.ndarray, experts: jnp.ndarray,
                          num_experts: int, k: int) -> jnp.ndarray:
    """Switch-style load-balancing auxiliary loss (mean over tokens)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    me = jnp.mean(probs, axis=0)                                    # (E,)
    ce = jnp.mean(
        jax.nn.one_hot(experts, num_experts).sum(axis=1), axis=0) / k
    return num_experts * jnp.sum(me * ce)
