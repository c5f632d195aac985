"""Decoder-only LM assembly for all non-enc-dec families.

Layers are grouped into homogeneous *superblocks* scanned with ``lax.scan``
(stacked params) to keep HLO size and compile time flat in depth:
dense/moe = 1-sublayer group, xlstm = (mlstm, slstm) pairs, jamba = the
period-8 attn/mamba/MoE pattern, granite the period-10 attn/mamba2/MoE one.
Remat policy applies per scanned body. Parameters are made in
``cfg.param_dtype``; a replica that holds a share of the experts states,
per leaf, which slice of the published image it holds (``param_share``).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models.blocks import (
    SubDef,
    decode_state_specs,
    sublayer_apply,
    sublayer_decode_state,
    sublayer_init,
)
from repro.models.common import (
    apply_norm,
    chunked_softmax_xent,
    embed_init,
    embed_lookup,
    norm_init,
    pad_vocab,
    softmax_xent,
    stable_fold,
)
from repro.sharding.constrain import logical_constraint


@dataclass(frozen=True)
class RunFlags:
    """Lowering-relevant knobs; the §Perf variants toggle these."""
    dtype: str = "bfloat16"
    remat: str = "full"              # full | dots | none
    skip_masked_blocks: bool = False  # causal flash: skip dead kv blocks
    chunked_loss: int = 0            # 0 = dense logits; else seq-chunk size
    flash_vjp: bool = False          # custom-VJP flash attention backward
    moe_impl: str = "sort"           # sort | shard_map (EP all-to-all)


def layout(cfg: ModelConfig) -> list[tuple[int, list[SubDef]]]:
    if cfg.family in ("dense", "vlm"):
        return [(cfg.num_layers, [SubDef("attn", "dense")])]
    if cfg.family == "moe":
        groups = []
        if cfg.first_dense_layers:
            groups.append((cfg.first_dense_layers,
                           [SubDef("attn", "dense", cfg.dense_d_ff)]))
        groups.append((cfg.num_layers - cfg.first_dense_layers,
                       [SubDef("attn", "moe")]))
        return groups
    if cfg.family == "ssm" and cfg.ssm_type == "xlstm":
        return [(cfg.num_layers // 2, [SubDef("mlstm", "none"),
                                       SubDef("slstm", "none")])]
    if cfg.family == "hybrid":
        subs = []
        for i in range(cfg.attn_period):
            mixer = "attn" if i == cfg.attn_offset else cfg.ssm_type
            ffn = "moe" if (cfg.num_experts and i % cfg.moe_every == cfg.moe_offset) else "dense"
            subs.append(SubDef(mixer, ffn, cfg.dense_d_ff if ffn == "dense" else 0))
        return [(cfg.num_layers // cfg.attn_period, subs)]
    raise ValueError(f"no layout for family {cfg.family}")


class DecoderLM:
    def __init__(self, cfg: ModelConfig, flags: RunFlags = RunFlags()):
        self.cfg = cfg
        self.flags = flags
        self.layout = layout(cfg)
        self._specs = None

    # ------------------------------------------------------------- params
    def _build(self, key):
        cfg = self.cfg
        params, specs = {}, {}
        params["embed"], specs["embed"] = embed_init(key, "embed", cfg.vocab_size, cfg.d_model)
        if not cfg.tie_embeddings:
            params["unembed"], specs["unembed"] = embed_init(key, "unembed", cfg.vocab_size, cfg.d_model)
        for gi, (R, subs) in enumerate(self.layout):
            gp, gs = self._stack_group(key, gi, subs, R)
            params[f"g{gi}"] = gp
            specs[f"g{gi}"] = gs
        params["final_norm"], specs["final_norm"] = norm_init(cfg.d_model, cfg.norm_type)
        self._specs = specs
        if cfg.param_dtype != "float32":
            params = jax.tree.map(lambda a: a.astype(cfg.param_dtype), params)
        return params

    def _stack_group(self, key, gi: int, subs, repeats: int):
        cfg = self.cfg

        def one(k):
            p = {}
            for j, sd in enumerate(subs):
                pj, _ = sublayer_init(k, f"g{gi}.s{j}", cfg, sd)
                p[f"s{j}"] = pj
            return p

        keys = jax.random.split(stable_fold(key, f"group{gi}"), repeats)
        gp = jax.vmap(one)(keys)
        gs = {}
        for j, sd in enumerate(subs):
            _, sj = sublayer_init(keys[0], f"g{gi}.s{j}", cfg, sd)
            gs[f"s{j}"] = jax.tree.map(
                lambda ax: (None,) + tuple(ax), sj,
                is_leaf=lambda x: isinstance(x, tuple) and all(
                    isinstance(a, (str, type(None))) for a in x))
        return gp, gs

    def init(self, key):
        return self._build(key)

    def param_specs(self):
        if self._specs is None:
            jax.eval_shape(self._build, jax.random.key(0))
        return self._specs

    def param_shapes(self):
        return jax.eval_shape(self.init, jax.random.key(0))

    def param_share(self) -> dict | None:
        """This replica's share of the published image, leaf by leaf:
        {path: per-dim (start, stop) in the image's tensor, or None where
        the leaf is held whole}. The expert axis is the one the specs
        name "expert"; None when every expert is held."""
        lo, hi = self.cfg.held_experts
        if (lo, hi) == (0, self.cfg.num_experts):
            return None
        shapes = jax.tree_util.tree_flatten_with_path(self.param_shapes())[0]
        axes = jax.tree.leaves(self.param_specs(), is_leaf=lambda x: isinstance(
            x, tuple) and all(isinstance(a, (str, type(None))) for a in x))
        share = {}
        for (path, leaf), ax in zip(shapes, axes):
            name = "/".join(str(k.key) for k in path)
            share[name] = None if "expert" not in ax else [
                (lo, hi) if a == "expert" else (0, n)
                for a, n in zip(ax, leaf.shape)]
        return share

    # ------------------------------------------------------------- shared
    def _maybe_remat(self, body):
        if self.flags.remat == "full":
            return jax.checkpoint(body)
        if self.flags.remat == "dots":
            return jax.checkpoint(
                body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        return body

    def _run_groups_stateless(self, params, x, positions, dtype, mode="train"):
        cfg = self.cfg
        for gi, (R, subs) in enumerate(self.layout):
            def body(carry, p_l, _subs=subs):
                h = carry
                for j, sd in enumerate(_subs):
                    h, _ = sublayer_apply(
                        p_l[f"s{j}"], h, cfg, sd, dtype, mode=mode,
                        positions=positions,
                        skip_blocks=self.flags.skip_masked_blocks,
                        flash_vjp=self.flags.flash_vjp,
                        moe_impl=self.flags.moe_impl)
                return h, None
            x, _ = jax.lax.scan(self._maybe_remat(body), x, params[f"g{gi}"])
            x = logical_constraint(x, ("batch", "seq", None))
        return x

    def _run_groups_state(self, params, x, dtype, mode, states, positions=None,
                          pos=None):
        cfg = self.cfg
        new_states = {}
        for gi, (R, subs) in enumerate(self.layout):
            def body(carry, xs, _subs=subs):
                h = carry
                p_l, st_l = xs
                new_st = {}
                for j, sd in enumerate(_subs):
                    h, ns = sublayer_apply(
                        p_l[f"s{j}"], h, cfg, sd, dtype, mode=mode,
                        positions=positions, pos=pos, state=st_l[f"s{j}"],
                        skip_blocks=self.flags.skip_masked_blocks,
                        flash_vjp=False if mode != "train" else self.flags.flash_vjp,
                        moe_impl=self.flags.moe_impl)
                    new_st[f"s{j}"] = ns
                return h, new_st
            x, new_g = jax.lax.scan(body, x, (params[f"g{gi}"], states[f"g{gi}"]))
            new_states[f"g{gi}"] = new_g
        return x, new_states

    # -------------------------------------------------------------- embed
    def _embed(self, params, batch, dtype):
        cfg = self.cfg
        x = self._scale_embed(
            embed_lookup(params["embed"], batch["tokens"], dtype))
        if cfg.family == "vlm":
            x = jnp.concatenate([batch["patches"].astype(dtype), x], axis=1)
        x = logical_constraint(x, ("batch", "seq", None))
        return x

    def _scale_embed(self, x):
        m = self.cfg.embedding_multiplier
        return x if m == 1.0 else x * m

    def _logits(self, params, x, dtype):
        table = params["embed"] if self.cfg.tie_embeddings else params["unembed"]
        logits = x @ table.T.astype(dtype)
        s = self.cfg.logits_scaling
        return logits if s == 1.0 else logits / s

    # --------------------------------------------------------------- train
    def loss(self, params, batch):
        cfg, flags = self.cfg, self.flags
        dtype = jnp.dtype(flags.dtype)
        x = self._embed(params, batch, dtype)
        S = x.shape[1]
        positions = jnp.arange(S)
        x = self._run_groups_stateless(params, x, positions, dtype)
        x = apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
        if cfg.family == "vlm":
            x = x[:, cfg.num_patches:]
        labels = batch["labels"]
        table = params["embed"] if cfg.tie_embeddings else params["unembed"]
        if flags.chunked_loss:
            if cfg.logits_scaling != 1.0:
                x = x / cfg.logits_scaling
            return chunked_softmax_xent(x, table.astype(dtype), labels,
                                        cfg.vocab_size, flags.chunked_loss)
        logits = self._logits(params, x, dtype)
        logits = logical_constraint(logits, ("batch", "seq", "vocab"))
        return softmax_xent(logits, labels, cfg.vocab_size)

    # --------------------------------------------------------------- serve
    def init_decode_state(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        states = {}
        for gi, (R, subs) in enumerate(self.layout):
            g = {}
            for j, sd in enumerate(subs):
                single = sublayer_decode_state(self.cfg, sd, batch, max_len, dtype)
                g[f"s{j}"] = jax.tree.map(
                    lambda a: jnp.zeros((R,) + a.shape, a.dtype), single)
            states[f"g{gi}"] = g
        return states

    def decode_state_spec_tree(self):
        tree = {}
        for gi, (R, subs) in enumerate(self.layout):
            g = {}
            for j, sd in enumerate(subs):
                sp = decode_state_specs(sd)
                g[f"s{j}"] = jax.tree.map(
                    lambda ax: (None,) + tuple(ax), sp,
                    is_leaf=lambda x: isinstance(x, tuple) and all(
                        isinstance(a, (str, type(None))) for a in x))
            tree[f"g{gi}"] = g
        return tree

    def prefill(self, params, batch, state):
        """Full-sequence forward that fills the decode state.

        Returns (last-position logits, new state)."""
        cfg, flags = self.cfg, self.flags
        dtype = jnp.dtype(flags.dtype)
        x = self._embed(params, batch, dtype)
        S = x.shape[1]
        positions = jnp.arange(S)
        x, new_states = self._run_groups_state(
            params, x, dtype, "prefill", state, positions=positions)
        x = apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
        logits = self._logits(params, x[:, -1], dtype)
        return logits, new_states

    def decode_step(self, params, state, tokens, pos):
        """tokens: (B,) int32; pos: (B,) positions being written."""
        cfg, flags = self.cfg, self.flags
        dtype = jnp.dtype(flags.dtype)
        x = self._scale_embed(
            embed_lookup(params["embed"], tokens, dtype))       # (B, D)
        x, new_states = self._run_groups_state(
            params, x, dtype, "decode", state, pos=pos)
        x = apply_norm(params["final_norm"], x, cfg.norm_type, cfg.norm_eps)
        logits = self._logits(params, x, dtype)
        return logits, new_states

    # --------------------------------------------------------------- specs
    def input_specs(self, shape: ShapeConfig):
        """ShapeDtypeStructs for every model input of this cell (no alloc)."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct
        if shape.kind == "train":
            if cfg.family == "vlm":
                P = cfg.num_patches
                return {"tokens": sds((B, S - P), i32),
                        "patches": sds((B, P, cfg.d_model), jnp.bfloat16),
                        "labels": sds((B, S - P), i32)}
            return {"tokens": sds((B, S), i32), "labels": sds((B, S), i32)}
        if shape.kind == "prefill":
            if cfg.family == "vlm":
                P = cfg.num_patches
                return {"tokens": sds((B, S - P), i32),
                        "patches": sds((B, P, cfg.d_model), jnp.bfloat16)}
            return {"tokens": sds((B, S), i32)}
        # decode: one token per sequence; KV/state of length S
        return {"tokens": sds((B,), i32), "pos": sds((B,), i32)}

    def input_logical_specs(self, shape: ShapeConfig):
        if shape.kind == "train":
            if self.cfg.family == "vlm":
                return {"tokens": ("batch", None), "patches": ("batch", None, None),
                        "labels": ("batch", None)}
            return {"tokens": ("batch", None), "labels": ("batch", None)}
        if shape.kind == "prefill":
            if self.cfg.family == "vlm":
                return {"tokens": ("batch", None), "patches": ("batch", None, None)}
            return {"tokens": ("batch", None)}
        return {"tokens": ("batch",), "pos": ("batch",)}
