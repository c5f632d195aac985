"""Sublayer = (norm -> mixer -> residual) [+ (norm -> ffn -> residual)].

Mixers: attn | cross_attn | mamba | mamba2 | mlstm | slstm. FFNs: dense |
moe | none. Each sublayer's output joins the residual stream scaled by
``cfg.residual_multiplier`` (granite; 1.0 elsewhere).
One ``sublayer_apply`` covers train/encode/prefill/decode so every
architecture family assembles from the same parts (see ``lm.layout``).
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import mamba as mamba_mod
from repro.models import mamba2 as mamba2_mod
from repro.models import xlstm as xlstm_mod
from repro.models.common import apply_norm, apply_rope, dense_init, norm_init
from repro.models.mlp import mlp_apply, mlp_init
from repro.models.moe import moe_apply, moe_init
from repro.sharding.constrain import logical_constraint


class SubDef(NamedTuple):
    mixer: str          # attn | cross_attn | mamba | mamba2 | mlstm | slstm
    ffn: str            # dense | moe | none
    d_ff: int = 0       # 0 -> cfg.d_ff
    causal: bool = True


# ------------------------------------------------------------------ attention

def _attn_init(key, prefix: str, cfg: ModelConfig):
    D, Hhd, KVhd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    p, s = {}, {}
    p["wq"], s["wq"] = dense_init(key, f"{prefix}.wq", D, Hhd, "fsdp", "heads")
    p["wk"], s["wk"] = dense_init(key, f"{prefix}.wk", D, KVhd, "fsdp", "kv_heads")
    p["wv"], s["wv"] = dense_init(key, f"{prefix}.wv", D, KVhd, "fsdp", "kv_heads")
    p["wo"], s["wo"] = dense_init(key, f"{prefix}.wo", Hhd, D, "heads", "fsdp")
    if cfg.use_bias:
        for nm, dim in (("bq", Hhd), ("bk", KVhd), ("bv", KVhd), ("bo", D)):
            p[nm] = jnp.zeros((dim,), jnp.float32)
            s[nm] = (None,)
    return p, s


def _proj(p, x, nm, dtype, cfg):
    y = x @ p[f"w{nm}"].astype(dtype)
    if cfg.use_bias:
        y = y + p[f"b{nm}"].astype(dtype)
    return y


def _qkv(p, x, cfg: ModelConfig, dtype):
    B = x.shape[0]
    lead = x.shape[:-1]
    q = _proj(p, x, "q", dtype, cfg).reshape(lead + (cfg.num_heads, cfg.head_dim))
    k = _proj(p, x, "k", dtype, cfg).reshape(lead + (cfg.num_kv_heads, cfg.head_dim))
    v = _proj(p, x, "v", dtype, cfg).reshape(lead + (cfg.num_kv_heads, cfg.head_dim))
    if cfg.attention_multiplier:
        # scores scaled by attention_multiplier, not 1/sqrt(head_dim)
        q = q * (cfg.attention_multiplier * cfg.head_dim ** 0.5)
    return q, k, v


def _sp_flash(q, k, v, cfg, *, causal, use_vjp):
    """Sequence-parallel flash attention via shard_map: each model-axis
    rank computes its q-slice against (all-gathered) full K/V with the
    right causal offset. The lever for archs whose head count doesn't
    divide the TP axis (arctic 56, smollm 15): without it XLA replicates
    the whole attention across the model axis."""
    from jax.sharding import PartitionSpec as P
    from repro.sharding.constrain import active_policy

    act = active_policy()
    if act is None:
        return None
    mesh, policy = act
    seq_axes = tuple(a for a in policy.rules.get("seq", ()) if a in mesh.shape)
    if len(seq_axes) != 1:
        return None
    axis = seq_axes[0]
    n = mesh.shape[axis]
    B, S = q.shape[0], q.shape[1]
    if n <= 1 or S % n:
        return None
    b_axes = tuple(a for a in policy.rules.get("batch", ())
                   if a in mesh.shape and a != axis)
    bsz = 1
    for a in b_axes:
        bsz *= mesh.shape[a]
    bspec = (b_axes if len(b_axes) > 1 else b_axes[0]) \
        if (b_axes and B % bsz == 0) else None

    def local(ql, kf, vf):
        r = jax.lax.axis_index(axis)
        off = r * (S // n)
        if use_vjp:
            # custom-vjp path keeps offsets via explicit position shift
            return attn_mod.flash_attn(ql, kf, vf, causal=causal,
                                       q_offset=off,
                                       window=cfg.sliding_window)
        return attn_mod.flash_attn(ql, kf, vf, causal=causal, q_offset=off,
                                   window=cfg.sliding_window)

    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(P(bspec, axis, None, None),
                                 P(bspec, None, None, None),
                                 P(bspec, None, None, None)),
                       out_specs=P(bspec, axis, None, None),
                       check_vma=False)
    return fn(q, k, v)


def _attn_train(p, x, cfg: ModelConfig, dtype, positions, causal: bool,
                cache=None, skip_blocks: bool = False, use_vjp: bool = False):
    """x: (B,S,D). Returns (out, new_cache)."""
    B, S, D = x.shape
    q, k, v = _qkv(p, x, cfg, dtype)
    if cfg.pos_emb == "rope":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    o = _sp_flash(q, k, v, cfg, causal=causal, use_vjp=use_vjp)
    new_cache = None
    if cache is not None:
        kc = jnp.zeros_like(cache["k"]).at[:, :S].set(k.astype(cache["k"].dtype))
        vc = jnp.zeros_like(cache["v"]).at[:, :S].set(v.astype(cache["v"].dtype))
        new_cache = {"k": kc, "v": vc}
    if o is None:
        q = logical_constraint(q, ("batch", None, "heads", None))
        k = logical_constraint(k, ("batch", None, "kv_heads", None))
        if use_vjp:
            o = attn_mod.flash_attn_vjp(q, k, v, causal=causal,
                                        window=cfg.sliding_window)
        else:
            o = attn_mod.flash_attn(q, k, v, causal=causal,
                                    window=cfg.sliding_window,
                                    skip_masked_blocks=skip_blocks)
        o = logical_constraint(o, ("batch", None, "heads", None))
    out = _proj_out(p, o.reshape(B, S, cfg.q_dim), cfg, dtype)
    return out, new_cache


def _proj_out(p, o, cfg, dtype):
    y = o @ p["wo"].astype(dtype)
    if cfg.use_bias:
        y = y + p["bo"].astype(dtype)
    return y


def _attn_decode(p, x, cfg: ModelConfig, dtype, cache, pos):
    """x: (B,D); cache k/v: (B,Smax,Hkv,hd); pos: (B,) index of new token."""
    B, D = x.shape
    q, k, v = _qkv(p, x, cfg, dtype)               # (B,H,hd)/(B,Hkv,hd)
    if cfg.pos_emb == "rope":
        q = apply_rope(q[:, None], pos[:, None], cfg.rope_theta)[:, 0]
        k = apply_rope(k[:, None], pos[:, None], cfg.rope_theta)[:, 0]
    kc, vc = attn_mod.update_kv_cache(cache["k"], cache["v"], k, v, pos)
    o = attn_mod.decode_attn(q, kc, vc, pos + 1, window=cfg.sliding_window)
    return _proj_out(p, o.reshape(B, cfg.q_dim), cfg, dtype), {"k": kc, "v": vc}


def _cross_attn(p, x, cfg: ModelConfig, dtype, enc_kv):
    """Decoder cross-attention; enc_kv = dict(k,v) precomputed (B,Senc,Hkv,hd)."""
    lead = x.shape[:-1]
    q = _proj(p, x, "q", dtype, cfg).reshape(lead + (cfg.num_heads, cfg.head_dim))
    if x.ndim == 2:                                 # decode: (B,D)
        o = attn_mod.decode_attn(
            q, enc_kv["k"], enc_kv["v"],
            jnp.full((x.shape[0],), enc_kv["k"].shape[1], jnp.int32))
        return _proj_out(p, o.reshape(lead + (cfg.q_dim,)), cfg, dtype)
    o = attn_mod.flash_attn(q, enc_kv["k"], enc_kv["v"], causal=False)
    return _proj_out(p, o.reshape(lead + (cfg.q_dim,)), cfg, dtype)


def cross_kv(p, enc_out, cfg: ModelConfig, dtype):
    lead = enc_out.shape[:-1]
    k = _proj(p, enc_out, "k", dtype, cfg).reshape(lead + (cfg.num_kv_heads, cfg.head_dim))
    v = _proj(p, enc_out, "v", dtype, cfg).reshape(lead + (cfg.num_kv_heads, cfg.head_dim))
    return {"k": k, "v": v}


# ------------------------------------------------------------------ sublayer

def sublayer_init(key, prefix: str, cfg: ModelConfig, sd: SubDef):
    p, s = {}, {}
    p["norm1"], s["norm1"] = norm_init(cfg.d_model, cfg.norm_type)
    if sd.mixer in ("attn", "cross_attn"):
        p["mixer"], s["mixer"] = _attn_init(key, f"{prefix}.attn", cfg)
    elif sd.mixer == "mamba":
        p["mixer"], s["mixer"] = mamba_mod.mamba_init(key, f"{prefix}.mamba", cfg)
    elif sd.mixer == "mamba2":
        p["mixer"], s["mixer"] = mamba2_mod.mamba2_init(key, f"{prefix}.mamba2", cfg)
    elif sd.mixer == "mlstm":
        p["mixer"], s["mixer"] = xlstm_mod.mlstm_init(key, f"{prefix}.mlstm", cfg)
    elif sd.mixer == "slstm":
        p["mixer"], s["mixer"] = xlstm_mod.slstm_init(key, f"{prefix}.slstm", cfg)
    else:
        raise ValueError(sd.mixer)
    if sd.ffn != "none":
        p["norm2"], s["norm2"] = norm_init(cfg.d_model, cfg.norm_type)
        if sd.ffn == "moe":
            p["ffn"], s["ffn"] = moe_init(key, f"{prefix}.moe", cfg)
        else:
            d_ff = sd.d_ff or cfg.d_ff
            p["ffn"], s["ffn"] = mlp_init(key, f"{prefix}.mlp", cfg.d_model, d_ff, cfg.mlp_type)
    return p, s


def sublayer_decode_state(cfg: ModelConfig, sd: SubDef, batch: int, max_len: int,
                          dtype, enc_len: int = 0) -> Any:
    if sd.mixer == "attn":
        kv = jnp.zeros((batch, max_len, cfg.num_kv_heads, cfg.head_dim), dtype)
        return {"k": kv, "v": kv}
    if sd.mixer == "cross_attn":
        kv = jnp.zeros((batch, enc_len, cfg.num_kv_heads, cfg.head_dim), dtype)
        return {"k": kv, "v": kv}
    if sd.mixer == "mamba":
        return mamba_mod.mamba_decode_state(cfg, batch, dtype)
    if sd.mixer == "mamba2":
        return mamba2_mod.mamba2_decode_state(cfg, batch, dtype)
    if sd.mixer == "mlstm":
        return xlstm_mod.mlstm_decode_state(cfg, batch)
    if sd.mixer == "slstm":
        return xlstm_mod.slstm_decode_state(cfg, batch, dtype)
    raise ValueError(sd.mixer)


def decode_state_specs(sd: SubDef):
    """Logical axis specs for a sublayer's decode state."""
    if sd.mixer in ("attn", "cross_attn"):
        return {"k": ("batch", "kv_seq", "kv_heads", None),
                "v": ("batch", "kv_seq", "kv_heads", None)}
    if sd.mixer == "mamba":
        return {"conv": ("batch", None, "tp"), "ssm": ("batch", "tp", None)}
    if sd.mixer == "mamba2":
        return {"conv": ("batch", None, "tp"),
                "ssm": ("batch", "heads", None, None)}
    if sd.mixer == "mlstm":
        return {"C": ("batch", "heads", None, None),
                "n": ("batch", "heads", None), "m": ("batch", "heads")}
    if sd.mixer == "slstm":
        return {"h": ("batch", "tp"), "c": ("batch", "tp"),
                "n": ("batch", "tp"), "m": ("batch", "tp"),
                "conv": ("batch", None, "tp")}
    raise ValueError(sd.mixer)


def _apply_ffn(p, x, cfg: ModelConfig, sd: SubDef, dtype, moe_impl="sort"):
    h = apply_norm(p["norm2"], x, cfg.norm_type, cfg.norm_eps)
    if sd.ffn == "moe":
        if h.ndim == 2:             # decode: drops no token
            y = moe_apply(p["ffn"], h[:, None, :], cfg, dtype, impl=moe_impl,
                          dropless=True)[:, 0]
        else:
            y = moe_apply(p["ffn"], h, cfg, dtype, impl=moe_impl)
    else:
        y = mlp_apply(p["ffn"], h, cfg.mlp_type, dtype)
    return x + _residual(y, cfg)


def _residual(y, cfg: ModelConfig):
    return y if cfg.residual_multiplier == 1.0 else y * cfg.residual_multiplier


def sublayer_apply(p, x, cfg: ModelConfig, sd: SubDef, dtype, *,
                   mode: str, positions=None, pos=None, state=None,
                   enc_out=None, skip_blocks: bool = False,
                   flash_vjp: bool = False, moe_impl: str = "sort"):
    """Returns (x, new_state). mode: train | encode | prefill | decode."""
    h = apply_norm(p["norm1"], x, cfg.norm_type, cfg.norm_eps)
    new_state = state
    if sd.mixer == "attn":
        if mode == "decode":
            y, new_state = _attn_decode(p["mixer"], h, cfg, dtype, state, pos)
        else:
            cache = state if mode == "prefill" else None
            y, new_state = _attn_train(p["mixer"], h, cfg, dtype, positions,
                                       causal=(mode != "encode") and sd.causal,
                                       cache=cache, skip_blocks=skip_blocks,
                                       use_vjp=flash_vjp)
    elif sd.mixer == "cross_attn":
        if mode == "prefill":
            new_state = cross_kv(p["mixer"], enc_out, cfg, dtype)
            y = _cross_attn(p["mixer"], h, cfg, dtype, new_state)
        else:
            kv = state if mode == "decode" else cross_kv(p["mixer"], enc_out, cfg, dtype)
            y = _cross_attn(p["mixer"], h, cfg, dtype, kv)
            new_state = state
    elif sd.mixer == "mamba":
        if mode == "decode":
            y, new_state = mamba_mod.mamba_decode(p["mixer"], h, state, cfg, dtype)
        elif mode == "prefill":
            y, new_state = mamba_mod.mamba_apply(p["mixer"], h, cfg, dtype,
                                                 return_state=True)
        else:
            y = mamba_mod.mamba_apply(p["mixer"], h, cfg, dtype)
    elif sd.mixer == "mamba2":
        if mode == "decode":
            y, new_state = mamba2_mod.mamba2_decode(p["mixer"], h, state, cfg,
                                                    dtype)
        else:
            y = mamba2_mod.mamba2_apply(p["mixer"], h, cfg, dtype,
                                        return_state=mode == "prefill")
            if mode == "prefill":
                y, new_state = y
    elif sd.mixer == "mlstm":
        if mode == "decode":
            y, new_state = xlstm_mod.mlstm_decode(p["mixer"], h, state, cfg, dtype)
        elif mode == "prefill":
            y, new_state = xlstm_mod.mlstm_apply(p["mixer"], h, cfg, dtype,
                                                 return_state=True)
        else:
            y = xlstm_mod.mlstm_apply(p["mixer"], h, cfg, dtype)
    elif sd.mixer == "slstm":
        if mode == "decode":
            y, new_state = xlstm_mod.slstm_decode(p["mixer"], h, state, cfg, dtype)
        elif mode == "prefill":
            y, new_state = xlstm_mod.slstm_apply(p["mixer"], h, cfg, dtype,
                                                 return_state=True)
        else:
            y = xlstm_mod.slstm_apply(p["mixer"], h, cfg, dtype)
    else:
        raise ValueError(sd.mixer)
    x = x + _residual(y, cfg)
    if sd.ffn != "none":
        x = _apply_ffn(p, x, cfg, sd, dtype, moe_impl=moe_impl)
    return x, new_state
