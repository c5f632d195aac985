"""Plain reference of Granite 4.0-H (``granitemoehybrid``): the forward
pass of one sequence in float32 ``jax.numpy`` with the "highest" matmul
precision; no cache, no kernels, no batching. The Mamba-2 mixer is its
per-step recurrence.

A layer (``layer_types``: "mamba" or "attention"):

    h = x + residual_multiplier * mixer(rmsnorm(x))
    x = h + residual_multiplier * (moe(rmsnorm(h)) + shared(rmsnorm(h)))

with the input ``embed[tokens] * embedding_multiplier`` and the output
``rmsnorm(x) @ embed.T / logits_scaling`` (tied embeddings).

* Mamba-2: ``in_proj`` -> z, xBC, dt; causal depthwise conv of xBC (with
  bias), silu; x, B, C; per head ``dA = exp(softplus(dt + dt_bias) *
  -exp(A_log))``, ``h = dA h + dt x (outer) B``, ``y = h . C + D x``;
  ``rmsnorm(y * silu(z)) * norm`` per group; ``out_proj``.
* attention: GQA, no positional encoding, scores scaled by
  ``attention_multiplier``, causal.
* MoE: the router's logits over every expert, top-k, softmax over those
  k; the experts held (``held``) add their gated SwiGLU outputs; the
  shared SwiGLU is added for every token.

Departures from the published model, all of layout, none of arithmetic:

* separate ``w_gate`` / ``w_up`` matrices where the published checkpoint
  fuses them into one ``input_linear`` (experts and shared expert), and
  (in, out) matrices where it stores (out, in);
* ``conv_w`` as (d_conv, channels) where it stores (channels, 1, d_conv);
* the parameters of layer ``i`` under ``g0/s{i % period}/...`` at index
  ``i // period`` (the program's stacked layout);
* a replica holding experts [lo, hi) computes their part of the layer
  only, as each chip of an expert-parallel deployment does.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Sizes(NamedTuple):
    """The numbers the reference reads."""
    kinds: tuple            # "mamba" | "attention", per layer
    period: int             # layers per stacked group of the layout
    vocab: int
    heads: int
    kv_heads: int
    head_dim: int
    ssm_head_dim: int
    d_state: int
    groups: int
    d_conv: int
    d_inner: int
    top_k: int
    held: tuple             # [lo, hi) of the experts held
    eps: float
    embedding_multiplier: float
    residual_multiplier: float
    logits_scaling: float
    attention_multiplier: float


def sizes(cfg) -> Sizes:
    """The numbers the reference reads, from a ``ModelConfig``."""
    kinds = tuple("attention" if i % cfg.attn_period == cfg.attn_offset
                  else "mamba" for i in range(cfg.num_layers))
    return Sizes(
        kinds=kinds, period=cfg.attn_period, vocab=cfg.vocab_size,
        heads=cfg.num_heads, kv_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, ssm_head_dim=cfg.ssm_head_dim,
        d_state=cfg.d_state, groups=cfg.ssm_groups, d_conv=cfg.d_conv,
        d_inner=cfg.ssm_expand * cfg.d_model, top_k=cfg.experts_per_token,
        held=cfg.held_experts, eps=cfg.norm_eps,
        embedding_multiplier=cfg.embedding_multiplier,
        residual_multiplier=cfg.residual_multiplier,
        logits_scaling=cfg.logits_scaling,
        attention_multiplier=cfg.attention_multiplier)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def mamba2(p, x, hp):
    """x: (S, D) -> (S, D), one step at a time."""
    S = x.shape[0]
    din, n, g = hp.d_inner, hp.d_state, hp.groups
    H, P = din // hp.ssm_head_dim, hp.ssm_head_dim
    zxbcdt = x @ p["in_proj"]
    z, xbc, dt = jnp.split(zxbcdt, [din, 2 * din + 2 * g * n], axis=-1)
    k = hp.d_conv
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1])), xbc])
    conv = jnp.stack([jnp.sum(padded[t:t + k] * p["conv_w"], axis=0)
                      for t in range(S)])
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs, B, C = jnp.split(xbc, [din, din + g * n], axis=-1)
    xs = xs.reshape(S, H, P)
    group = np.arange(H) // (H // g)
    B = B.reshape(S, g, n)[:, group]                      # (S, H, N)
    C = C.reshape(S, g, n)[:, group]
    dt = jax.nn.softplus(dt + p["dt_bias"])               # (S, H)
    A = -jnp.exp(p["A_log"])

    def step(h, inp):
        dt_t, x_t, b_t, c_t = inp
        h = (jnp.exp(dt_t * A)[:, None, None] * h
             + dt_t[:, None, None] * x_t[:, :, None] * b_t[:, None, :])
        return h, jnp.einsum("hpn,hn->hp", h, c_t) + p["D"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, n)), (dt, xs, B, C))
    y = y.reshape(S, din) * jax.nn.silu(z)
    y = rmsnorm(y.reshape(S, g, din // g), 1.0, hp.eps).reshape(S, din)
    return (y * p["norm"]) @ p["out_proj"]


def attention(p, x, hp):
    """x: (S, D) -> (S, D), causal, no positions."""
    S = x.shape[0]
    q = (x @ p["wq"]).reshape(S, hp.heads, hp.head_dim)
    k = (x @ p["wk"]).reshape(S, hp.kv_heads, hp.head_dim)
    v = (x @ p["wv"]).reshape(S, hp.kv_heads, hp.head_dim)
    rep = hp.heads // hp.kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * hp.attention_multiplier
    causal = np.tril(np.ones((S, S), bool))
    w = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", w, v).reshape(S, -1) @ p["wo"]


def moe(p, x, hp):
    """x: (S, D) -> the held experts' gated outputs plus the shared one."""
    top, experts = jax.lax.top_k(x @ p["router"], hp.top_k)
    gates = jax.nn.softmax(top, axis=-1)
    lo, hi = hp.held
    y = swiglu(x, p["shared/w_gate"], p["shared/w_up"], p["shared/w_down"])
    for e in range(lo, hi):
        w = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)
        j = e - lo
        y = y + w[:, None] * swiglu(x, p["w_gate"][j], p["w_up"][j],
                                    p["w_down"][j])
    return y


def layer(p, x, kind: str, hp):
    """One layer; `p` holds its leaves by path below ``g0/s{j}/``."""
    sub = lambda pre: {k[len(pre):]: v for k, v in p.items()  # noqa: E731
                       if k.startswith(pre)}
    mixer = mamba2 if kind == "mamba" else attention
    h = rmsnorm(x, p["norm1/scale"], hp.eps)
    x = x + hp.residual_multiplier * mixer(sub("mixer/"), h, hp)
    h = rmsnorm(x, p["norm2/scale"], hp.eps)
    return x + hp.residual_multiplier * moe(sub("ffn/"), h, hp)


def layer_params(flat: dict, i: int, period: int) -> dict:
    """Layer `i`'s leaves from the flat {path: array} tree, float32."""
    pre = f"g0/s{i % period}/"
    return {k[len(pre):]: jnp.asarray(v[i // period], jnp.float32)
            for k, v in flat.items() if k.startswith(pre)}


def forward(flat: dict, tokens, hp) -> jnp.ndarray:
    """Logits (S, vocab) of one sequence; `flat` is {path: array}."""
    with jax.default_matmul_precision("highest"):
        embed = jnp.asarray(flat["embed"], jnp.float32)
        x = embed[jnp.asarray(tokens)] * hp.embedding_multiplier
        for i, kind in enumerate(hp.kinds):
            x = layer(layer_params(flat, i, hp.period), x, kind, hp)
        x = rmsnorm(x, jnp.asarray(flat["final_norm/scale"], jnp.float32),
                    hp.eps)
        return (x @ embed.T)[:, :hp.vocab] / hp.logits_scaling
