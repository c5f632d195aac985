"""The benchmark's plain reference of Granite 4.0-H (``granitemoehybrid``):
the logits of one sequence in float32 ``jax.numpy`` with the "highest"
matmul precision; no cache, no kernels, no batching; the Mamba-2 mixer
as its per-step recurrence. It imports nothing of the program, and runs
one layer on the device at a time, so the reference of a chip's share
fits beside the share itself.

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
  k; the experts held add their gated SwiGLU outputs; the shared SwiGLU
  is added for every token.

Departures from the published checkpoint, all of layout: separate
``w_gate`` / ``w_up`` where it fuses them into ``input_linear``, (in, out)
matrices, ``conv_w`` as (d_conv, channels), and layer ``i``'s leaves
under ``g0/s{i % period}/...`` at index ``i // period``, as the image
lays them out. A chip that holds experts [lo, hi) computes their part
of each layer only, as each chip of the expert-parallel deployment does.

The numbers come from the configuration file: the sizes it is run at
(``sizes``) where it states them, else its published keys.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class Sizes(NamedTuple):
    """The numbers the reference reads."""
    kinds: tuple            # "mamba" | "attention", per layer
    period: int             # layers per stacked group of the layout
    vocab: int
    d_model: int
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


def sizes(config: dict) -> Sizes:
    """The numbers the reference reads, from a configuration file."""
    run = config["sizes"]
    types = config["layer_types"]
    period = next(p for p in range(1, len(types) + 1)
                  if all(t == types[i % p] for i, t in enumerate(types)))
    period = run.get("attn_period", period)
    attn = run.get("attn_offset", types.index("attention"))
    layers = run.get("num_layers", config["num_hidden_layers"])
    d_model = run.get("d_model", config["hidden_size"])
    heads = run.get("num_heads", config["num_attention_heads"])
    experts = run.get("num_experts", config["num_local_experts"])
    ep_size, ep_rank = run.get("ep_size", 1), run.get("ep_rank", 0)
    return Sizes(
        kinds=tuple("attention" if i % period == attn else "mamba"
                    for i in range(layers)),
        period=period, vocab=run.get("vocab_size", config["vocab_size"]),
        d_model=d_model, heads=heads, kv_heads=run.get("num_kv_heads",
                                      config["num_key_value_heads"]),
        head_dim=run.get("head_dim", d_model // heads),
        ssm_head_dim=run.get("ssm_head_dim", config["mamba_d_head"]),
        d_state=run.get("d_state", config["mamba_d_state"]),
        groups=run.get("ssm_groups", config["mamba_n_groups"]),
        d_conv=run.get("d_conv", config["mamba_d_conv"]),
        d_inner=run.get("ssm_expand", config["mamba_expand"]) * d_model,
        top_k=run.get("experts_per_token", config["num_experts_per_tok"]),
        held=(experts * ep_rank // ep_size,
              experts * (ep_rank + 1) // ep_size),
        eps=config["rms_norm_eps"],
        embedding_multiplier=config["embedding_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        logits_scaling=config["logits_scaling"],
        attention_multiplier=config["attention_multiplier"])


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


def held_experts(p, x, hp):
    """x: (S, D) -> the gated outputs of the experts held, each token
    routed over every expert: top-k of the router's logits, softmax over
    those k."""
    top, experts = jax.lax.top_k(x @ p["router"], hp.top_k)
    gates = jax.nn.softmax(top, axis=-1)
    lo, hi = hp.held
    y = jnp.zeros_like(x)
    for e in range(lo, hi):
        w = jnp.sum(jnp.where(experts == e, gates, 0.0), axis=-1)
        j = e - lo
        y = y + w[:, None] * swiglu(x, p["w_gate"][j], p["w_up"][j],
                                    p["w_down"][j])
    return y


def router_margin(p, x, hp):
    """Each token's gap between its k-th and (k+1)-th router logit, over
    the larger magnitude of the two (infinite where k is every expert)."""
    logits = x @ p["router"]
    if hp.top_k >= logits.shape[-1]:
        return jnp.full(x.shape[:1], jnp.inf)
    top = jax.lax.top_k(logits, hp.top_k + 1)[0]
    a, b = top[:, -2], top[:, -1]
    return (a - b) / jnp.maximum(jnp.abs(a), jnp.abs(b))


def moe(p, x, hp):
    """x: (S, D) -> the held experts' gated outputs plus the shared one."""
    return (swiglu(x, p["shared/w_gate"], p["shared/w_up"],
                   p["shared/w_down"]) + held_experts(p, x, hp))


def layer(p, x, kind: str, hp):
    """One layer; `p` holds its leaves by path below ``g0/s{j}/``."""
    sub = lambda pre: {k[len(pre):]: v for k, v in p.items()  # noqa: E731
                       if k.startswith(pre)}
    mixer = mamba2 if kind == "mamba" else attention
    h = rmsnorm(x, p["norm1/scale"], hp.eps)
    x = x + hp.residual_multiplier * mixer(sub("mixer/"), h, hp)
    h = rmsnorm(x, p["norm2/scale"], hp.eps)
    return x + hp.residual_multiplier * moe(sub("ffn/"), h, hp)


def _highest(fn):
    def run(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return run


@partial(jax.jit, static_argnums=(2, 3))
@_highest
def _layer(p, x, kind, hp):
    return layer({k: v.astype(jnp.float32) for k, v in p.items()}, x, kind,
                 hp)


@partial(jax.jit, static_argnums=(2,))
@_highest
def _held(p, x, hp):
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    x = x.astype(jnp.float32)
    return held_experts(p, x, hp), router_margin(p, x, hp)


@jax.jit
@_highest
def _embed(table, tokens, scale):
    return table.astype(jnp.float32)[tokens] * scale


@partial(jax.jit, static_argnums=(3, 4))
@_highest
def _head(x, norm, table, eps, vocab):
    x = rmsnorm(x, norm.astype(jnp.float32), eps)
    return (x @ table.astype(jnp.float32).T)[:vocab]


def last_logits(flat: dict, tokens, hp: Sizes) -> np.ndarray:
    """Logits (vocab,) at the last position of `tokens`. `flat` is
    {path: array} on the host, in the program's layout; each layer's
    leaves go to the device in their own dtype and are computed in
    float32 there, one layer at a time."""
    table = jax.device_put(flat["embed"])
    x = _embed(table, jnp.asarray(tokens), hp.embedding_multiplier)
    for i, kind in enumerate(hp.kinds):
        pre = f"g0/s{i % hp.period}/"
        p = {k[len(pre):]: jax.device_put(v[i // hp.period])
             for k, v in flat.items() if k.startswith(pre)}
        x = _layer(p, x, kind, hp)
        del p
    logits = _head(x[-1], jax.device_put(flat["final_norm/scale"]), table,
                   hp.eps, hp.vocab)
    return np.asarray(logits) / hp.logits_scaling


def ffn_of(flat: dict, i: int, hp: Sizes) -> dict:
    """Layer `i`'s router and held experts, {router, w_gate, w_up,
    w_down}, from `flat` in the program's layout."""
    pre = f"g0/s{i % hp.period}/ffn/"
    return {n: flat[pre + n][i // hp.period]
            for n in ("router", "w_gate", "w_up", "w_down")}


def held_outputs(flat: dict, x, hp: Sizes) -> list:
    """Per layer, (the held experts' part of its MoE on `x` (T, D),
    each token's router margin), computed in float32 on the device one
    layer at a time."""
    out = []
    for i in range(len(hp.kinds)):
        p = {k: jax.device_put(v) for k, v in ffn_of(flat, i, hp).items()}
        y, margin = _held(p, jnp.asarray(x), hp)
        out.append((np.asarray(y), np.asarray(margin)))
        del p
    return out
