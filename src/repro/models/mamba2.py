"""Mamba-2 mixer (SSD, arXiv:2405.21060), as Granite 4.0-H uses it.

``in_proj`` gives z, xBC and dt. xBC passes a causal depthwise conv (with
bias) and silu, then splits into x (heads of ``ssm_head_dim``), B and C
(``ssm_groups`` groups of ``d_state``, shared by the heads of a group).
Per head, with one scalar decay:

    dt = softplus(dt + dt_bias),  dA = exp(dt * -exp(A_log))
    h  = dA * h + dt * x (outer) B,   y = h . C + D * x

then a gated RMSNorm, ``rmsnorm(y * silu(z)) * norm`` (per group), and
``out_proj``. The full forward runs the chunked SSD scan: quadratic
inside a chunk of ``ssm_chunk`` steps, a recurrence over chunk states
between chunks. Decode keeps O(1) state: a (d_conv-1)-deep window of the
conv's input and the (heads, head_dim, d_state) SSM state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.common import dense_init, stable_fold


def dims(cfg: ModelConfig) -> tuple:
    """(d_inner, heads, groups x d_state, conv channels)."""
    din = cfg.ssm_expand * cfg.d_model
    gn = cfg.ssm_groups * cfg.d_state
    return din, din // cfg.ssm_head_dim, gn, din + 2 * gn


def mamba2_init(key, prefix: str, cfg: ModelConfig):
    """Projections normal as ``dense_init``; conv as torch's Conv1d
    default (uniform, bound 1/sqrt(d_conv)); A, dt and D as the Mamba-2
    reference module: A ~ U(1, 16), dt ~ logU(1e-3, 1e-1) stored as its
    inverse softplus, D = 1; the gated norm's weight 1."""
    din, heads, _, conv = dims(cfg)
    fold = lambda nm: stable_fold(key, f"{prefix}.{nm}")  # noqa: E731
    bound = 1.0 / np.sqrt(cfg.d_conv)
    p, s = {}, {}
    p["in_proj"], s["in_proj"] = dense_init(
        key, f"{prefix}.in_proj", cfg.d_model, din + conv + heads, "fsdp", "tp")
    p["conv_w"] = jax.random.uniform(fold("conv_w"), (cfg.d_conv, conv),
                                     jnp.float32, -bound, bound)
    s["conv_w"] = (None, "tp")
    p["conv_b"] = jax.random.uniform(fold("conv_b"), (conv,), jnp.float32,
                                     -bound, bound)
    s["conv_b"] = ("tp",)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        fold("dt"), (heads,), jnp.float32, np.log(1e-3), np.log(1e-1))), 1e-4)
    p["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    p["A_log"] = jnp.log(jax.random.uniform(fold("A"), (heads,), jnp.float32,
                                            1.0, 16.0))
    p["D"] = jnp.ones((heads,), jnp.float32)
    for nm in ("dt_bias", "A_log", "D"):
        s[nm] = (None,)
    p["norm"] = jnp.ones((din,), jnp.float32)
    s["norm"] = ("tp",)
    p["out_proj"], s["out_proj"] = dense_init(
        key, f"{prefix}.out_proj", din, cfg.d_model, "tp", "fsdp")
    return p, s


def mamba2_decode_state(cfg: ModelConfig, batch: int, dtype):
    _, heads, _, conv = dims(cfg)
    return {"conv": jnp.zeros((batch, cfg.d_conv - 1, conv), dtype),
            "ssm": jnp.zeros((batch, heads, cfg.ssm_head_dim, cfg.d_state),
                             jnp.float32)}


def _split(p, x, cfg: ModelConfig, dtype):
    """in_proj -> (z, xBC, dt)."""
    din, heads, _, conv = dims(cfg)
    zxbcdt = x @ p["in_proj"].astype(dtype)
    return jnp.split(zxbcdt, [din, din + conv], axis=-1)


def _ssm_inputs(p, xbc, dt, cfg: ModelConfig):
    """Post-conv xBC and raw dt -> x (..., H, P), B and C (..., H, N) in
    float32 (each group's B and C repeated over its heads), dt (..., H)
    and A (H,)."""
    din, heads, gn, _ = dims(cfg)
    lead = xbc.shape[:-1]
    x, b, c = jnp.split(xbc.astype(jnp.float32), [din, din + gn], axis=-1)
    rep = heads // cfg.ssm_groups

    def per_head(m):
        m = m.reshape(lead + (cfg.ssm_groups, cfg.d_state))
        return jnp.repeat(m, rep, axis=-2)

    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    return (x.reshape(lead + (heads, cfg.ssm_head_dim)), per_head(b),
            per_head(c), dt, A)


def _gated_out(p, y, z, cfg: ModelConfig, dtype):
    """y: (..., H, P) float32 -> rmsnorm(y * silu(z)) * norm @ out_proj."""
    din = y.shape[-2] * y.shape[-1]
    g = y.reshape(y.shape[:-2] + (din,)) * jax.nn.silu(z.astype(jnp.float32))
    grouped = g.reshape(g.shape[:-1] + (cfg.ssm_groups, din // cfg.ssm_groups))
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + cfg.norm_eps)
    g = grouped.reshape(g.shape) * p["norm"].astype(jnp.float32)
    return g.astype(dtype) @ p["out_proj"].astype(dtype)


def _segsum(a):
    """(..., T) -> (..., T, T): out[i, j] = a[j+1] + ... + a[i] for i >= j,
    -inf above the diagonal (exp of it is the decay from j to i)."""
    T = a.shape[-1]
    x = jnp.repeat(a[..., :, None], T, axis=-1)          # x[i, j] = a[i]
    x = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), x, 0.0)
    x = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), x, -jnp.inf)


def _ssd(X, A, B, C, chunk: int):
    """Chunked SSD scan from a zero state. X: (b, l, h, p) (x * dt);
    A: (b, l, h) (dt * A); B, C: (b, l, h, n); l a multiple of `chunk`.
    Returns y (b, l, h, p) and the final state (b, h, p, n)."""
    b, l, h, p = X.shape
    c = l // chunk
    X, B, C = (t.reshape((b, c, chunk) + t.shape[2:]) for t in (X, B, C))
    A = A.reshape(b, c, chunk, h).transpose(0, 3, 1, 2)   # (b, h, c, l)
    A_cum = jnp.cumsum(A, axis=-1)
    # within each chunk
    L = jnp.exp(_segsum(A))
    y_diag = jnp.einsum("bclhn,bcshn,bhcls,bcshp->bclhp", C, B, L, X)
    # each chunk's end state, then the recurrence over chunks
    decay = jnp.exp(A_cum[..., -1:] - A_cum)
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", B, decay, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], axis=1)
    ends = jnp.pad(A_cum[..., -1], ((0, 0), (0, 0), (1, 0)))
    states = jnp.einsum("bhzc,bchpn->bzhpn", jnp.exp(_segsum(ends)), states)
    states, final = states[:, :-1], states[:, -1]
    # each chunk's start state carried to its steps
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", C, states, jnp.exp(A_cum))
    return (y_diag + y_off).reshape(b, l, h, p), final


def mamba2_apply(p, x: jnp.ndarray, cfg: ModelConfig, dtype,
                 return_state: bool = False):
    """Full forward. x: (B, S, D) -> (B, S, D) [, final decode state]."""
    B, S, _ = x.shape
    z, xbc_raw, dt = _split(p, x, cfg, dtype)
    k = cfg.d_conv
    pad = jnp.pad(xbc_raw, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + S] * p["conv_w"][i].astype(dtype) for i in range(k))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(dtype))
    xs, Bm, Cm, dt, A = _ssm_inputs(p, xbc, dt, cfg)
    chunk = min(cfg.ssm_chunk, S)
    extra = -S % chunk                  # padded steps have dt = 0: no-ops
    grow = lambda t: jnp.pad(t, ((0, 0), (0, extra)) + ((0, 0),) * (t.ndim - 2))  # noqa: E731
    y, h = _ssd(grow(xs * dt[..., None]), grow(dt * A), grow(Bm), grow(Cm),
                chunk)
    y = y[:, :S] + p["D"].astype(jnp.float32)[:, None] * xs
    out = _gated_out(p, y, z, cfg, dtype)
    if return_state:
        return out, {"conv": pad[:, S:], "ssm": h}
    return out


def mamba2_decode(p, x: jnp.ndarray, state, cfg: ModelConfig, dtype):
    """One token. x: (B, D) -> (B, D), new state."""
    z, xbc, dt = _split(p, x, cfg, dtype)
    window = jnp.concatenate([state["conv"], xbc[:, None, :]], axis=1)
    conv = jnp.einsum("bkc,kc->bc", window, p["conv_w"].astype(dtype))
    xs, Bm, Cm, dt, A = _ssm_inputs(
        p, jax.nn.silu(conv + p["conv_b"].astype(dtype)), dt, cfg)
    h = (jnp.exp(dt * A)[..., None, None] * state["ssm"]
         + (dt[..., None] * xs)[..., None] * Bm[..., None, :])
    y = jnp.einsum("bhpn,bhn->bhp", h, Cm) \
        + p["D"].astype(jnp.float32)[:, None] * xs
    return _gated_out(p, y, z, cfg, dtype), {"conv": window[:, 1:], "ssm": h}
