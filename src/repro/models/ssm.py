"""State-space sequence mixers: Mamba2 (SSD) and RWKV-6 (Finch).

Both use the chunked formulation: within a chunk the recurrence is evaluated
as a masked quadratic (attention-like) form; across chunks a small recurrent
state is carried by ``lax.scan``. This bounds activation memory at
O(S * chunk) instead of O(S^2) or O(S * state) and is the TPU-native way to
run linear-recurrent layers (MXU-friendly chunk matmuls + tiny carry).

Decode is a single recurrence step on an O(1) state — these layers are what
makes ``long_500k`` native for rwkv6/zamba2 (DESIGN.md §4).

Numerical notes:
* Mamba2 decay exponents L_t - L_s are <= 0 only on the causal half of the
  chunk quadratic; the other half grows with the chunk (~chunk * dt) and
  overflows exp at chunk 256. It is masked to -inf *before* the exp, so the
  backward pass never multiplies a zero cotangent by inf.
* RWKV6 per-channel decays are clamped to log w in [-2, -1e-6] and the
  intra-chunk factors are stabilized around the chunk-midpoint cumulative
  decay (documented simplification; chunk=32).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from .config import MambaConfig, ModelConfig, RWKVConfig
from .layers import _dense_init, rmsnorm, rmsnorm_init

Array = jax.Array


# ===========================================================================
# Mamba2 (SSD)
# ===========================================================================


def mamba_init(cfg: ModelConfig, key):
    m: MambaConfig = cfg.mamba
    d_inner = m.expand * cfg.d_model
    nheads = d_inner // m.head_dim
    conv_dim = d_inner + 2 * m.state_dim
    ks = jax.random.split(key, 4)
    return {
        # order: [z | x | B | C | dt]
        "in_proj": _dense_init(ks[0], cfg.d_model, 2 * d_inner + 2 * m.state_dim + nheads),
        "conv_w": jax.random.normal(ks[1], (m.conv_width, conv_dim), jnp.float32) * 0.1,
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "A_log": jnp.zeros((nheads,), jnp.float32),  # A = -exp(A_log) = -1
        "D": jnp.ones((nheads,), jnp.float32),
        "dt_bias": jnp.zeros((nheads,), jnp.float32),
        "out_norm": rmsnorm_init(d_inner),
        "out_proj": _dense_init(ks[2], d_inner, cfg.d_model),
    }


def _causal_conv(xBC, conv_w, conv_b):
    """Depthwise causal conv along seq. xBC: [B,S,C]; conv_w: [W,C]."""
    W = conv_w.shape[0]
    pads = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(
        pads[:, i : i + xBC.shape[1], :] * conv_w[i].astype(xBC.dtype) for i in range(W)
    )
    return out + conv_b.astype(xBC.dtype)


def _mamba_project(cfg, params, x):
    m = cfg.mamba
    d_inner = m.expand * cfg.d_model
    nheads = d_inner // m.head_dim
    zxbcdt = x @ params["in_proj"].astype(x.dtype)
    z = zxbcdt[..., :d_inner]
    xBC = zxbcdt[..., d_inner : 2 * d_inner + 2 * m.state_dim]
    dt_raw = zxbcdt[..., 2 * d_inner + 2 * m.state_dim :]
    return z, xBC, dt_raw, d_inner, nheads


def _mamba_post(cfg, params, xin, y, z, dt, Bv=None):
    """y + D skip, gated norm, out proj."""
    m = cfg.mamba
    B_, S, H, hd = y.shape
    xh = xin.reshape(B_, S, H, hd)
    y = y + params["D"].astype(y.dtype)[None, None, :, None] * xh
    y = y.reshape(B_, S, H * hd)
    y = rmsnorm(params["out_norm"], y, cfg.rms_eps)
    y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    return y @ params["out_proj"].astype(y.dtype)


def mamba_apply(cfg: ModelConfig, params, x):
    """Training/prefill SSD. x: [B,S,D] -> [B,S,D]."""
    m = cfg.mamba
    B_, S, _ = x.shape
    z, xBC, dt_raw, d_inner, H = _mamba_project(cfg, params, x)
    xBC = jax.nn.silu(_causal_conv(xBC, params["conv_w"], params["conv_b"]).astype(jnp.float32)).astype(x.dtype)
    xs = xBC[..., :d_inner]
    Bm = xBC[..., d_inner : d_inner + m.state_dim].astype(jnp.float32)  # [B,S,N]
    Cm = xBC[..., d_inner + m.state_dim :].astype(jnp.float32)  # [B,S,N]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])  # [B,S,H]
    A = -jnp.exp(params["A_log"])  # [H]
    loga = dt * A[None, None, :]  # log decay, <= 0
    xh = xs.reshape(B_, S, H, m.head_dim).astype(jnp.float32)
    xdt = xh * dt[..., None]  # dt-weighted input

    Q = m.chunk
    assert S % Q == 0, (S, Q)
    nc = S // Q

    def r(t):  # chunk reshape
        return t.reshape((B_, nc, Q) + t.shape[2:])

    loga_c, x_c, B_c, C_c = r(loga), r(xdt), r(Bm), r(Cm)
    L = jnp.cumsum(loga_c, axis=2)  # [B,nc,Q,H] inclusive

    # ---- intra-chunk quadratic: scores[t,s] = (C_t.B_s) e^{L_t-L_s} (s<=t)
    CB = jnp.einsum("bctn,bcsn->bcts", C_c, B_c)  # [B,nc,Q,Q]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    seg = L[:, :, :, None, :] - L[:, :, None, :, :]  # [B,nc,Q,Q,H]
    dec = jnp.exp(jnp.where(mask[None, None, :, :, None], seg, -jnp.inf))
    scores = CB[..., None] * dec
    y_intra = jnp.einsum("bctsh,bcshd->bcthd", scores, x_c)

    # ---- inter-chunk recurrence over carried state [B,H,N,hd]
    # state_in decays to t as e^{L_t}; token s contributes to the chunk-end
    # state with decay e^{L_last - L_s}.
    w_state = jnp.exp(L[:, :, -1, None, :] - L)  # [B,nc,Q,H] decay from s to chunk end
    state_add = jnp.einsum("bcsh,bcsn,bcshd->bchnd", w_state, B_c, x_c)
    chunk_decay = jnp.exp(L[:, :, -1, :])  # [B,nc,H]

    def body(S_prev, inp):
        add, cdec, Cc, Lc = inp  # [B,H,N,hd], [B,H], [B,Q,N], [B,Q,H]
        y_in = jnp.einsum("bqn,bhnd,bqh->bqhd", Cc, S_prev, jnp.exp(Lc))
        S_new = cdec[:, :, None, None] * S_prev + add
        return S_new, y_in

    S0 = jnp.zeros((B_, H, m.state_dim, m.head_dim), jnp.float32)
    xs_scan = (
        state_add.transpose(1, 0, 2, 3, 4),
        chunk_decay.transpose(1, 0, 2),
        C_c.transpose(1, 0, 2, 3),
        L.transpose(1, 0, 2, 3),
    )
    _, y_inter = jax.lax.scan(body, S0, xs_scan)
    y_inter = y_inter.transpose(1, 0, 2, 3, 4)  # [B,nc,Q,H,hd]

    y = (y_intra + y_inter).reshape(B_, S, H, m.head_dim).astype(x.dtype)
    return _mamba_post(cfg, params, xs, y, z, dt)


def mamba_cache_init(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    m = cfg.mamba
    d_inner = m.expand * cfg.d_model
    H = d_inner // m.head_dim
    conv_dim = d_inner + 2 * m.state_dim
    return {
        "ssm": jnp.zeros((batch, H, m.state_dim, m.head_dim), jnp.float32),
        "conv": jnp.zeros((batch, m.conv_width - 1, conv_dim), dtype),
    }


def mamba_decode(cfg: ModelConfig, params, x, cache, pos):
    """One-token recurrence. x: [B,1,D]."""
    m = cfg.mamba
    B_ = x.shape[0]
    z, xBC, dt_raw, d_inner, H = _mamba_project(cfg, params, x)
    # conv over cached window
    hist = jnp.concatenate([cache["conv"], xBC.astype(cache["conv"].dtype)], axis=1)
    conv_out = jnp.einsum("bwc,wc->bc", hist, params["conv_w"].astype(hist.dtype)) + params[
        "conv_b"
    ].astype(hist.dtype)
    xBC1 = jax.nn.silu(conv_out.astype(jnp.float32))[:, None, :].astype(x.dtype)
    new_conv = hist[:, 1:, :]
    xs = xBC1[..., :d_inner]
    Bm = xBC1[..., d_inner : d_inner + m.state_dim].astype(jnp.float32)[:, 0]
    Cm = xBC1[..., d_inner + m.state_dim :].astype(jnp.float32)[:, 0]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + params["dt_bias"])[:, 0]  # [B,H]
    a = jnp.exp(dt * (-jnp.exp(params["A_log"]))[None, :])  # [B,H]
    xh = xs.reshape(B_, 1, H, m.head_dim).astype(jnp.float32)[:, 0]  # [B,H,hd]
    S_new = a[:, :, None, None] * cache["ssm"] + jnp.einsum(
        "bn,bhd,bh->bhnd", Bm, xh, dt
    )
    y = jnp.einsum("bn,bhnd->bhd", Cm, S_new)[:, None]  # [B,1,H,hd]
    out = _mamba_post(cfg, params, xs, y.astype(x.dtype), z, dt)
    return out, {"ssm": S_new, "conv": new_conv}


# ===========================================================================
# RWKV-6 (Finch)
# ===========================================================================

RWKV_LOGW_MIN, RWKV_LOGW_MAX = -2.0, -1e-6
RWKV_CHUNK = 32


def rwkv_init(cfg: ModelConfig, key):
    r: RWKVConfig = cfg.rwkv
    D = cfg.d_model
    H = D // r.head_dim
    ks = jax.random.split(key, 10)
    p = {
        "mu": jax.random.uniform(ks[0], (5, D), jnp.float32),  # r,k,v,w,g lerps
        "w_r": _dense_init(ks[1], D, D),
        "w_k": _dense_init(ks[2], D, D),
        "w_v": _dense_init(ks[3], D, D),
        "w_g": _dense_init(ks[4], D, D),
        "w0": jnp.full((D,), -0.6, jnp.float32),  # base log-log decay
        "w_lora_a": _dense_init(ks[5], D, r.decay_lora),
        "w_lora_b": jnp.zeros((r.decay_lora, D), jnp.float32),
        "u": jax.random.normal(ks[6], (D,), jnp.float32) * 0.1,  # bonus
        "out_norm": rmsnorm_init(r.head_dim),  # per-head norm
        "w_out": _dense_init(ks[7], D, D),
        # channel mix
        "cm_mu": jax.random.uniform(ks[8], (2, D), jnp.float32),
        "cm_k": _dense_init(ks[9], D, cfg.d_ff),
        "cm_v": _dense_init(jax.random.fold_in(key, 99), cfg.d_ff, D),
        "cm_r": _dense_init(jax.random.fold_in(key, 98), D, D),
    }
    return p


def _shift(x, last=None):
    """Token shift: x_{t-1} (zeros / provided state at t=0)."""
    pad = jnp.zeros_like(x[:, :1]) if last is None else last[:, None, :]
    return jnp.concatenate([pad, x[:, :-1]], axis=1)


def _rwkv_proj(cfg, params, x, x_prev):
    r = cfg.rwkv
    D = cfg.d_model
    H = D // r.head_dim
    B_, S, _ = x.shape
    mu = params["mu"].astype(x.dtype)
    mix = lambda i: x + mu[i] * (x_prev - x)
    rv = (mix(0) @ params["w_r"].astype(x.dtype)).reshape(B_, S, H, r.head_dim)
    kv = (mix(1) @ params["w_k"].astype(x.dtype)).reshape(B_, S, H, r.head_dim)
    vv = (mix(2) @ params["w_v"].astype(x.dtype)).reshape(B_, S, H, r.head_dim)
    logw = params["w0"] + jnp.tanh(
        (mix(3) @ params["w_lora_a"].astype(x.dtype)).astype(jnp.float32)
    ) @ params["w_lora_b"]
    logw = -jnp.exp(logw)  # < 0
    logw = jnp.clip(logw, RWKV_LOGW_MIN, RWKV_LOGW_MAX).reshape(B_, S, H, r.head_dim)
    gv = jax.nn.silu((mix(4) @ params["w_g"].astype(x.dtype)).astype(jnp.float32)).astype(x.dtype)
    return rv, kv, vv, logw, gv


def _rwkv_out(cfg, params, wkv, g, x_dtype):
    r = cfg.rwkv
    B_, S, H, hd = wkv.shape
    y = rmsnorm(params["out_norm"], wkv.astype(jnp.float32)).astype(x_dtype)
    y = (y.reshape(B_, S, H * hd) * g.reshape(B_, S, H * hd))
    return y @ params["w_out"].astype(x_dtype)


def rwkv_timemix_apply(cfg: ModelConfig, params, x, x_last=None):
    """Chunked RWKV6 time mix. x: [B,S,D]."""
    r = cfg.rwkv
    B_, S, D = x.shape
    H = D // r.head_dim
    rv, kv, vv, logw, g = _rwkv_proj(cfg, params, x, _shift(x, x_last))
    rv, kv, vv = (t.astype(jnp.float32) for t in (rv, kv, vv))
    u = params["u"].reshape(H, r.head_dim)

    Q = RWKV_CHUNK
    assert S % Q == 0, (S, Q)
    nc = S // Q
    ch = lambda t: t.reshape((B_, nc, Q) + t.shape[2:])
    rc, kc, vc, lw = ch(rv), ch(kv), ch(vv), ch(logw)
    Wc = jnp.cumsum(lw, axis=2)  # [B,nc,Q,H,hd] inclusive cum log decay
    Wprev = Wc - lw  # exclusive (W_{t-1})
    Wref = Wc[:, :, Q // 2 : Q // 2 + 1]  # midpoint stabilizer
    r_t = rc * jnp.exp(Wprev - Wref)
    k_s = kc * jnp.exp(Wref - Wc)
    scores = jnp.einsum("bcthd,bcshd->bchts", r_t, k_s)
    mask = jnp.tril(jnp.ones((Q, Q), bool), k=-1)  # strict s < t
    scores = jnp.where(mask[None, None, None], scores, 0.0)
    bonus = jnp.einsum("bcthd,hd,bcthd->bcth", rc, u, kc)  # s == t term
    y_intra = jnp.einsum("bchts,bcshd->bcthd", scores, vc)
    y_intra += bonus[..., None] * vc

    # inter-chunk state S in [B,H,hd_k,hd_v]
    w_end = jnp.exp(Wc[:, :, -1:, :, :] - Wc)  # decay s -> chunk end
    add = jnp.einsum("bcshk,bcshv->bchkv", kc * w_end, vc)
    cdec = jnp.exp(Wc[:, :, -1])  # [B,nc,H,hd]
    r_in = rc * jnp.exp(Wprev)  # decay from chunk start

    def body(S_prev, inp):
        a, cd, rr = inp
        y_in = jnp.einsum("bqhk,bhkv->bqhv", rr, S_prev)
        S_new = cd[:, :, :, None] * S_prev + a
        return S_new, y_in

    S0 = jnp.zeros((B_, H, r.head_dim, r.head_dim), jnp.float32)
    _, y_inter = jax.lax.scan(
        body,
        S0,
        (add.transpose(1, 0, 2, 3, 4), cdec.transpose(1, 0, 2, 3), r_in.transpose(1, 0, 2, 3, 4)),
    )
    y = y_intra + y_inter.transpose(1, 0, 2, 3, 4)
    y = y.reshape(B_, S, H, r.head_dim)
    return _rwkv_out(cfg, params, y, g, x.dtype)


def rwkv_timemix_decode(cfg: ModelConfig, params, x, cache, pos):
    """One-token RWKV6 step. cache: {state:[B,H,k,v], x_last:[B,D]}."""
    r = cfg.rwkv
    B_ = x.shape[0]
    D = cfg.d_model
    H = D // r.head_dim
    rv, kv, vv, logw, g = _rwkv_proj(cfg, params, x, cache["x_last"][:, None, :].astype(x.dtype))
    rv, kv, vv = (t.astype(jnp.float32)[:, 0] for t in (rv, kv, vv))  # [B,H,hd]
    w = jnp.exp(logw.astype(jnp.float32))[:, 0]  # [B,H,hd]
    u = params["u"].reshape(H, r.head_dim)
    S_prev = cache["state"]
    y = jnp.einsum("bhk,bhkv->bhv", rv, S_prev) + jnp.einsum(
        "bhk,hk,bhk,bhv->bhv", rv, u, kv, vv
    )
    S_new = w[..., None] * S_prev + jnp.einsum("bhk,bhv->bhkv", kv, vv)
    out = _rwkv_out(cfg, params, y[:, None], g, x.dtype)
    return out, {"state": S_new, "x_last": x[:, 0].astype(cache["x_last"].dtype)}


def rwkv_chanmix_apply(cfg: ModelConfig, params, x, x_last=None):
    xs = _shift(x, x_last)
    mu = params["cm_mu"].astype(x.dtype)
    xk = x + mu[0] * (xs - x)
    xr = x + mu[1] * (xs - x)
    h = jnp.square(jax.nn.relu((xk @ params["cm_k"].astype(x.dtype)).astype(jnp.float32))).astype(x.dtype)
    rgate = jax.nn.sigmoid((xr @ params["cm_r"].astype(x.dtype)).astype(jnp.float32)).astype(x.dtype)
    return rgate * (h @ params["cm_v"].astype(x.dtype))


def rwkv_cache_init(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    r = cfg.rwkv
    D = cfg.d_model
    H = D // r.head_dim
    return {
        "state": jnp.zeros((batch, H, r.head_dim, r.head_dim), jnp.float32),
        "x_last": jnp.zeros((batch, D), dtype),
        "cm_x_last": jnp.zeros((batch, D), dtype),
    }
