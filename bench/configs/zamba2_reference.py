"""Plain float32 reference of the zamba2 configuration: its weights from
the seed, its forward pass and loss, and its FLOP count. The training
round around it is ``bench/lib/reference_train.py``. It imports nothing of
the program.

Everything is computed in float32 (the round sets ``highest`` matmul
precision); the Mamba2 state is carried by the plain recurrence over
positions (h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T, y_t = C_t h_t +
D x_t), not by chunks. Attention is softmax over the full causal score
matrix.

Weights are made from the seed by the draws that the configuration's
initialiser defines, in its layout: the leading run of Mamba2 layers is
stacked on a leading axis (``stack/segments/0/b0``), the shared block has
one set of weights (``stack/shared_blk``), and each Mamba2 layer after it
has a segment of its own. The leaf order of that layout also orders the
broadcast's per-leaf randomness.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.lib import counts

F32 = jnp.float32
REC_STRETCH = 64


# ---------------------------------------------------------------------------
# weights from the seed
# ---------------------------------------------------------------------------


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, F32) * scale


def _layout(cfg):
    """(k, j): the pattern is k >= 4 Mamba2 layers, the shared block, then
    j <= 3 Mamba2 layers (the hybrid slot's own, in the published model)."""
    pat = list(cfg["block_pattern"])
    k = pat.index("shared") if "shared" in pat else -1
    j = len(pat) - k - 1
    if k < 4 or not 0 <= j <= 3 or pat != ["mamba"] * k + ["shared"] + ["mamba"] * j:
        raise ValueError("the reference covers k >= 4 Mamba2 layers, the shared block "
                         f"and at most 3 more Mamba2 layers, not {pat}")
    return k, j


def init_params(cfg, key):
    D, V = cfg["d_model"], cfg["vocab_size"]
    m = cfg["mamba"]
    d_inner = m["expand"] * D
    H = d_inner // m["head_dim"]
    N = m["state_dim"]
    conv_dim = d_inner + 2 * N
    q_dim = cfg["num_heads"] * cfg["head_dim"]
    kv_dim = cfg["num_kv_heads"] * cfg["head_dim"]
    k_mamba, j_mamba = _layout(cfg)
    k_embed, k_stack, k_out = jax.random.split(key, 3)
    keys = jax.random.split(k_stack, len(cfg["block_pattern"]) + 1)

    def mamba_layer(key):
        ks = jax.random.split(jax.random.split(key, 4)[1], 4)
        return {
            "ln": {"scale": jnp.ones((D,), F32)},
            "mamba": {
                "in_proj": _normal(ks[0], (D, 2 * d_inner + 2 * N + H), D ** -0.5),
                "conv_w": _normal(ks[1], (m["conv_width"], conv_dim), 0.1),
                "conv_b": jnp.zeros((conv_dim,), F32),
                "A_log": jnp.zeros((H,), F32),
                "D": jnp.ones((H,), F32),
                "dt_bias": jnp.zeros((H,), F32),
                "out_norm": {"scale": jnp.ones((d_inner,), F32)},
                "out_proj": _normal(ks[2], (d_inner, D), d_inner ** -0.5),
            },
        }

    layers = [mamba_layer(keys[i]) for i in range(k_mamba)]
    after = [mamba_layer(keys[k_mamba + 1 + i]) for i in range(j_mamba)]
    k_attn, k_mlp = jax.random.split(keys[-1])
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    shared = {
        "ln1": {"scale": jnp.ones((D,), F32)},
        "attn": {
            "wq": _normal(ka[0], (D, q_dim), D ** -0.5),
            "wk": _normal(ka[1], (D, kv_dim), D ** -0.5),
            "wv": _normal(ka[2], (D, kv_dim), D ** -0.5),
            "wo": _normal(ka[3], (q_dim, D), q_dim ** -0.5),
        },
        "ln2": {"scale": jnp.ones((D,), F32)},
        "mlp": {
            "w_in": _normal(km[0], (D, cfg["d_ff"]), D ** -0.5),
            "w_out": _normal(km[1], (cfg["d_ff"], D), cfg["d_ff"] ** -0.5),
            "w_gate": _normal(km[2], (D, cfg["d_ff"]), D ** -0.5),
        },
    }
    return {
        "embed": _normal(k_embed, (V, D), 0.02),
        "unembed": _normal(k_out, (D, V), D ** -0.5),
        "ln_f": {"scale": jnp.ones((D,), F32)},
        "stack": {
            "segments": [{"b0": jax.tree.map(lambda *t: jnp.stack(t), *layers)}, {}] + after,
            "shared_blk": shared,
        },
    }


# ---------------------------------------------------------------------------
# forward and loss of one sequence
# ---------------------------------------------------------------------------


def _rmsnorm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def forward_loss(cfg, params, tokens, mm):
    """Mean next-token cross entropy of one sequence ``tokens`` [S]."""
    D = cfg["d_model"]
    eps = cfg["rms_eps"]
    m = cfg["mamba"]
    d_inner = m["expand"] * D
    hd_m, N = m["head_dim"], m["state_dim"]
    H = d_inner // hd_m
    S = tokens.shape[0]

    def mamba(p, x):
        zxbcdt = mm(x, p["in_proj"])
        z = zxbcdt[:, :d_inner]
        xbc = zxbcdt[:, d_inner:2 * d_inner + 2 * N]
        dt_raw = zxbcdt[:, 2 * d_inner + 2 * N:]
        W = p["conv_w"].shape[0]
        padded = jnp.concatenate([jnp.zeros((W - 1, xbc.shape[1]), F32), xbc])
        conv = sum(padded[j:j + S] * p["conv_w"][j] for j in range(W)) + p["conv_b"]
        xbc = jax.nn.silu(conv)
        xs = xbc[:, :d_inner].reshape(S, H, hd_m)
        Bm, Cm = xbc[:, d_inner:d_inner + N], xbc[:, d_inner + N:]
        dt = jax.nn.softplus(dt_raw + p["dt_bias"])
        a = jnp.exp(dt * -jnp.exp(p["A_log"]))

        def step(h, inp):
            a_t, dt_t, b_t, c_t, x_t = inp
            h = a_t[:, None, None] * h + dt_t[:, None, None] * b_t[None, :, None] * x_t[:, None, :]
            return h, jnp.einsum("n,hnd->hd", c_t, h)

        # the recurrence over positions, run in stretches of REC_STRETCH whose
        # inner states are recomputed in the backward pass (memory only)
        seq = [v.reshape((S // REC_STRETCH, REC_STRETCH) + v.shape[1:])
               for v in (a, dt, Bm, Cm, xs)]
        stretch = jax.checkpoint(lambda h, inp: jax.lax.scan(step, h, inp))
        _, y = jax.lax.scan(stretch, jnp.zeros((H, N, hd_m), F32), seq)
        y = y.reshape(S, H, hd_m)
        y = (y + p["D"][None, :, None] * xs).reshape(S, d_inner)
        y = _rmsnorm(p["out_norm"]["scale"], y, eps) * jax.nn.silu(z)
        return mm(y, p["out_proj"])

    def attention(p, x):
        nh, hd = cfg["num_heads"], cfg["head_dim"]
        kvh = cfg["num_kv_heads"]
        q = mm(x, p["wq"]).reshape(S, nh, hd)
        k = mm(x, p["wk"]).reshape(S, kvh, hd)
        v = mm(x, p["wv"]).reshape(S, kvh, hd)
        freqs = cfg["rope_theta"] ** (-jnp.arange(0, hd, 2, dtype=F32) / hd)
        ang = jnp.arange(S, dtype=F32)[:, None, None] * freqs
        cos, sin = jnp.cos(ang), jnp.sin(ang)

        def rope(t):
            t1, t2 = jnp.split(t, 2, axis=-1)
            return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], axis=-1)

        q, k = rope(q), rope(k)
        k = jnp.repeat(k, nh // kvh, axis=1)
        v = jnp.repeat(v, nh // kvh, axis=1)
        scores = mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0)) * hd ** -0.5
        causal = jnp.tril(jnp.ones((S, S), bool))
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out = mm(probs, v.transpose(1, 0, 2)).transpose(1, 0, 2).reshape(S, nh * hd)
        return mm(out, p["wo"])

    gate = {"swiglu": jax.nn.silu,
            "geglu": lambda g: jax.nn.gelu(g, approximate=True)}[cfg["mlp_kind"]]

    def shared_block(p, x):
        x = x + attention(p["attn"], _rmsnorm(p["ln1"]["scale"], x, eps))
        h = _rmsnorm(p["ln2"]["scale"], x, eps)
        mlp = p["mlp"]
        return x + mm(gate(mm(h, mlp["w_gate"])) * mm(h, mlp["w_in"]), mlp["w_out"])

    mamba_block = jax.checkpoint(
        lambda p, h: h + mamba(p["mamba"], _rmsnorm(p["ln"]["scale"], h, eps)))
    x = params["embed"][tokens]
    segments = params["stack"]["segments"]
    k_mamba, _ = _layout(cfg)
    for i in range(k_mamba):
        x = mamba_block(jax.tree.map(lambda t: t[i], segments[0]["b0"]), x)
    x = jax.checkpoint(shared_block)(params["stack"]["shared_blk"], x)
    for layer in segments[2:]:
        x = mamba_block(layer, x)
    logits = mm(_rmsnorm(params["ln_f"]["scale"], x, eps), params["unembed"])
    lse = jax.scipy.special.logsumexp(logits[:-1], axis=-1)
    picked = jnp.take_along_axis(logits[:-1], tokens[1:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked)


# ---------------------------------------------------------------------------
# operations a token needs
# ---------------------------------------------------------------------------


def forward_flops_per_token(cfg, seq_len: int) -> float:
    """Forward FLOPs a token (``bench/lib/counts.py``): each Mamba2 layer,
    the shared block's attention and gated MLP where it sits in the
    pattern, and the output head."""
    D, m = cfg["d_model"], cfg["mamba"]
    k_mamba, j_mamba = _layout(cfg)
    mamba = counts.mamba2_layer_flops(D, m["state_dim"], m["head_dim"], m["expand"],
                                      m["conv_width"])
    shared = (counts.attention_flops(D, cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"],
                                     seq_len)
              + counts.gated_mlp_flops(D, cfg["d_ff"]))
    return (k_mamba + j_mamba) * mamba + shared + counts.output_head_flops(D, cfg["vocab_size"])
