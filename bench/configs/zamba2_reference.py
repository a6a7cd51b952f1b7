"""Plain float32 reference of the zamba2 configuration and of its training
round: the forward pass and loss, the gradient, AdamW, and the server-to-
worker broadcast (MARINA-P with the RotK partition). It imports nothing of
the program.

Everything is computed in float32 with ``highest`` matmul precision; the
Mamba2 state is carried by the plain recurrence over positions
(h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T, y_t = C_t h_t + D x_t), not by
chunks. Attention is softmax over the full causal score matrix.

Weights are made from the seed by the draws that the configuration's
initialiser defines, in its layout: the leading run of Mamba2 layers is
stacked on a leading axis (``stack/segments/0/b0``), the shared block has
one set of weights (``stack/shared_blk``), and each Mamba2 layer after it
has a segment of its own. The leaf order of that layout also orders the
broadcast's per-leaf randomness.

``matmul="fp8"`` rounds both operands of every projection, attention and
output-head matmul of the forward pass to float8 e4m3 with a per-tensor
scale (gradients pass straight through): the control that computes in the
precision below the configuration's bfloat16.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

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


def _fp8(x):
    """x rounded to float8 e4m3 under a per-tensor scale; identity gradient."""
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * s).astype(jnp.float8_e4m3fn).astype(F32) / s
    return x + jax.lax.stop_gradient(q - x)


def _rmsnorm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _forward_loss(cfg, params, tokens, mm):
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


def _matmul(kind: str):
    if kind == "f32":
        return lambda a, b: jnp.matmul(a, b, precision="highest")
    if kind == "fp8":
        return lambda a, b: jnp.matmul(_fp8(a), _fp8(b), precision="highest")
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# one training round
# ---------------------------------------------------------------------------


def _adamw(t, g, m, v, count):
    count = count + 1
    m = jax.tree.map(lambda m_, g_: t["b1"] * m_ + (1 - t["b1"]) * g_, m, g)
    v = jax.tree.map(lambda v_, g_: t["b2"] * v_ + (1 - t["b2"]) * g_ * g_, v, g)
    c1 = 1 - t["b1"] ** count.astype(F32)
    c2 = 1 - t["b2"] ** count.astype(F32)
    return m, v, count, lambda p, m_, v_: p - t["lr"] * (
        (m_ / c1) / (jnp.sqrt(v_ / c2) + t["eps"]) + t["weight_decay"] * p)


def _marina_perm(key, x_new, x_old, replicas, n):
    """Algorithm 2's broadcast, RotK partition: with probability 1/n every
    replica becomes x_new; otherwise worker w adds n times the entries j of
    each leaf's change with j mod n == (w + r) mod n, r drawn per leaf."""
    k_bern, k_comp = jax.random.split(key)
    sync = jax.random.bernoulli(k_bern, 1.0 / n)
    out = []
    for li, (xn, xo, wr) in enumerate(zip(jax.tree.leaves(x_new), jax.tree.leaves(x_old),
                                          jax.tree.leaves(replicas))):
        r = jax.random.randint(jax.random.fold_in(k_comp, li), (), 0, n)
        j = jnp.arange(xn.size) % n
        masks = jnp.stack([(j == (w + r) % n).reshape(xn.shape) for w in range(n)])
        upd = wr + jnp.where(masks, (xn - xo)[None] * n, 0.0)
        out.append(jnp.where(sync, jnp.broadcast_to(xn[None], wr.shape), upd))
    return jax.tree.unflatten(jax.tree.structure(replicas), out)


def make_round(cfg, traffic, matmul: str = "f32", fault: str = ""):
    """``round(state, tokens [W, B, S], key) -> (state, loss, grad_leaf_norms)``
    under the MARINA-P perm downlink (the only one the reference covers).
    ``state`` is ``{"server", "m", "v", "count", "workers"}``; ``workers`` has a
    leading worker axis.

    ``fault`` plants one of the faults the comparison must catch, for the
    control's readings: ``half_batch`` (every worker trains on worker 0's
    rows, so the mean is over half the batch) or ``altered`` (the final
    norm's scale moved by 1e-2 after the update)."""
    t = cfg["train"]
    n = t["workers"]
    mm = _matmul(matmul)
    if traffic["downlink"] != "marina:perm":
        raise ValueError(f"the reference covers marina:perm, not {traffic['downlink']}")
    loss_grad = jax.value_and_grad(
        lambda p, toks: jnp.mean(jnp.stack([_forward_loss(cfg, p, s, mm) for s in toks])))

    def rnd(state, tokens, key):
        with jax.default_matmul_precision("highest"):
            g_sum, losses = None, []
            for w in range(n):
                at = jax.tree.map(lambda r: r[w], state["workers"])
                loss, g = loss_grad(at, tokens[0 if fault == "half_batch" else w])
                losses.append(loss)
                g_sum = g if g_sum is None else jax.tree.map(jnp.add, g_sum, g)
            g = jax.tree.map(lambda s: s / n, g_sum)
            m, v, count, upd = _adamw(t, g, state["m"], state["v"], state["count"])
            x_new = jax.tree.map(upd, state["server"], m, v)
            if fault == "altered":
                x_new = dict(x_new, ln_f={"scale": x_new["ln_f"]["scale"] + 1e-2})
            workers = _marina_perm(key, x_new, state["server"], state["workers"], n)
            gnorms = [jnp.sqrt(jnp.sum(l * l)) for l in jax.tree.leaves(g)]
        new = {"server": x_new, "m": m, "v": v, "count": count, "workers": workers}
        return new, jnp.mean(jnp.stack(losses)), gnorms

    return jax.jit(rnd, donate_argnums=0)


def init_state(cfg, traffic, key):
    x0 = init_params(cfg, key)
    zeros = jax.tree.map(jnp.zeros_like, x0)
    n = cfg["train"]["workers"]
    workers = jax.tree.map(lambda t: jnp.broadcast_to(t[None], (n,) + t.shape), x0)
    return {"server": x0, "m": zeros, "v": jax.tree.map(jnp.copy, zeros),
            "count": jnp.zeros((), jnp.int32), "workers": workers}


def leaf_change_norms(tree, x0) -> List[float]:
    """Per leaf, the norm of ``tree - x0``, summed in float64 on the host."""
    return [float(np.sqrt(np.sum((np.asarray(a, np.float64) - np.asarray(b, np.float64)) ** 2)))
            for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(x0))]


def worker_trees(workers, n: int) -> List:
    """The workers' replicas one by one."""
    return [jax.tree.map(lambda t: t[w], workers) for w in range(n)]


def run(cfg, traffic, tokens: List, key_init, step_keys: List, matmul: str = "f32",
        fault: str = "") -> Dict:
    """The reference's readings over ``len(step_keys)`` rounds from the
    seed: each round's loss, the first gradient's leaf norms, AdamW's first
    moment after the first round (``m1``, leaf by leaf on the host: (1 - b1)
    times the first gradient), and the leaf norms of the server's and of
    each worker's change over all the rounds."""
    rnd = make_round(cfg, traffic, matmul, fault)
    state = init_state(cfg, traffic, key_init)
    losses, gnorms, m1 = [], None, None
    for i, k in enumerate(step_keys):
        state, loss, g = rnd(state, tokens[i], k)
        losses.append(float(loss))
        if i == 0:
            gnorms = [float(x) for x in g]
            m1 = [np.asarray(l) for l in jax.tree.leaves(jax.device_get(state["m"]))]
    server, workers = jax.device_get((state["server"], state["workers"]))
    del state
    x0 = jax.device_get(init_params(cfg, key_init))
    return {"loss": losses, "grad": gnorms, "m1": m1,
            "change": [leaf_change_norms(server, x0)]
            + [leaf_change_norms(w, x0) for w in worker_trees(workers, cfg["train"]["workers"])]}
