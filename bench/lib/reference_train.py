"""The plain float32 training round that every training configuration's
reference shares: the gradient of the mean loss over the workers, AdamW,
and the server-to-worker broadcast (MARINA-P with the RotK partition). It
imports nothing of the program.

The model comes from the configuration's reference module
(``bench/configs/<model>_reference.py``), passed in as ``model``:

    init_params(cfg, key) -> params      the weights from the seed, in the
                                         program's layout (its leaf order
                                         also orders the broadcast's per-leaf
                                         randomness; the final norm's scale
                                         is ``params["ln_f"]["scale"]``)
    forward_loss(cfg, params, tokens, mm) -> loss
                                         mean next-token cross entropy of one
                                         sequence ``tokens`` [S], every matmul
                                         through ``mm(a, b)``

Everything is computed in float32 with ``highest`` matmul precision.
``matmul="fp8"`` rounds both operands of every matmul the model sends
through ``mm`` to float8 e4m3 with a per-tensor scale (gradients pass
straight through): the control that computes in the precision below the
configurations' bfloat16.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _fp8(x):
    """x rounded to float8 e4m3 under a per-tensor scale; identity gradient."""
    s = 448.0 / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    q = (x * s).astype(jnp.float8_e4m3fn).astype(F32) / s
    return x + jax.lax.stop_gradient(q - x)


def _matmul(kind: str):
    if kind == "f32":
        return lambda a, b: jnp.matmul(a, b, precision="highest")
    if kind == "fp8":
        return lambda a, b: jnp.matmul(_fp8(a), _fp8(b), precision="highest")
    raise ValueError(kind)


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


def make_round(model, cfg, traffic, matmul: str = "f32", fault: str = ""):
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
        lambda p, toks: jnp.mean(jnp.stack([model.forward_loss(cfg, p, s, mm) for s in toks])))

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


def init_state(model, cfg, traffic, key):
    x0 = model.init_params(cfg, key)
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


def run(model, cfg, traffic, tokens: List, key_init, step_keys: List, matmul: str = "f32",
        fault: str = "") -> Dict:
    """The reference's readings over ``len(step_keys)`` rounds from the
    seed: each round's loss, the first gradient's leaf norms, AdamW's first
    moment after the first round (``m1``, leaf by leaf on the host: (1 - b1)
    times the first gradient), and the leaf norms of the server's and of
    each worker's change over all the rounds."""
    rnd = make_round(model, cfg, traffic, matmul, fault)
    state = init_state(model, cfg, traffic, key_init)
    losses, gnorms, m1 = [], None, None
    for i, k in enumerate(step_keys):
        state, loss, g = rnd(state, tokens[i], k)
        losses.append(float(loss))
        if i == 0:
            gnorms = [float(x) for x in g]
            m1 = [np.asarray(l) for l in jax.tree.leaves(jax.device_get(state["m"]))]
    server, workers = jax.device_get((state["server"], state["workers"]))
    del state
    x0 = jax.device_get(model.init_params(cfg, key_init))
    return {"loss": losses, "grad": gnorms, "m1": m1,
            "change": [leaf_change_norms(server, x0)]
            + [leaf_change_norms(w, x0) for w in worker_trees(workers, cfg["train"]["workers"])]}
