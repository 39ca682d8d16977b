"""Plain reference for ``gpt2-124m``: forward, loss, gradients and Adam by
formula on a pytree — straight ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no kernels, no table, no
jobserver. It replays the job's first steps from the same seeded initial
parameters and the same batches and returns each step's loss.

The block is the repo's (models/transformer.py), which departs from the
published GPT-2 in two assumed ways (perf/configs/gpt2-124m.json): RMSNorm
(eps 1e-6) in place of LayerNorm, and no bias terms. Everything else is
GPT-2: learned positions, 12 heads of 64, a 4x GELU (tanh form) MLP,
pre-norm residual blocks, a final norm, the readout tied to the embedding.

``jax.checkpoint`` around a block bounds the float32 activations (the
[B, 12, 1024, 1024] score matrices of twelve layers would not fit beside
the logits); it recomputes, it does not change a number.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def init_params(app: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The trainer's seeded initial parameters, drawn the way
    ``TransformerLM.init`` draws them (same key splits, same scales)."""
    d, f, L = app["d_model"], app["d_ff"], app["n_layers"]
    k_emb, k_pos, *k_layers = jax.random.split(jax.random.PRNGKey(seed), 2 + L)

    def dense(key, shape):
        return jax.random.normal(key, shape, jnp.float32) * shape[0] ** -0.5

    layers = []
    for kl in k_layers:
        ks = jax.random.split(kl, 4)
        layers.append({
            "ln1": jnp.ones((d,), jnp.float32),
            "wqkv": dense(ks[0], (d, 3 * d)),
            "wo": dense(ks[1], (d, d)),
            "ln2": jnp.ones((d,), jnp.float32),
            "w1": dense(ks[2], (d, f)),
            "w2": dense(ks[3], (f, d)),
        })
    return {
        "embed": 0.02 * jax.random.normal(
            k_emb, (app["vocab_size"], d), jnp.float32),
        "pos": 0.02 * jax.random.normal(
            k_pos, (app["max_seq"], d), jnp.float32),
        "ln_f": jnp.ones((d,), jnp.float32),
        "layers": layers,
    }


def _rms_norm(x, w):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) * w


def _block(x, layer, n_heads: int):
    B, S, d = x.shape
    hd = d // n_heads
    q, k, v = jnp.split(_rms_norm(x, layer["ln1"]) @ layer["wqkv"], 3, axis=-1)
    heads = lambda t: t.reshape(B, S, n_heads, hd).transpose(0, 2, 1, 3)
    q, k, v = heads(q), heads(k), heads(v)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)
    x = x + o.transpose(0, 2, 1, 3).reshape(B, S, d) @ layer["wo"]
    h = jax.nn.gelu(_rms_norm(x, layer["ln2"]) @ layer["w1"], approximate=True)
    return x + h @ layer["w2"]


def loss_fn(params, tokens, n_heads: int):
    """Mean next-token cross-entropy of ``tokens[:, :-1] -> tokens[:, 1:]``."""
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    x = params["embed"][inp] + params["pos"][: inp.shape[1]]
    block = jax.checkpoint(_block, static_argnums=2)
    for layer in params["layers"]:
        x = block(x, layer, n_heads)
    logits = _rms_norm(x, params["ln_f"]) @ params["embed"].T
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, tgt[..., None], axis=-1).mean()


def replay(app: Dict[str, Any], data: Sequence[np.ndarray], batch: int,
           steps: int, seed: int, ablate: Optional[str] = None) -> List[float]:
    """Loss of each of the first ``steps`` steps (batch ``i`` is rows
    ``[i * batch, (i + 1) * batch)`` of the data set, cycling per epoch, as
    dolphin/data.py serves them unshuffled). ``ablate`` breaks the
    arithmetic on purpose — ``"no_m"`` / ``"no_v"`` drop an Adam moment —
    so that perf/tests can show the tolerance tells them apart."""
    if app.get("optimizer") != "adam":
        raise ValueError("this reference implements Adam only")
    tokens = np.asarray(data[0])
    nb = tokens.shape[0] // batch
    lr = float(app["step_size"])
    n_heads = int(app["n_heads"])

    @jax.jit
    def step(params, m, v, t, toks):
        loss, g = jax.value_and_grad(loss_fn)(params, toks, n_heads)
        tm = jax.tree.map
        m = tm(lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b, m, g)
        v = tm(lambda a, b: ADAM_B2 * a + (1 - ADAM_B2) * b * b, v, g)
        mhat = g if ablate == "no_m" else tm(
            lambda a: a / (1 - ADAM_B1 ** t), m)
        vhat = tm(jnp.ones_like, v) if ablate == "no_v" else tm(
            lambda a: a / (1 - ADAM_B2 ** t), v)
        params = tm(lambda p, a, b: p - lr * a / (jnp.sqrt(b) + ADAM_EPS),
                    params, mhat, vhat)
        return params, m, v, loss

    losses = []
    with jax.default_matmul_precision("highest"):
        params = init_params(app, seed)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        for i in range(steps):
            toks = jnp.asarray(tokens[(i % nb) * batch:(i % nb + 1) * batch])
            params, m, v, loss = step(params, m, v, jnp.float32(i + 1), toks)
            losses.append(float(loss))
    return losses
