"""Vision Transformer classifier — the vision model family.

No reference counterpart (SURVEY.md §2.7 ships classic PS workloads only):
like the LM, this exists because a TPU framework is judged on model
coverage. Design shares the LM's conventions — functional params pytree,
bf16 activations with f32 norm statistics and logits, attention through
the framework kernels (Pallas flash on TPU when the token count tiles,
blockwise elsewhere), `make_train_step` producing a jitted
data-parallel SPMD step over a mesh.

Layout: images [B, H, W, C] -> non-overlapping patches -> linear embed +
learned positions + CLS token -> pre-norm encoder blocks (non-causal
attention) -> CLS readout head.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from harmony_tpu.models.common import (
    dense_init,
    flash_on_mesh,
    resolve_attn,
    rms_norm,
    validate_attn,
)
from harmony_tpu.models.pytree_trainer import PyTreeTrainer
from harmony_tpu.ops import blockwise_attention
from harmony_tpu.parallel.mesh import DATA_AXIS


@dataclasses.dataclass
class ViTConfig:
    image_size: int = 32
    patch_size: int = 4
    channels: int = 3
    num_classes: int = 10
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 4
    d_ff: int = 512
    dtype: Any = jnp.float32      # bf16 on hardware
    attn: str = "auto"            # "auto" | "flash" | "blockwise"

    def __post_init__(self):
        if self.image_size % self.patch_size:
            raise ValueError("patch_size must divide image_size")
        if self.d_model % self.n_heads:
            raise ValueError("d_model must divide by n_heads")
        validate_attn(self.attn)

    @property
    def num_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    @property
    def patch_dim(self) -> int:
        return self.patch_size * self.patch_size * self.channels

    @property
    def seq(self) -> int:
        return self.num_patches + 1  # + CLS


_norm = rms_norm


class ViT:
    def __init__(self, cfg: ViTConfig) -> None:
        self.cfg = cfg

    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        ks = jax.random.split(key, 4 + cfg.n_layers)
        d, f = cfg.d_model, cfg.d_ff

        layers = []
        for i in range(cfg.n_layers):
            lk = jax.random.split(ks[4 + i], 4)
            layers.append({
                "ln1": jnp.ones((d,), jnp.float32),
                "wqkv": dense_init(lk[0], (d, 3 * d)),
                "wo": dense_init(lk[1], (d, d)),
                "ln2": jnp.ones((d,), jnp.float32),
                "w1": dense_init(lk[2], (d, f)),
                "w2": dense_init(lk[3], (f, d)),
            })
        return {
            "embed": dense_init(ks[0], (cfg.patch_dim, d)),
            "pos": 0.02 * jax.random.normal(ks[1], (cfg.seq, d), jnp.float32),
            "cls": jnp.zeros((d,), jnp.float32),
            "ln_f": jnp.ones((d,), jnp.float32),
            "head": dense_init(ks[2], (d, cfg.num_classes)),
            "layers": layers,
        }

    def _patchify(self, images: jnp.ndarray) -> jnp.ndarray:
        cfg = self.cfg
        B = images.shape[0]
        p, n = cfg.patch_size, cfg.image_size // cfg.patch_size
        x = images.reshape(B, n, p, n, p, cfg.channels)
        return x.transpose(0, 1, 3, 2, 4, 5).reshape(B, n * n, cfg.patch_dim)

    def _attend(self, q, k, v):
        attn = resolve_attn(self.cfg.attn, self.cfg.seq,
                            head_dim=q.shape[3], dtype=q.dtype)
        fn = flash_on_mesh if attn == "flash" else blockwise_attention
        return fn(q, k, v, causal=False)

    def apply(self, params, images: jnp.ndarray) -> jnp.ndarray:
        """images [B, H, W, C] -> logits [B, num_classes]."""
        cfg = self.cfg
        B = images.shape[0]
        x = self._patchify(images.astype(cfg.dtype))
        x = x @ params["embed"].astype(cfg.dtype)
        cls = jnp.broadcast_to(params["cls"].astype(cfg.dtype),
                               (B, 1, cfg.d_model))
        x = jnp.concatenate([cls, x], axis=1) + params["pos"].astype(cfg.dtype)

        def to_heads(t):
            return t.reshape(B, cfg.seq, cfg.n_heads, -1).transpose(0, 2, 1, 3)

        for layer in params["layers"]:
            xn = _norm(x, layer["ln1"].astype(cfg.dtype))
            qkv = xn @ layer["wqkv"].astype(cfg.dtype)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            o = self._attend(to_heads(q), to_heads(k), to_heads(v))
            o = o.transpose(0, 2, 1, 3).reshape(B, cfg.seq, cfg.d_model)
            x = x + o @ layer["wo"].astype(cfg.dtype)
            xn = _norm(x, layer["ln2"].astype(cfg.dtype))
            x = x + jax.nn.gelu(xn @ layer["w1"].astype(cfg.dtype)) \
                @ layer["w2"].astype(cfg.dtype)
        x = _norm(x[:, 0], params["ln_f"].astype(cfg.dtype))  # CLS token
        return x.astype(jnp.float32) @ params["head"]          # f32 logits

    def loss(self, params, images, labels) -> jnp.ndarray:
        logits = self.apply(params, images)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, labels[:, None], axis=-1).mean()

    def accuracy(self, params, images, labels) -> jnp.ndarray:
        logits = self.apply(params, images)
        return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))


def make_train_step(model: ViT, mesh=None, learning_rate: float = 0.1,
                    donate: bool = True):
    """Jitted SGD step ``(params, images, labels) -> (params, loss)``;
    with ``mesh``, the batch shards over the data axis (params replicated,
    XLA inserts the gradient all-reduce at the batch contraction).
    ``donate`` (default, matching the LM steps) reuses the params buffer —
    callers must not read the old tree after a step; pass False when
    comparing trajectories from a shared initial tree."""
    dn = (0,) if donate else ()

    def step(params, images, labels):
        loss, grads = jax.value_and_grad(model.loss)(params, images, labels)
        new = jax.tree.map(
            lambda p, g: p - learning_rate * g.astype(p.dtype), params, grads
        )
        return new, loss

    if mesh is None:
        return jax.jit(step, donate_argnums=dn)
    batch_sh = NamedSharding(mesh, P(DATA_AXIS))
    rep = NamedSharding(mesh, P())

    def sharded(params, images, labels):
        images = jax.lax.with_sharding_constraint(images, batch_sh)
        labels = jax.lax.with_sharding_constraint(labels, batch_sh)
        return step(params, images, labels)

    return jax.jit(sharded, out_shardings=(rep, rep), donate_argnums=dn)


class ViTTrainer(PyTreeTrainer):
    """ViT through the framework's elastic-table substrate (see
    PyTreeTrainer for the row layout and optimizer-state sections). Batch =
    (images [B,H,W,C], labels [B])."""

    default_table_id = "vit-model"
    config_cls = ViTConfig

    def build_model(self, config: ViTConfig) -> "ViT":
        return ViT(config)

    def loss_on_batch(self, params, batch):
        images, labels = batch
        return self.model.loss(params, images, labels)

    def eval_metrics(self, params, batch):
        images, labels = batch
        return {
            "loss": self.model.loss(params, images, labels),
            "accuracy": self.model.accuracy(params, images, labels),
        }


def make_synthetic(
    n: int, cfg: Optional[ViTConfig] = None, seed: int = 0, **cfg_kwargs
) -> Tuple[np.ndarray, np.ndarray]:
    """Class-separable synthetic images: each class gets a random template,
    samples are noisy copies. Accepts flat config kwargs (image_size, ...)
    so JSON-serialized job configs can parameterize it; unknown keys (and
    kwargs alongside an explicit cfg) raise — a typo'd override must not
    silently revert to defaults."""
    if cfg is not None and cfg_kwargs:
        raise TypeError("pass either cfg= or flat config kwargs, not both")
    if cfg is None:
        unknown = set(cfg_kwargs) - set(ViTConfig.__dataclass_fields__)
        if unknown:
            raise TypeError(f"unknown make_synthetic kwargs {sorted(unknown)}")
        cfg = ViTConfig(**cfg_kwargs)
    rng = np.random.default_rng(seed)
    templates = rng.standard_normal(
        (cfg.num_classes, cfg.image_size, cfg.image_size, cfg.channels)
    ).astype(np.float32)
    y = rng.integers(0, cfg.num_classes, n).astype(np.int32)
    x = templates[y] + 0.5 * rng.standard_normal(
        (n, cfg.image_size, cfg.image_size, cfg.channels)
    ).astype(np.float32)
    return x, y
