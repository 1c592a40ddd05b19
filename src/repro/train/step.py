"""Train / serve step builders: loss, grad, update — pjit-ready.

``build_train_step`` returns a pure function suitable for
``jax.jit(..., in_shardings=..., out_shardings=...)`` — the launcher and the
dry-run both consume it.  Gradient-compression (EF-int8 over the "pod" axis)
is wired via shard_map with auto inner axes when enabled.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.models.model import Model
from repro.train.optimizer import AdamWConfig, adamw_update, init_opt_state


def cross_entropy(
    logits: jnp.ndarray,  # (B, S, V) fp32
    labels: jnp.ndarray,  # (B, S) int32
    *,
    z_loss: float = 1e-4,
) -> jnp.ndarray:
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    ce = (lse - gold).mean()
    if z_loss:
        ce = ce + z_loss * jnp.square(lse).mean()
    return ce


def build_loss_fn(model: Model, batch_part=None) -> Callable:
    def loss_fn(params, batch: dict[str, jnp.ndarray]):
        inputs = {k: v for k, v in batch.items() if k != "labels"}
        logits, _ = model.apply(
            params, **inputs, mode="train", batch_part=batch_part
        )
        return cross_entropy(logits, batch["labels"])

    return loss_fn


def build_train_step(
    model: Model,
    opt_cfg: AdamWConfig = AdamWConfig(),
    batch_part=None,
) -> Callable:
    """(params, opt_state, batch) -> (loss, params, opt_state)."""
    loss_fn = build_loss_fn(model, batch_part)

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        new_params, new_opt = adamw_update(grads, opt_state, opt_cfg)
        return loss, new_params, new_opt

    return train_step


def build_grad_accum_train_step(
    model: Model,
    opt_cfg: AdamWConfig = AdamWConfig(),
    num_microbatches: int = 4,
    batch_part=None,
) -> Callable:
    """Gradient accumulation over the leading batch dim, python-unrolled
    (keeps HLO FLOP accounting exact; microbatch counts are small)."""
    loss_fn = build_loss_fn(model, batch_part)

    def train_step(params, opt_state, batch):
        def micro(i):
            mb = jax.tree.map(
                lambda x: x.reshape(num_microbatches,
                                    x.shape[0] // num_microbatches,
                                    *x.shape[1:])[i],
                batch,
            )
            return jax.value_and_grad(loss_fn)(params, mb)

        loss, grads = micro(0)
        for i in range(1, num_microbatches):
            li, gi = micro(i)
            loss = loss + li
            grads = jax.tree.map(jnp.add, grads, gi)
        inv = 1.0 / num_microbatches
        loss = loss * inv
        grads = jax.tree.map(lambda g: g * inv, grads)
        new_params, new_opt = adamw_update(grads, opt_state, opt_cfg)
        return loss, new_params, new_opt

    return train_step


def build_serve_step(model: Model, batch_part=None) -> Callable:
    """(params, cache, tokens/embeds, pos) -> (logits, new_cache): one decode
    step against a KV cache/state at fill level ``pos``."""

    def serve_step(params, cache, batch, pos):
        logits, new_cache = model.apply(
            params, **batch, mode="decode", cache=cache, pos=pos,
            batch_part=batch_part,
        )
        return logits, new_cache

    return serve_step


def build_prefill_step(model: Model, cache_len: int, batch_part=None) -> Callable:
    def prefill_step(params, batch):
        first = next(iter(batch.values()))
        b = first.shape[0]
        cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            model.abstract_cache(b, cache_len),
        )
        logits, new_cache = model.apply(
            params, **batch, mode="prefill", cache=cache, pos=0,
            batch_part=batch_part,
        )
        return logits, new_cache  # (B, 1, V): model slices pre-head

    return prefill_step


def init_train_state(model: Model, key: jax.Array):
    params = model.init(key)
    return params, init_opt_state(params)


# ---------------------------------------------------------------------------
# Compressed-DP variant (EF-int8 across "pod")
# ---------------------------------------------------------------------------

def build_compressed_train_step(
    model: Model,
    mesh,
    param_pspecs,
    batch_pspecs,
    opt_cfg: AdamWConfig = AdamWConfig(),
) -> Callable:
    """Pod-local gradients + EF-int8 compressed all-reduce over "pod".

    The grad computation runs under shard_map manual on "pod" (auto on
    data/model), so each pod computes gradients on its local batch and only
    the int8 payload crosses pods.  state carries the error-feedback tree.
    """
    from jax.sharding import PartitionSpec as P

    from repro.train.compression import ef_int8_psum

    loss_fn = build_loss_fn(model)

    def pod_local(params, batch, err):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        flat_g, tdef = jax.tree.flatten(grads)
        flat_e = jax.tree.leaves(err)
        synced = [
            ef_int8_psum(g, e, "pod") for g, e in zip(flat_g, flat_e)
        ]
        grads = jax.tree.unflatten(tdef, [s[0] for s in synced])
        new_err = jax.tree.unflatten(tdef, [s[1] for s in synced])
        loss = jax.lax.pmean(loss, "pod")
        return loss, grads, new_err

    # Partial-manual shard_map: specs mention ONLY the manual "pod" axis;
    # the data/model shardings of params/batch ride through as auto axes
    # governed by the outer jit's in_shardings.
    def pod_only(spec):
        def fix(part):
            parts = part if isinstance(part, (tuple, list)) else (part,)
            return "pod" if "pod" in parts else None

        return P(*(fix(p) for p in spec))

    is_p = lambda x: isinstance(x, P)  # noqa: E731
    rep = jax.tree.map(lambda _: P(), param_pspecs, is_leaf=is_p)

    def train_step(params, opt_state, err_state, batch):
        wrapped = jax.shard_map(
            pod_local,
            mesh=mesh,
            in_specs=(
                rep,
                jax.tree.map(pod_only, batch_pspecs, is_leaf=is_p),
                rep,
            ),
            out_specs=(P(), rep, rep),
            check_vma=False,
            axis_names=frozenset({"pod"}),
        )
        loss, grads, new_err = wrapped(params, batch, err_state)
        new_params, new_opt = adamw_update(grads, opt_state, opt_cfg)
        return loss, new_params, new_opt, new_err

    return train_step
