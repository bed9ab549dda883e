"""Pipeline parallelism over the pod axis (GPipe-style fill–drain).

The multi-pod mesh adds a "pod" axis; inter-pod ICI/DCN links are the
slowest in the hierarchy, so the natural large-scale layout is pipeline
stages across pods (layer ranges per pod) with microbatches streaming
through — DP×TP inside each pod stays exactly as in the single-pod design.

Implementation: ``shard_map`` manual over ("pod",) with stage-stacked
parameters (leading dim = n_stages sharded over "pod"); activations step
stage-to-stage with ``lax.ppermute`` inside a scan over
``n_micro + n_stages - 1`` ticks (fill–drain schedule; bubble fraction
(n_stages-1)/(n_micro+n_stages-1)).  The backward pass differentiates
through the ppermute scan (its transpose is the reverse permute), giving
GPipe-correct gradients without hand-written send/recv.

This module is intentionally self-contained (dense decoder family) — it is
the PP *feature* demonstration lowered in the dry-run; fusing it with the
full trainer is configuration plumbing, not new machinery.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import transformer as tfm
from repro.models.layers import rms_norm


def stage_params_shape(cfg, n_stages: int):
    """Abstract stage-stacked block params: (n_stages, L/n_stages, ...)."""
    assert cfg.n_layers % n_stages == 0
    per = cfg.n_layers // n_stages

    def stack(leaf):
        return jax.ShapeDtypeStruct(
            (n_stages, per) + leaf.shape[1:], leaf.dtype
        )

    blocks = jax.eval_shape(
        lambda k: tfm._stack_init(
            lambda kk: tfm.init_decoder_block(kk, cfg), k, cfg.n_layers
        ),
        jax.random.key(0),
    )
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((n_stages, cfg.n_layers // n_stages)
                                       + l.shape[1:], l.dtype),
        blocks,
    )


def make_pipeline_forward(cfg, mesh: Mesh, n_micro: int):
    """Jittable pipelined forward + mean CE loss over microbatches.

    Args (abstract shapes):
      embed:   (V, d) replicated over pod (used by stage 0 / last stage)
      blocks:  stage-stacked block params, leading dim sharded over "pod"
      norm_w, lm_head: final norm + head (last stage)
      tokens, labels: (n_micro, B_micro, S) batch, replicated over pod
    """
    n_stages = mesh.shape["pod"]
    per = cfg.n_layers // n_stages

    def stage_apply(stage_blocks, x):
        positions = jnp.broadcast_to(
            jnp.arange(x.shape[1])[None], x.shape[:2]
        )

        def body(xx, bp):
            return tfm.decoder_block(bp, xx, cfg, positions), None

        x, _ = jax.lax.scan(body, x, stage_blocks)
        return x

    def local_fn(embed, blocks, norm_w, lm_head, tokens, labels):
        # blocks arrive as (1, per, ...) — this pod's stage
        stage_blocks = jax.tree.map(lambda b: b[0], blocks)
        stage_id = jax.lax.axis_index("pod")
        n_ticks = n_micro + n_stages - 1
        B, S = tokens.shape[1], tokens.shape[2]
        d = cfg.d_model

        def tick(carry, t):
            loss_sum, buf = carry
            # stage 0 ingests microbatch t (if in range)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            x_in = embed[tokens[mb_idx]]
            x = jnp.where(stage_id == 0, x_in, buf)
            y = stage_apply(stage_blocks, x.astype(x_in.dtype))
            # last stage computes the loss for the microbatch that entered
            # the pipe at tick t - (n_stages - 1)
            done_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            logits = (
                rms_norm(y, norm_w, cfg.norm_eps) @ lm_head
            ).astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(
                logits, labels[done_idx][..., None], axis=-1
            )[..., 0]
            mb_loss = jnp.mean(logz - gold)
            active = (t >= n_stages - 1) & (stage_id == n_stages - 1)
            loss_sum = loss_sum + jnp.where(active, mb_loss, 0.0)
            # shift activations one stage forward
            perm = [(i, i + 1) for i in range(n_stages - 1)]
            buf_next = jax.lax.ppermute(y, "pod", perm)
            return (loss_sum, buf_next), None

        buf0 = jnp.zeros((B, S, d), embed.dtype)
        (loss_sum, _), _ = jax.lax.scan(
            tick, (jnp.zeros((), jnp.float32), buf0),
            jnp.arange(n_ticks),
        )
        # broadcast the last stage's mean loss to every pod
        loss = jax.lax.psum(loss_sum, "pod") / n_micro
        return loss[None]

    pod_axis = ("pod",)
    in_specs = (
        P(*([None] * 2)),                     # embed replicated over pod
        jax.tree.map(lambda _: P("pod"), stage_params_shape(
            cfg, n_stages)),                  # stage dim over pod
        P(None),
        P(None, None),
        P(*([None] * 3)),                     # tokens (n_micro, B, S)
        P(*([None] * 3)),
    )
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=in_specs, out_specs=P("pod"),
        check_vma=False,
    )

    def loss_fn(embed, blocks, norm_w, lm_head, tokens, labels):
        # fn returns the (identical, psum'd) loss once per pod: average
        out = fn(embed, blocks, norm_w, lm_head, tokens, labels)
        return jnp.sum(out) / n_stages

    return jax.jit(loss_fn), stage_params_shape(cfg, n_stages)
