"""Public model API: init / train_step / serve steps / input_specs.

``input_specs(cfg, shape)`` yields ShapeDtypeStruct stand-ins for every
input of the step function named by the shape kind — the dry-run lowers
against these with zero allocation:

  train_*    -> train_step(params, opt_state, batch)
  prefill_*  -> prefill(params, batch)
  decode_* / long_* -> serve_decode(params, caches, token, index)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.base import ArchConfig
from repro.models.transformer import (decode_epoch, decode_step, encode,
                                      init_caches, init_lm, lm_forward,
                                      prefill_chunk)
from repro.optim import adamw


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


def shape_applicable(cfg: ArchConfig, shape: ShapeSpec) -> Tuple[bool, str]:
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return False, "full-attention arch: 500k ctx needs sub-quadratic attn"
    return True, ""


# --------------------------------------------------------------- steps --
def init_params(cfg: ArchConfig, key=None):
    key = key if key is not None else jax.random.PRNGKey(0)
    return init_lm(key, cfg)


def mask_padded_logits(logits, cfg: ArchConfig):
    """Neutralize the Megatron-style vocab-padding rows (base.py
    padded_vocab) so they never win argmax / enter logsumexp."""
    if cfg.padded_vocab == cfg.vocab_size:
        return logits
    ids = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return jnp.where(ids < cfg.vocab_size, logits, -1e30)


def loss_fn(params, batch, cfg: ArchConfig):
    prefix = batch.get("embeds_prefix")
    logits, aux = lm_forward(params, batch["tokens"], cfg,
                             embeds_prefix=prefix, remat=True)
    # next-token CE over the token positions only (prefix positions are
    # conditioning context)
    if prefix is not None and cfg.family != "encdec":
        logits = logits[:, prefix.shape[1]:, :]
    labels = batch["labels"]
    logits = mask_padded_logits(logits[:, :-1, :].astype(jnp.float32), cfg)
    tgt = labels[:, 1:]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    ce = (logz - gold).mean()
    return ce + 0.01 * aux, {"ce": ce, "aux": aux}


def make_train_step(cfg: ArchConfig, opt_cfg: Optional[adamw.AdamWConfig] = None,
                    microbatches: int = 1):
    """Training step; ``microbatches > 1`` accumulates gradients over a
    lax.scan of micro-steps — each micro-step's gradient reduction can
    overlap the next micro-step's compute (XLA schedules the per-bucket
    all-reduces asynchronously), the standard compute/comm overlap trick
    for large global batches."""
    opt_cfg = opt_cfg or adamw.AdamWConfig()
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, batch):
        if microbatches == 1:
            (loss, metrics), grads = grad_fn(params, batch, cfg)
        else:
            def split(x):
                return x.reshape((microbatches, x.shape[0] // microbatches)
                                 + x.shape[1:])
            micro = jax.tree_util.tree_map(split, batch)

            def acc_step(carry, mb):
                (loss, metrics), g = grad_fn(params, mb, cfg)
                gsum = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(jnp.float32), carry[0], g)
                return (gsum, carry[1] + loss), metrics

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum), ms = jax.lax.scan(acc_step, (zeros, 0.0), micro)
            grads = jax.tree_util.tree_map(lambda g: g / microbatches, gsum)
            loss = lsum / microbatches
            metrics = jax.tree_util.tree_map(lambda x: x.mean(), ms)
        params, opt_state, opt_metrics = adamw.update(
            opt_cfg, grads, opt_state, params)
        return params, opt_state, {"loss": loss, **metrics, **opt_metrics}

    return train_step


def make_prefill(cfg: ArchConfig, serve: bool = False):
    """One-shot prefill.  ``serve=True`` selects serving semantics —
    drop-free MoE buckets and the unrolled shallow-stack group loop,
    bit-identical to the chunked serving prefill
    (:func:`repro.models.transformer.prefill_chunk`).  The default
    keeps the scan-over-layers HLO and the dropping MoE capacity
    factor: the dry-run dimensioning path models the same workload it
    always did."""
    def prefill(params, batch, plan=None):
        prefix = batch.get("embeds_prefix")
        logits, _ = lm_forward(params, batch["tokens"], cfg,
                               embeds_prefix=prefix, plan=plan,
                               serve_prefill=serve)
        return logits[:, -1, :]
    return prefill


def _greedy_next_token(cfg: ArchConfig):
    """Greedy decode feedback: logits [B, 1, V] -> next token [B]."""
    def next_token(logits):
        logits = mask_padded_logits(logits, cfg)
        return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
    return next_token


def make_prefill_chunk(cfg: ArchConfig):
    """Cache-resuming prefill chunk for the continuous-batching server:
    writes one prompt chunk's KV/SSM state into the live decode caches
    at position ``index`` and returns (next_token [B, 1] — the greedy
    token from the chunk's last position, meaningful only for the final
    chunk of a prompt — and the updated caches).  ``kv_len`` is static;
    jit with ``static_argnames=("kv_len",)`` and ``donate_argnums=(1,)``
    so each (arch, chunk_len, kv_len) triple compiles once and the
    caches update in place across the chunk sequence.  After the last
    chunk the tenant flips to decode with no recompile: the decode step
    consumes the same cache buffers and the returned token."""
    next_token = _greedy_next_token(cfg)

    def serve_prefill_chunk(params, caches, tokens, index, enc_out=None,
                            kv_len=None):
        logits, caches = prefill_chunk(params, tokens, caches, index, cfg,
                                       enc_out=enc_out, kv_len=kv_len)
        return next_token(logits)[:, None], caches
    return serve_prefill_chunk


def make_decode_epoch(cfg: ArchConfig):
    """K-token serving epoch: one on-device lax.scan over the decode
    step with greedy token feedback.  ``plan`` and ``k`` are static —
    jit with ``static_argnames=("plan", "k")`` and
    ``donate_argnums=(1,)`` so each (tenant, plan, k) triple compiles
    once and the KV/SSM caches are updated in place across the epoch.
    Returns (tokens [B, k], caches); bit-identical to k sequential
    ``make_decode_step`` calls feeding each token back in."""
    next_token = _greedy_next_token(cfg)

    def serve_decode_epoch(params, caches, token, index, enc_out=None,
                           plan=None, k=1, kv_len=None):
        return decode_epoch(params, token, caches, index, cfg, k,
                            next_token_fn=next_token, enc_out=enc_out,
                            plan=plan, kv_len=kv_len)
    return serve_decode_epoch


def make_decode_epoch_batched(cfg: ArchConfig):
    """Plan-bucketed batched epoch: tenants of one arch sharing a
    KernelPlan stack along a leading tenant axis and decode as ONE
    device call (``jax.vmap`` of the epoch scan), so one compile-cache
    entry serves the whole bucket and one dispatch replaces
    n_tenants x k step dispatches.

    params / caches / token / index all carry a leading tenant axis
    ([n, ...]); ``enc_out`` (when given) too.  Returns
    (tokens [n, B, k], caches [n, ...]); each tenant slice is
    bit-identical to its unbatched epoch (tests/test_serve_pipeline.py).
    """
    next_token = _greedy_next_token(cfg)

    def serve_decode_epoch_batched(params, caches, token, index,
                                   enc_out=None, plan=None, k=1,
                                   kv_len=None):
        def one(params, caches, token, index, enc_out):
            return decode_epoch(params, token, caches, index, cfg, k,
                                next_token_fn=next_token, enc_out=enc_out,
                                plan=plan, kv_len=kv_len)
        enc_axis = None if enc_out is None else 0
        return jax.vmap(one, in_axes=(0, 0, 0, 0, enc_axis)
                        )(params, caches, token, index, enc_out)
    return serve_decode_epoch_batched


def make_decode_step(cfg: ArchConfig):
    """One-token serving step.  ``plan`` is a static
    core.plan.KernelPlan: jit it with ``static_argnames=("plan",)`` so
    each (tenant, plan) pair compiles once and the allocator's grant
    decides which Pallas kernel variant the step executes.  ``kv_len``
    (static) bounds the attention read to the cache's live prefix."""
    def serve_decode(params, caches, token, index, enc_out=None, plan=None,
                     kv_len=None):
        logits, caches = decode_step(params, token, caches, index, cfg,
                                     enc_out=enc_out, plan=plan,
                                     kv_len=kv_len)
        logits = mask_padded_logits(logits, cfg)
        return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32), caches
    return serve_decode


# ---------------------------------------------------------- input specs --
def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def batch_specs(cfg: ArchConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """ShapeDtypeStructs for the data batch of a train/prefill step."""
    B, S = shape.global_batch, shape.seq_len
    d = cfg.d_model
    out: Dict[str, Any] = {}
    if cfg.family == "encdec":
        out["embeds_prefix"] = _sds((B, cfg.enc_len, d), jnp.float32)
        out["tokens"] = _sds((B, S), jnp.int32)
        out["labels"] = _sds((B, S), jnp.int32)
        return out
    if cfg.family == "vlm":
        P = cfg.num_patches
        out["embeds_prefix"] = _sds((B, P, d), jnp.float32)
        out["tokens"] = _sds((B, S - P), jnp.int32)
        out["labels"] = _sds((B, S - P), jnp.int32)
        return out
    out["tokens"] = _sds((B, S), jnp.int32)
    out["labels"] = _sds((B, S), jnp.int32)
    return out


def cache_specs(cfg: ArchConfig, batch: int, max_len: int,
                kv_dtype: Optional[str] = None):
    return jax.eval_shape(
        lambda: init_caches(None, cfg, batch, max_len, kv_dtype=kv_dtype))


def param_specs_shapes(cfg: ArchConfig):
    return jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))


def opt_specs_shapes(params_shapes):
    return jax.eval_shape(adamw.init, params_shapes)


# Keyed on (arch config, batch, max_len, kv_dtype, group): the AOT
# precompiler asks for the same handful of spec tuples on every warmup
# round, and each construction costs two eval_shape traces.  Specs are
# immutable ShapeDtypeStruct trees, so sharing is safe.
_EPOCH_SPEC_CACHE: Dict[tuple, tuple] = {}


def decode_epoch_input_specs(cfg: ArchConfig, batch: int, max_len: int,
                             kv_dtype: Optional[str] = None,
                             group: Optional[int] = None):
    """(params, caches, token, index, enc_out) ShapeDtypeStructs for one
    fused-epoch work item — the abstract arguments the serving layer's
    AOT precompiler lowers fused epoch programs against.  ``group`` adds
    the leading tenant axis of a plan-bucketed item
    (:func:`make_decode_epoch_batched`)."""
    ck = (cfg, batch, max_len, kv_dtype, group)
    hit = _EPOCH_SPEC_CACHE.get(ck)
    if hit is not None:
        return hit
    params = param_specs_shapes(cfg)
    caches = cache_specs(cfg, batch, max_len, kv_dtype=kv_dtype)
    token = _sds((batch, 1), jnp.int32)
    index = _sds((), jnp.int32)
    enc = (_sds((batch, cfg.enc_len, cfg.d_model), cfg.jdtype)
           if cfg.family == "encdec" else None)
    if group is not None:
        def stack(x):
            return _sds((group,) + tuple(x.shape), x.dtype)
        params = jax.tree_util.tree_map(stack, params)
        caches = jax.tree_util.tree_map(stack, caches)
        token = stack(token)
        index = _sds((group,), jnp.int32)
        enc = stack(enc) if enc is not None else None
    _EPOCH_SPEC_CACHE[ck] = (params, caches, token, index, enc)
    return params, caches, token, index, enc


def input_specs(cfg: ArchConfig, shape: ShapeSpec) -> Dict[str, Any]:
    """All step-function inputs as ShapeDtypeStructs, keyed by arg name."""
    params = param_specs_shapes(cfg)
    if shape.kind == "train":
        return {"params": params,
                "opt_state": opt_specs_shapes(params),
                "batch": batch_specs(cfg, shape)}
    if shape.kind == "prefill":
        return {"params": params, "batch": batch_specs(cfg, shape)}
    # decode: one new token against caches of length seq_len
    B = shape.global_batch
    out = {"params": params,
           "caches": cache_specs(cfg, B, shape.seq_len),
           "token": _sds((B, 1), jnp.int32),
           "index": _sds((), jnp.int32)}
    if cfg.family == "encdec":
        out["enc_out"] = _sds((B, cfg.enc_len, cfg.d_model), cfg.jdtype)
    return out
