"""Composable LM assembly: dense / MoE / SSM / hybrid decoder stacks and
the Whisper-style encoder-decoder, with scan-over-layers (compact HLO,
essential for the 512-device dry-run) and optional remat.

Layer stacks are homogeneous *groups* so params stack cleanly for
``lax.scan``:
  dense/moe : one group = 1 x (attn + ffn/moe)          x num_layers
  ssm       : one group = 1 x mamba2                    x num_layers
  hybrid    : one group = (attn_every-1) x mamba2 + 1 x (attn + ffn)
              x (num_layers / attn_every)   (Zamba2-style shared attn)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.sharding import shard_hint
from repro.models.attention import init_attention, init_kv_cache, mha
from repro.models.base import ArchConfig
from repro.models.layers import (Params, embed, ffn, init_embedding, init_ffn,
                                 init_norm, rms_norm, unembed)
from repro.models.moe import init_moe, moe_apply
from repro.models.ssm import (init_mamba2, init_ssm_state, mamba2_forward,
                              ssd_decode_step)


# Lowering knob: the dry-run sets this to 2 to measure per-layer HLO
# cost via the unroll-delta method (cost_analysis counts a while-loop
# body once regardless of trip count; see launch/hlo_analysis.py).
_SCAN_UNROLL = 1
_REMAT_POLICY = "full"   # "full" | "dots" (save matmul outputs)
# Decode-path group loop: stacks this shallow are unrolled to
# straight-line code so cache writes are in-place dynamic-update-slices
# on the carried buffer (see decode_step docstring); deeper stacks keep
# the compact scan-over-layers HLO.
_DECODE_UNROLL_MAX_GROUPS = 8


def ssm_state_in_place(cfg: ArchConfig) -> bool:
    """Whether :func:`decode_step` updates this model's SSM state in
    place in its stack, through the Pallas decode kernel: a pure SSM
    stack deep enough for the stacked-cache scan.  Shallow stacks
    update per-group buffers in jnp; hybrid stacks keep their SSM
    state inside each attention group."""
    return (cfg.family == "ssm"
            and num_groups(cfg) > _DECODE_UNROLL_MAX_GROUPS)


def set_scan_unroll(u: int) -> None:
    global _SCAN_UNROLL
    _SCAN_UNROLL = max(1, int(u))


def set_remat_policy(p: str) -> None:
    global _REMAT_POLICY
    assert p in ("full", "dots"), p
    _REMAT_POLICY = p


# ---------------------------------------------------------------- init --
def _stack_init(fn, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(fn)(keys)


def init_dense_layer(key, cfg: ArchConfig) -> Params:
    k1, k2 = jax.random.split(key)
    p = {"ln1": init_norm(cfg.d_model, cfg.jdtype),
         "attn": init_attention(k1, cfg),
         "ln2": init_norm(cfg.d_model, cfg.jdtype)}
    p["mlp"] = init_moe(k2, cfg) if cfg.is_moe else init_ffn(
        k2, cfg.d_model, cfg.d_ff, cfg.jdtype)
    return p


def init_ssm_layer(key, cfg: ArchConfig) -> Params:
    return {"ln1": init_norm(cfg.d_model, cfg.jdtype),
            "mamba": init_mamba2(key, cfg)}


def init_hybrid_group(key, cfg: ArchConfig) -> Params:
    # Zamba2-style: the attention block is SHARED across all groups (one
    # set of weights, stored once at the top level) — only the Mamba2
    # layers are per-group.
    n_ssm = cfg.attn_every - 1
    return {"ssm": _stack_init(lambda k: init_ssm_layer(k, cfg), key, n_ssm)}


def num_groups(cfg: ArchConfig) -> int:
    if cfg.family == "hybrid":
        assert cfg.num_layers % cfg.attn_every == 0
        return cfg.num_layers // cfg.attn_every
    return cfg.num_layers


def init_lm(key, cfg: ArchConfig) -> Params:
    ke, kl, ku = jax.random.split(key, 3)
    if cfg.family == "hybrid":
        group_fn = lambda k: init_hybrid_group(k, cfg)
    elif cfg.family == "ssm":
        group_fn = lambda k: init_ssm_layer(k, cfg)
    else:
        group_fn = lambda k: init_dense_layer(k, cfg)
    params = {
        "embed": init_embedding(ke, cfg.padded_vocab, cfg.d_model, cfg.jdtype),
        "layers": _stack_init(group_fn, kl, num_groups(cfg)),
        "final_norm": init_norm(cfg.d_model, cfg.jdtype),
    }
    if cfg.family == "hybrid":
        params["shared_attn"] = init_dense_layer(
            jax.random.fold_in(kl, 7), cfg)
    if cfg.family == "encdec":
        kenc, kx = jax.random.split(ku)
        params["encoder"] = {
            "layers": _stack_init(lambda k: init_dense_layer(k, cfg), kenc,
                                  cfg.enc_layers),
            "final_norm": init_norm(cfg.d_model, cfg.jdtype),
        }
        params["xattn"] = _stack_init(
            lambda k: {"ln": init_norm(cfg.d_model, cfg.jdtype),
                       "attn": init_attention(k, cfg)},
            kx, num_groups(cfg))
    return params


# ------------------------------------------------------------- blocks --
def _dense_block(p: Params, x, cfg: ArchConfig, *, causal=True, kv_cache=None,
                 cache_index=None, kv_len=None, positions=None, xattn_kv=None,
                 xp=None, plan=None, moe_fast=True, moe_drop_free=False):
    h, new_cache = mha(p["attn"], rms_norm(p["ln1"], x, cfg.norm_eps), cfg,
                       causal=causal, kv_cache=kv_cache,
                       cache_index=cache_index, kv_len=kv_len,
                       positions=positions,
                       attn_plan=plan.attn if plan is not None else None)
    x = x + h
    aux = 0.0
    if xp is not None:  # cross-attention (enc-dec decoder)
        hx, _ = mha(xp["attn"], rms_norm(xp["ln"], x, cfg.norm_eps), cfg,
                    causal=False, xattn_kv=xattn_kv)
        x = x + hx
    y = rms_norm(p["ln2"], x, cfg.norm_eps)
    ffn_plan = plan.ffn if plan is not None else None
    if cfg.is_moe:
        out, aux = moe_apply(p["mlp"], y, cfg, plan=ffn_plan,
                             decode_fast=moe_fast,
                             drop_free=moe_drop_free)
    else:
        out = ffn(p["mlp"], y, plan=ffn_plan)
    return x + out, new_cache, aux


def _ssm_block(p: Params, x, cfg: ArchConfig, state=None, decode=False,
               plan=None, layer=None):
    y = rms_norm(p["ln1"], x, cfg.norm_eps)
    if decode:
        out, new_state = ssd_decode_step(p["mamba"], y, cfg, state,
                                         layer=layer)
    else:
        chunk = plan.ssm_chunk if plan is not None else None
        out, new_state = mamba2_forward(p["mamba"], y, cfg, state,
                                        chunk=chunk)
    return x + out, new_state


# ------------------------------------------------------------ forward --
def lm_forward(params: Params, tokens: jnp.ndarray, cfg: ArchConfig, *,
               embeds_prefix: Optional[jnp.ndarray] = None,
               remat: bool = False,
               plan=None,
               serve_prefill: bool = False,
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Training / prefill forward.  tokens: [B, S] -> logits [B, S, V].

    ``embeds_prefix`` [B, P, d] (VLM patches / audio frames) is
    prepended to the token embeddings; logits cover the full sequence.
    ``plan`` (a static core.plan.KernelPlan) executes FFN/attention/SSD
    through the plan-lowered Pallas kernels.  Returns (logits,
    moe_aux_loss).

    ``serve_prefill=True`` selects the SERVING one-shot-prefill
    semantics: drop-free MoE buckets (the kept-token set must not
    depend on how a prompt is chunked — :func:`repro.models.moe
    .moe_apply`) and, for shallow stacks
    (<= ``_DECODE_UNROLL_MAX_GROUPS`` groups, no remat), the same
    unrolled group loop the decode/prefill-chunk paths use — so
    ``make_prefill(cfg, serve=True)`` is bit-identical to the cached
    chunked prefill (:func:`prefill_chunk`): same per-group param
    slices, same float association.  The default keeps the compact
    scan-over-layers HLO and the dropping MoE capacity factor — the
    dry-run dimensioning and training paths are unchanged.
    """
    x = embed(params["embed"], tokens)
    if embeds_prefix is not None:
        x = jnp.concatenate([embeds_prefix.astype(x.dtype), x], axis=1)
    x = shard_hint(x, ("data", None, None))
    S = x.shape[1]
    positions = jnp.arange(S)[None, :]
    enc_out = None
    if cfg.family == "encdec":
        enc_out = encode(params, embeds_prefix if embeds_prefix is not None
                         else x, cfg)
        x = embed(params["embed"], tokens)  # decoder stream = tokens only
        x = shard_hint(x, ("data", None, None))
        positions = jnp.arange(x.shape[1])[None, :]

    def group_fn(carry, gp):
        x, aux = carry
        if cfg.family == "hybrid":
            def ssm_step(xc, sp):
                y, _ = _ssm_block(sp, xc, cfg, plan=plan)
                return y, None
            x, _ = jax.lax.scan(ssm_step, x, gp["ssm"],
                                unroll=max(1, cfg.attn_every - 1))
            x, _, a = _dense_block(params["shared_attn"], x, cfg,
                                   positions=positions, plan=plan,
                                   moe_fast=False,
                                   moe_drop_free=serve_prefill)
            aux = aux + a
        elif cfg.family == "ssm":
            x, _ = _ssm_block(gp, x, cfg, plan=plan)
        elif cfg.family == "encdec":
            lp, xp = gp
            x, _, a = _dense_block(lp, x, cfg, positions=positions,
                                   xattn_kv=enc_out, xp=xp, plan=plan,
                                   moe_fast=False,
                                   moe_drop_free=serve_prefill)
            aux = aux + a
        else:
            x, _, a = _dense_block(gp, x, cfg, positions=positions,
                                   plan=plan, moe_fast=False,
                                   moe_drop_free=serve_prefill)
            aux = aux + a
        x = shard_hint(x, ("data", None, None))
        return (x, aux), None

    layer_stack = params["layers"] if cfg.family != "encdec" else (
        params["layers"], params["xattn"])
    G = num_groups(cfg)
    if remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if _REMAT_POLICY == "dots" else None)
        fn = jax.checkpoint(group_fn, prevent_cse=False, policy=policy)
        (x, aux), _ = jax.lax.scan(fn, (x, jnp.float32(0.0)), layer_stack,
                                   unroll=_SCAN_UNROLL)
    elif serve_prefill and G <= _DECODE_UNROLL_MAX_GROUPS:
        carry = (x, jnp.float32(0.0))
        for g in range(G):
            gp = jax.tree_util.tree_map(lambda p: p[g], layer_stack)
            carry, _ = group_fn(carry, gp)
        x, aux = carry
    else:
        (x, aux), _ = jax.lax.scan(group_fn, (x, jnp.float32(0.0)),
                                   layer_stack, unroll=_SCAN_UNROLL)
    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["embed"], x)
    return logits, aux


def encode(params: Params, frames: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    """Bidirectional encoder over precomputed frame embeddings."""
    x = frames.astype(cfg.jdtype)
    positions = jnp.arange(x.shape[1])[None, :]

    def step(x, lp):
        y, _, _ = _dense_block(lp, x, cfg, causal=False, positions=positions)
        return y, None

    x, _ = jax.lax.scan(step, x, params["encoder"]["layers"],
                        unroll=_SCAN_UNROLL)
    return rms_norm(params["encoder"]["final_norm"], x, cfg.norm_eps)


# -------------------------------------------------------------- decode --
def init_caches(params: Params, cfg: ArchConfig, batch: int, max_len: int,
                kv_dtype: Optional[str] = None):
    """Per-group decode caches.

    Shallow stacks (<= ``_DECODE_UNROLL_MAX_GROUPS`` groups — every
    ``reduced()`` serving config) get a TUPLE of independent per-group
    buffers: the unrolled decode path updates each group's KV/SSM state
    with one in-place dynamic-update-slice on its own carry leaf, and
    attention reads the buffer directly — no group-axis slicing, no
    re-stacking, so a donated epoch scan's per-step cache cost is
    O(tokens written) instead of O(cache bytes).  Deep stacks keep the
    single stacked array the compact scan-over-layers decode consumes.

    ``kv_dtype`` ("int8" | "fp8_e4m3") builds quantized KV buffers with
    per-row fp32 scale leaves (see :func:`repro.models.attention
    .init_kv_cache`); SSM states are recurrent fp state, never
    quantized.  None/"native" is byte-identical to the pre-quant cache
    structure."""
    G = num_groups(cfg)

    def one(_):
        if cfg.family == "hybrid":
            return {"ssm": jax.vmap(lambda _: init_ssm_state(cfg, batch))(
                        jnp.arange(cfg.attn_every - 1)),
                    "attn": init_kv_cache(cfg, batch, max_len,
                                          kv_dtype=kv_dtype)}
        if cfg.family == "ssm":
            return init_ssm_state(cfg, batch)
        return init_kv_cache(cfg, batch, max_len, kv_dtype=kv_dtype)

    if G <= _DECODE_UNROLL_MAX_GROUPS:
        return tuple(one(None) for _ in range(G))
    return jax.vmap(one)(jnp.arange(G))


def seed_caches_from_prefix(cfg: ArchConfig, batch: int, max_len: int,
                            snapshot, prefix_len: int,
                            kv_dtype: Optional[str] = None):
    """Fresh decode caches pre-seeded with a shared KV prefix.

    ``snapshot`` is a cache pytree some co-tenant already filled through
    at least ``prefix_len`` tokens (same cfg/batch/max_len geometry).
    Returns ``init_caches``-fresh buffers with exactly rows
    ``[0, prefix_len)`` of the snapshot's KV copied in — one
    dynamic-update-slice per KV buffer — and everything past the prefix
    zero, so the result is bit-identical to the cache state a cold
    tenant would have after prefilling the same ``prefix_len`` tokens
    itself (causal attention never rewrites earlier KV rows).

    SSM state is cumulative rather than row-addressed, so for ssm /
    hybrid families the snapshot is only valid at its exact length:
    callers must pass ``prefix_len`` equal to the snapshot's token count
    and the recurrent state is adopted wholesale (hybrid still slices
    its attention KV).  ``kv_dtype`` must match the precision the
    snapshot was filled at (the serving layer keys prefix entries by
    it): quantized scale leaves are [B, L, Hkv, 1], so the same
    time-axis (ndim-3) slice copies them row-for-row with the
    quantized K/V.  ``prefix_len`` must be a Python int (static
    under jit).  encdec is unsupported — cross-attention caches are
    encoder-derived, not prompt-prefix-derived.
    """
    fresh = init_caches(None, cfg, batch, max_len, kv_dtype=kv_dtype)

    def kv_seed(dst, src):
        # KV leaves are [..., time, kv_heads, head_dim]: time is axis -3
        # for both per-group 4D buffers and stacked 5D buffers
        pre = jax.lax.slice_in_dim(src, 0, prefix_len, axis=src.ndim - 3)
        return jax.lax.dynamic_update_slice_in_dim(
            dst, pre.astype(dst.dtype), 0, axis=dst.ndim - 3)

    def adopt(dst, src):
        return src.astype(dst.dtype)

    tree_map = jax.tree_util.tree_map
    if cfg.family in ("dense", "moe"):
        return tree_map(kv_seed, fresh, snapshot)
    if cfg.family == "ssm":
        return tree_map(adopt, fresh, snapshot)
    if cfg.family == "hybrid":
        def one(f, s):
            return {"ssm": tree_map(adopt, f["ssm"], s["ssm"]),
                    "attn": tree_map(kv_seed, f["attn"], s["attn"])}
        if isinstance(fresh, tuple):
            return tuple(one(f, s) for f, s in zip(fresh, snapshot))
        return one(fresh, snapshot)   # stacked leaves: same dict shape
    raise ValueError(f"prefix seeding unsupported for family {cfg.family}")


def decode_epoch(params: Params, token: jnp.ndarray, caches,
                 index: jnp.ndarray, cfg: ArchConfig, k: int, *,
                 next_token_fn,
                 enc_out: Optional[jnp.ndarray] = None,
                 plan=None, kv_len: Optional[int] = None
                 ) -> Tuple[jnp.ndarray, Any]:
    """K decode steps as ONE on-device ``lax.scan`` over
    :func:`decode_step` — the epoch-granted serving path.

    Per-token Python scheduling and ``jit`` dispatch amortize from
    per-step to per-epoch: the scan body compiles once per (plan, k)
    and the carry (token, caches, position) never leaves the device.
    ``next_token_fn(logits) -> [B] int32`` closes the feedback loop
    (greedy argmax in serving; anything sample-like works as long as it
    is a pure function of the logits).  The cache pytree is a
    donation-safe carry: :func:`decode_step` returns caches with the
    exact structure/shape/dtype it consumed, so callers can
    ``jax.jit(..., donate_argnums=...)`` the caches argument and XLA
    updates the KV/SSM buffers in place across the whole epoch.

    token: [B, 1] int32 (the first input token); index: starting
    position.  Returns (tokens [B, k] — the k decoded tokens — and the
    updated caches).  Bit-identical to k sequential decode_step calls
    feeding each output token back in (tests/test_serve_pipeline.py).
    """
    def step(carry, _):
        tok, caches, idx = carry
        logits, caches = decode_step(params, tok, caches, idx, cfg,
                                     enc_out=enc_out, plan=plan,
                                     kv_len=kv_len)
        nxt = next_token_fn(logits)
        return (nxt[:, None], caches, idx + 1), nxt

    carry = (token, caches, jnp.asarray(index, jnp.int32))
    (_, caches, _), toks = jax.lax.scan(step, carry, None, length=k)
    return jnp.swapaxes(toks, 0, 1), caches


def decode_step(params: Params, token: jnp.ndarray, caches, index: jnp.ndarray,
                cfg: ArchConfig, enc_out: Optional[jnp.ndarray] = None,
                plan=None, kv_len: Optional[int] = None
                ) -> Tuple[jnp.ndarray, Any]:
    """One decode step.  token: [B, 1] int32; index: scalar position.
    ``plan`` (a static core.plan.KernelPlan) executes each layer's FFN
    through the Pallas kernel variant the granted candidate lowered to.
    ``kv_len`` (static) bounds the attention read to the live prefix of
    the KV cache — see :func:`repro.models.attention.mha`; requires
    index < kv_len.  Returns (logits [B, 1, V], updated caches).

    Cache-update structure matters enormously here.  The old layer scan
    consumed the stacked caches as scan *xs* and re-stacked the updated
    caches as scan *ys* — allocating and filling a fresh full-cache
    buffer on EVERY decode step (O(cache bytes) per token).  For shallow
    stacks (every ``reduced()`` serving config) the caches are a tuple
    of independent per-group buffers (see :func:`init_caches`) and the
    group loop is unrolled as straight-line code: each KV write is one
    in-place dynamic-update-slice on its own buffer — which XLA aliases
    end-to-end when the caller donates the caches (the serving epoch
    scan) — and attention reads the buffer directly, no group-axis
    slicing.  Per-step cache cost drops from O(cache bytes) to
    O(tokens written) plus the unavoidable attention read.  Deep stacks
    keep the compact scan-over-layers HLO — essential for the
    512-device dry-run — carrying the stacked caches through the scan
    instead of the xs/ys re-stack.  A deep pure-SSM stack
    (:func:`ssm_state_in_place`) hands its whole stacked state to the
    Pallas decode kernel, which rewrites layer g's slice in place: no
    slice of the state is taken or written back around it."""
    x = embed(params["embed"], token)
    positions = jnp.full((1, 1), index, jnp.int32)
    G = num_groups(cfg)
    layer_stack = params["layers"] if cfg.family != "encdec" else (
        params["layers"], params["xattn"])

    def run_group(x, gp, xp, cache):
        """One layer group against its own cache: returns
        (x, new_cache)."""
        if cfg.family == "hybrid":
            def ssm_step(xc, sp_state):
                sp, st = sp_state
                y, new_st = _ssm_block(sp, xc, cfg, state=st, decode=True)
                return y, new_st
            x, new_ssm = jax.lax.scan(ssm_step, x,
                                      (gp["ssm"], cache["ssm"]),
                                      unroll=max(1, cfg.attn_every - 1))
            x, new_kv, _ = _dense_block(params["shared_attn"], x, cfg,
                                        kv_cache=cache["attn"],
                                        cache_index=index, kv_len=kv_len,
                                        positions=positions, plan=plan)
            return x, {"ssm": new_ssm, "attn": new_kv}
        if cfg.family == "ssm":
            return _ssm_block(gp, x, cfg, state=cache, decode=True)
        x, new_kv, _ = _dense_block(gp, x, cfg, kv_cache=cache,
                                    cache_index=index, kv_len=kv_len,
                                    positions=positions,
                                    xattn_kv=enc_out, xp=xp, plan=plan)
        return x, new_kv

    if G <= _DECODE_UNROLL_MAX_GROUPS:
        new_caches = list(caches)
        for g in range(G):
            stk = jax.tree_util.tree_map(lambda p: p[g], layer_stack)
            gp, xp = stk if cfg.family == "encdec" else (stk, None)
            x, new_caches[g] = run_group(x, gp, xp, new_caches[g])
        new_caches = tuple(new_caches)
    elif ssm_state_in_place(cfg):
        # the SSM stack goes whole to the kernel, which rewrites layer g
        # in place; only the small conv window is sliced and written back
        def group_fn(carry, scan_in):
            x, caches = carry
            gp, g = scan_in
            conv = jax.lax.dynamic_index_in_dim(caches["conv"], g, 0,
                                                keepdims=False)
            x, new = _ssm_block(gp, x, cfg, decode=True, layer=g,
                                state={"conv": conv, "ssm": caches["ssm"]})
            return (x, {"conv": jax.lax.dynamic_update_index_in_dim(
                caches["conv"], new["conv"], g, 0), "ssm": new["ssm"]}), None

        (x, new_caches), _ = jax.lax.scan(
            group_fn, (x, caches), (layer_stack, jnp.arange(G)),
            unroll=_SCAN_UNROLL)
    else:
        def group_fn(carry, scan_in):
            x, caches = carry
            if cfg.family == "encdec":
                (gp, xp), g = scan_in
            else:
                (gp, g), xp = scan_in, None
            cache = jax.tree_util.tree_map(
                lambda c: jax.lax.dynamic_index_in_dim(c, g, 0,
                                                       keepdims=False),
                caches)
            x, new_cache = run_group(x, gp, xp, cache)
            caches = jax.tree_util.tree_map(
                lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n, g, 0),
                caches, new_cache)
            return (x, caches), None

        (x, new_caches), _ = jax.lax.scan(
            group_fn, (x, caches), (layer_stack, jnp.arange(G)),
            unroll=_SCAN_UNROLL)

    x = rms_norm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["embed"], x)
    return logits, new_caches


def prefill_chunk(params: Params, tokens: jnp.ndarray, caches,
                  index: jnp.ndarray, cfg: ArchConfig,
                  enc_out: Optional[jnp.ndarray] = None,
                  kv_len: Optional[int] = None
                  ) -> Tuple[jnp.ndarray, Any]:
    """One cache-resuming prefill chunk: forward ``tokens`` [B, S] at
    absolute positions [index, index + S), writing their KV / SSM state
    into the live decode caches, and return the LAST position's logits
    [B, 1, V] plus the updated caches.

    This is the chunked-prefill work item of the continuous-batching
    server: a prompt is consumed as a sequence of chunks (sizes chosen
    per chunk from the tenant's cache grant), each resuming from the
    partially filled caches, and the final chunk's logits seed the
    decode loop — no recompile of the decode path, which sees exactly
    the caches a one-shot prefill would have produced.

    Bitwise contract (tests/test_continuous_batching.py): splitting a
    prompt into chunks at LANE-aligned boundaries (multiples of the SSD
    chunk for SSM/hybrid archs) is bit-identical to one chunk covering
    the whole prompt — attention writes/reads only live positions, SSM
    segmentation is preserved (:func:`repro.models.ssm.mamba2_forward`),
    and MoE routes through DROP-FREE capacity buckets (``moe_fast=False,
    moe_drop_free=True``: the dropping capacity is a function of the
    chunk length, so capacity drops would make the kept-token set
    chunking-dependent).  To keep that contract independent
    of the scheduler, the chunk executes the reference jnp path: the
    tenant's granted KernelPlan decides the chunk's *size* and its NEC
    charge at the serving layer, not the kernel numerics.

    Requires index + S <= max_len (and <= kv_len when given)."""
    x = embed(params["embed"], tokens)
    S = x.shape[1]
    positions = (jnp.arange(S, dtype=jnp.int32)[None, :]
                 + jnp.asarray(index, jnp.int32))
    G = num_groups(cfg)
    layer_stack = params["layers"] if cfg.family != "encdec" else (
        params["layers"], params["xattn"])

    def run_group(x, gp, xp, cache):
        if cfg.family == "hybrid":
            def ssm_step(xc, sp_state):
                sp, st = sp_state
                y, new_st = _ssm_block(sp, xc, cfg, state=st, decode=False)
                return y, new_st
            x, new_ssm = jax.lax.scan(ssm_step, x,
                                      (gp["ssm"], cache["ssm"]),
                                      unroll=max(1, cfg.attn_every - 1))
            x, new_kv, _ = _dense_block(params["shared_attn"], x, cfg,
                                        kv_cache=cache["attn"],
                                        cache_index=index, kv_len=kv_len,
                                        positions=positions, moe_fast=False,
                                        moe_drop_free=True)
            return x, {"ssm": new_ssm, "attn": new_kv}
        if cfg.family == "ssm":
            return _ssm_block(gp, x, cfg, state=cache, decode=False)
        x, new_kv, _ = _dense_block(gp, x, cfg, kv_cache=cache,
                                    cache_index=index, kv_len=kv_len,
                                    positions=positions,
                                    xattn_kv=enc_out, xp=xp,
                                    moe_fast=False, moe_drop_free=True)
        return x, new_kv

    if G <= _DECODE_UNROLL_MAX_GROUPS:
        new_caches = list(caches)
        for g in range(G):
            stk = jax.tree_util.tree_map(lambda p: p[g], layer_stack)
            gp, xp = stk if cfg.family == "encdec" else (stk, None)
            x, new_caches[g] = run_group(x, gp, xp, new_caches[g])
        new_caches = tuple(new_caches)
    else:
        def group_fn(carry, scan_in):
            x, caches = carry
            if cfg.family == "encdec":
                (gp, xp), g = scan_in
            else:
                (gp, g), xp = scan_in, None
            cache = jax.tree_util.tree_map(
                lambda c: jax.lax.dynamic_index_in_dim(c, g, 0,
                                                       keepdims=False),
                caches)
            x, new_cache = run_group(x, gp, xp, cache)
            caches = jax.tree_util.tree_map(
                lambda c, n: jax.lax.dynamic_update_index_in_dim(c, n, g, 0),
                caches, new_cache)
            return (x, caches), None

        (x, new_caches), _ = jax.lax.scan(
            group_fn, (x, caches), (layer_stack, jnp.arange(G)),
            unroll=_SCAN_UNROLL)

    x = rms_norm(params["final_norm"], x[:, -1:, :], cfg.norm_eps)
    logits = unembed(params["embed"], x)
    return logits, new_caches
