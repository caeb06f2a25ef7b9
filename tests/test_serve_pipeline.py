"""Epoch-pipelined serving: decode equivalence and loop parity.

Covers the PR acceptance contract:
  * a K-step scan decode (``decode_epoch``) is bit-identical to K
    sequential decode_step calls feeding each token back — for a
    transformer, an MoE, and an SSM tenant, with and without a
    KernelPlan, including a plan switch at an epoch boundary,
  * a plan-bucketed batched decode (vmap over the tenant axis) is
    bit-identical per tenant slice (for the stacked SSM state of a deep
    stack: the same tokens, the state at float32 precision),
  * the pipelined server loop reproduces the serial reference loop
    bit-for-bit (decoded outputs, choice traces, lbm_frac) with an
    unchanged NEC ``dram_total`` — epoch charging with ``repeat=K``
    equals charging every step individually,
  * the epoch decode donates its caches (in-place KV/SSM update), the
    stacked SSM state of a deep stack included,
  * bounded-window attention (``kv_len``) matches the full-length read,
  * QoS slack is seeded at the target until a tenant has served,
  * the starvation fallback selects the minimum-footprint LWM.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.allocator import Selection
from repro.core.mct import MCT, MappingCandidate, ModelMapping
from repro.core.nec import Traffic
from repro.core.vmem import lower_selection
from repro.models import model as M
from repro.models.base import get_arch
from repro.models.transformer import init_caches, ssm_state_in_place

KEY = jax.random.PRNGKey(0)
# the reduced mamba2 deepened past _DECODE_UNROLL_MAX_GROUPS, so its
# decode runs the stacked-state scan and the in-place SSD kernel
DEEP_SSM = "mamba2-370m@10L"
EQUIV_ARCHS = ["yi-9b", "olmoe-1b-7b", "mamba2-370m"]


def _cfg(arch: str):
    if arch == DEEP_SSM:
        cfg = dataclasses.replace(get_arch("mamba2-370m").reduced(),
                                  num_layers=10, name="mamba2-370m-10L")
        assert ssm_state_in_place(cfg)
        return cfg
    return get_arch(arch).reduced()


def _cand(kind: str, p_need: int = 8) -> MappingCandidate:
    return MappingCandidate(kind=kind, p_need=p_need, dram_bytes=0, flops=0,
                            loops=(), cache_map=(), usage_limit_bytes=0)


def _plan(cfg, kind: str, pages: int):
    return lower_selection(
        Selection(_cand(kind, 8), 8, 0.0), pages, seq_block=128,
        d_model=cfg.d_model, d_ff=max(cfg.d_ff, cfg.d_model), dtype_bytes=4,
        head_dim=cfg.hd, ssm_chunk=cfg.ssm_chunk)


def _sequential(cfg, params, caches, token, start, k, plans):
    """k reference steps through the one-token jit, feeding tokens back.
    ``plans`` gives the static plan per step."""
    dec = jax.jit(M.make_decode_step(cfg), static_argnames=("plan", "kv_len"))
    toks = []
    for i in range(k):
        nxt, caches = dec(params, caches, token, jnp.int32(start + i),
                          plan=plans[i])
        toks.append(np.asarray(nxt))
        token = nxt[:, None]
    return np.stack(toks, axis=1), caches


def _trees_equal(a, b) -> bool:
    eq = jax.tree_util.tree_map(
        lambda x, y: bool(np.array_equal(np.asarray(x), np.asarray(y))), a, b)
    return all(jax.tree_util.tree_leaves(eq))


# ------------------------------------------------- scan == sequential --
@pytest.mark.parametrize("arch", EQUIV_ARCHS + [DEEP_SSM])
def test_epoch_scan_matches_sequential(arch):
    """One K-step on-device scan must reproduce K sequential decode
    steps bit-for-bit (tokens AND caches) for every model family the
    serving loop hosts, and for the stacked SSM state that the in-place
    kernel updates."""
    cfg = _cfg(arch)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    token = jnp.zeros((1, 1), jnp.int32)
    k = 4
    plan = _plan(cfg, "LBM", 4096) if cfg.family != "ssm" else None
    want_toks, want_caches = _sequential(
        cfg, params, init_caches(params, cfg, 1, 16), token, 0, k, [plan] * k)
    ep = jax.jit(M.make_decode_epoch(cfg), static_argnames=("plan", "k"))
    got_toks, got_caches = ep(params, init_caches(params, cfg, 1, 16), token,
                              jnp.int32(0), plan=plan, k=k)
    np.testing.assert_array_equal(np.asarray(got_toks), want_toks)
    assert _trees_equal(got_caches, want_caches)


def test_epoch_plan_switch_at_boundary_matches_sequential():
    """Mid-serve plan switch at an epoch boundary: epoch under plan A
    then epoch under plan B == 2K sequential steps switching plans at
    step K."""
    cfg = get_arch("yi-9b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(2))
    token = jnp.zeros((1, 1), jnp.int32)
    k = 3
    big, small = _plan(cfg, "LBM", 4096), _plan(cfg, "LWM", 2)
    assert big != small
    want_toks, want_caches = _sequential(
        cfg, params, init_caches(params, cfg, 1, 16), token, 0, 2 * k,
        [big] * k + [small] * k)
    ep = jax.jit(M.make_decode_epoch(cfg), static_argnames=("plan", "k"))
    caches = init_caches(params, cfg, 1, 16)
    t1, caches = ep(params, caches, token, jnp.int32(0), plan=big, k=k)
    t2, caches = ep(params, caches, t1[:, -1:], jnp.int32(k), plan=small, k=k)
    got = np.concatenate([np.asarray(t1), np.asarray(t2)], axis=1)
    np.testing.assert_array_equal(got, want_toks)
    assert _trees_equal(caches, want_caches)


def _bucket_and_singles(cfg, plan, k=3):
    """Two same-arch tenants (different params): their individual
    epochs, and the vmapped bucket epoch of both."""
    tenants = []
    for i in range(2):
        p = M.init_params(cfg, jax.random.PRNGKey(10 + i))
        tenants.append((p, init_caches(p, cfg, 1, 16)))
    ep = jax.jit(M.make_decode_epoch(cfg), static_argnames=("plan", "k"))
    singles = [ep(p, c, jnp.zeros((1, 1), jnp.int32), jnp.int32(0),
                  plan=plan, k=k) for p, c in tenants]
    stack = lambda *xs: jnp.stack(xs)
    sp = jax.tree_util.tree_map(stack, *[p for p, _ in tenants])
    sc = jax.tree_util.tree_map(stack, *[c for _, c in tenants])
    bep = jax.jit(M.make_decode_epoch_batched(cfg),
                  static_argnames=("plan", "k"))
    btoks, bcaches = bep(sp, sc, jnp.zeros((2, 1, 1), jnp.int32),
                         jnp.zeros((2,), jnp.int32), plan=plan, k=k)
    return singles, btoks, bcaches


@pytest.mark.parametrize("arch", ["yi-9b", "olmoe-1b-7b"])
def test_bucketed_batched_decode_matches_single(arch):
    """Two same-arch tenants (different params) stacked into one
    vmapped bucket decode must match their individual epochs
    bit-for-bit."""
    cfg = get_arch(arch).reduced()
    singles, btoks, bcaches = _bucket_and_singles(
        cfg, _plan(cfg, "LBM", 4096))
    for i, (toks, caches) in enumerate(singles):
        np.testing.assert_array_equal(np.asarray(btoks[i]), np.asarray(toks))
        assert _trees_equal(
            jax.tree_util.tree_map(lambda x, i=i: x[i], bcaches), caches)


def test_bucketed_stacked_ssm_matches_single():
    """The in-place SSD kernel runs under the bucket's vmap (a grid axis
    over tenants): each tenant slice decodes the same tokens as its own
    epoch, and its state agrees at float32 precision.  Not bit for bit:
    the vmapped projections take other XLA CPU dot kernels than the
    unbatched ones (the same holds for the unrolled reduced mamba2)."""
    singles, btoks, bcaches = _bucket_and_singles(_cfg(DEEP_SSM), None)
    for i, (toks, caches) in enumerate(singles):
        np.testing.assert_array_equal(np.asarray(btoks[i]), np.asarray(toks))
        for got, want in zip(jax.tree_util.tree_leaves(bcaches),
                             jax.tree_util.tree_leaves(caches)):
            np.testing.assert_allclose(np.asarray(got[i], np.float32),
                                       np.asarray(want, np.float32),
                                       rtol=1e-5, atol=1e-5)


def _assert_epoch_donates_caches(cfg):
    params = M.init_params(cfg, jax.random.PRNGKey(3))
    caches = init_caches(params, cfg, 1, 16)
    ep = jax.jit(M.make_decode_epoch(cfg), static_argnames=("plan", "k"),
                 donate_argnums=(1,))
    toks, _ = ep(params, caches, jnp.zeros((1, 1), jnp.int32), jnp.int32(0),
                 k=2)
    jax.block_until_ready(toks)
    assert all(leaf.is_deleted()
               for leaf in jax.tree_util.tree_leaves(caches))


def test_epoch_decode_donates_caches():
    """The serving epoch entry point updates KV caches in place: the
    donated input buffers must be consumed by the call."""
    _assert_epoch_donates_caches(get_arch("yi-9b").reduced())


def test_stacked_ssm_epoch_donates_caches():
    """The same for the stacked SSM state and conv window of a deep
    stack, whose state the kernel updates through an aliased buffer."""
    _assert_epoch_donates_caches(_cfg(DEEP_SSM))


def test_kv_len_window_matches_full_read():
    """Bounded-window attention: positions beyond kv_len are masked
    anyway, so a window covering the live prefix must reproduce the
    full-length read."""
    cfg = get_arch("yi-9b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(4))
    token = jnp.zeros((1, 1), jnp.int32)
    full_t, _ = _sequential(cfg, params, init_caches(params, cfg, 1, 256),
                            token, 0, 4, [None] * 4)
    dec = jax.jit(M.make_decode_step(cfg), static_argnames=("plan", "kv_len"))
    caches = init_caches(params, cfg, 1, 256)
    tok = token
    got = []
    for i in range(4):
        nxt, caches = dec(params, caches, tok, jnp.int32(i), kv_len=128)
        got.append(np.asarray(nxt))
        tok = nxt[:, None]
    np.testing.assert_array_equal(np.stack(got, 1), full_t)


# ------------------------------------------- server loop parity -------
@pytest.fixture(scope="module")
def parity_servers():
    from repro.launch.serve import MultiTenantServer
    kw = dict(batch=1, max_len=64, total_pages=128)
    serial = MultiTenantServer(EQUIV_ARCHS, pipeline=False, **kw)
    pipe = MultiTenantServer(EQUIV_ARCHS, epoch_len=5, **kw)
    return serial.run(steps=12), pipe.run(steps=12)


def test_pipelined_outputs_bit_identical_to_serial(parity_servers):
    out_s, out_p = parity_servers
    assert out_s["mode"] == "serial" and out_p["mode"] == "pipelined"
    for tid in out_s["tenants"]:
        np.testing.assert_array_equal(
            out_s["tenants"][tid]["output"], out_p["tenants"][tid]["output"],
            err_msg=f"pipelined decode diverged for {tid}")
        assert (out_s["tenants"][tid]["tokens"]
                == out_p["tenants"][tid]["tokens"])


def test_pipelined_preserves_choice_traces_and_lbm_frac(parity_servers):
    """The per-epoch scheduler must make the same CaMDN decisions the
    per-step scheduler makes — lbm_frac and the recent choice trace are
    preserved (one scheduling event per epoch instead of per step)."""
    out_s, out_p = parity_servers
    for tid in out_s["tenants"]:
        assert (out_s["tenants"][tid]["lbm_frac"]
                == out_p["tenants"][tid]["lbm_frac"])
        assert (out_s["tenants"][tid]["choices"]
                == out_p["tenants"][tid]["choices"])
        assert out_p["tenants"][tid]["plans"]


def test_epoch_charging_leaves_dram_total_unchanged(parity_servers):
    """Charging a block once with repeat=K must equal charging each of
    the K steps individually."""
    out_s, out_p = parity_servers
    assert out_s["dram_bytes"] == out_p["dram_bytes"] > 0


# ------------------------------------------ epoch-granular charging ---
def test_charge_repeat_equals_k_individual_charges():
    from repro.launch.serve import MultiTenantServer
    srv = MultiTenantServer(["yi-9b"], batch=1, max_len=8, total_pages=16)
    task = srv.tenants[0].task
    base = (7, 11, 13, 3, 5)

    def snapshot():
        return dataclasses.astuple(
            srv.nec.ledger.per_tenant.get(task.id, Traffic()))

    before = snapshot()
    task.charge_repeat = 4
    task.charge(base)
    task.charge_repeat = 1
    once = np.subtract(snapshot(), before)
    before = snapshot()
    for _ in range(4):
        task.charge(base)
    individually = np.subtract(snapshot(), before)
    assert (once == individually).all()


# ------------------------------------------------ satellite fixes -----
def test_slack_seeded_at_target_until_first_epoch():
    """A tenant that has not served yet must report slack 0.0 (exactly
    on target) instead of the 0-or-huge measured-rate artifact that
    made startup ordering flap."""
    from repro.launch.serve import MultiTenantServer
    srv = MultiTenantServer(["olmoe-1b-7b"], batch=1, max_len=8,
                            total_pages=16,
                            qos_targets={"olmoe-1b-7b": 0.01})
    t = srv.tenants[0]
    assert t.tokens_served == 0
    assert srv._slack(t, now=0.0) == 0.0
    assert srv._slack(t, now=5.0) == 0.0       # still no tokens served
    t.tokens_served = 30
    assert srv._slack(t, now=0.0) == 0.0       # clock not started yet
    s = srv._slack(t, now=1.0)                 # measured once serving:
    assert np.isfinite(s) and s == (30 - 100) / 100


def test_starved_fallback_selects_min_footprint_lwm():
    """When the pool cannot grant anything the fallback must pick the
    LWM with the smallest p_need EXPLICITLY — not positionally — so a
    starved tenant never streams with a mid-sized tile it holds no
    pages for (exercised by deliberately breaking the sorted-lwms
    invariant)."""
    from repro.launch.serve import MultiTenantServer
    srv = MultiTenantServer(["yi-9b"], batch=1, max_len=8, total_pages=1)
    t = srv.tenants[0]
    tm = t.task.model
    mcts = []
    for mct in tm.mapping.mcts:
        # every candidate outgrows the 1-page pool -> the grant loop
        # must starve; then break the ascending-p_need ordering so a
        # positional lwms[0] pick would select the WRONG candidate
        lwms = [dataclasses.replace(m, p_need=m.p_need + 5)
                for m in mct.lwms]
        clone = MCT(mct.layer_name, lwms, mct.lbm)
        clone.lwms.sort(key=lambda m: -m.p_need)   # violate ascending order
        mcts.append(clone)
    tm.mapping = ModelMapping(tm.mapping.model_name, mcts,
                              tm.mapping.blocks)
    min_needs = [min(m.p_need for m in mct.lwms) for mct in mcts]
    assert min(min_needs) > srv.cache.config.num_pages  # guaranteed starved
    sched = srv._schedule_block(t, now=0.0)
    for (sel, pages), want in zip(sched, min_needs):
        assert pages == 0
        assert sel.candidate.kind == "LWM"
        assert sel.candidate.p_need == want
