"""Continuous batching: cache-aware chunked prefill in the epoch
pipeline.

Covers the PR acceptance contract:
  * chunked prefill agrees with a one-shot prefill — logits and caches
    to float32 rounding, the cache rows past the prompt exactly, and
    the tokens a subsequent decode produces exactly — across chunk
    sizes, including prompt lengths not divisible by the chunk size,
    for dense + MoE + SSM archs (and a one-token MoE tail chunk, which
    must route through the capacity buckets, not the decode fast path),
  * a one-shot prefill through the cache path reproduces
    ``make_prefill`` to float32 rounding (exact kv window),
  * per-chunk LANE-rounded kv windows match the full-window read,
  * chunk lengths lower from the granted KernelPlan
    (core.plan.lower_prefill_chunk) and respect SSD chunk alignment,
  * the interleaved continuous-batching server and the sequential
    (static batching) baseline produce bit-identical decode outputs,
    with TTFT recorded for every prompt tenant,
  * tenants admit mid-run at per-tenant indices (the _kv_len fix:
    KV windows derive from each tenant's OWN index) — pipelined
    interleaved serving matches the serial reference bit-for-bit with
    staggered admissions and unequal prompt lengths,
  * a tenant departing mid-run frees its pages (grants + KV
    reservation) and surviving tenants' next grants — and therefore
    prefill chunk sizes — grow: the dynamic-allocation behaviour
    end-to-end in the real server, not only in sim/.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import model as M
from repro.models.base import get_arch
from repro.models.transformer import init_caches, prefill_chunk

PF_ARCHS = ["yi-9b", "olmoe-1b-7b", "mamba2-370m"]


# Chunked and one-shot prefill contract the same rows through different
# shapes (chunk lengths, kv windows, the MoE bucket size), and the
# installed XLA orders those reductions differently, so their values
# agree to float32 rounding, not bit for bit.  The bound, 2**-16 of the
# largest magnitude, is about a hundred float32 ulps there: far above
# that rounding, far below what a wrong mask, position or cache row
# (changes of order one) would give.
F32_REL = 2.0 ** -16


def _assert_f32_close(actual, desired, err_msg=""):
    desired = np.asarray(desired)
    np.testing.assert_allclose(np.asarray(actual), desired, rtol=0,
                               atol=F32_REL * np.abs(desired).max(),
                               err_msg=err_msg)


def _assert_caches_close(actual, desired, live: int, err_msg=""):
    """Cache leaves agree to float32 rounding; the KV rows at or past
    ``live`` were never written by the prompt's dynamic-update-slices
    and must stay exactly as initialized."""
    flat_a = jax.tree_util.tree_flatten_with_path(actual)[0]
    flat_d = jax.tree_util.tree_leaves(desired)
    assert len(flat_a) == len(flat_d), err_msg
    for (path, a), d in zip(flat_a, flat_d):
        a, d = np.asarray(a), np.asarray(d)
        msg = f"{err_msg} {jax.tree_util.keystr(path)}"
        if getattr(path[-1], "key", None) in ("k", "v"):
            np.testing.assert_array_equal(a[:, live:], d[:, live:],
                                          err_msg=msg)
            a, d = a[:, :live], d[:, :live]
        _assert_f32_close(a, d, err_msg=msg)


def _chunked_prefill(cfg, params, toks, max_len, sizes, kv_full):
    """Consume ``toks`` in chunks of ``sizes`` (last one truncated),
    with the serve-style LANE-rounded kv window per chunk."""
    caches = init_caches(params, cfg, 1, max_len)
    P = toks.shape[1]
    pos, i = 0, 0
    while pos < P:
        S = min(sizes[min(i, len(sizes) - 1)], P - pos)
        kv = min(max_len, -(-(pos + S) // 128) * 128)
        logits, caches = prefill_chunk(params, toks[:, pos:pos + S], caches,
                                       jnp.int32(pos), cfg, kv_len=kv)
        pos += S
        i += 1
    return logits, caches


def _decode_from(cfg, params, caches, token, start, n):
    dec = jax.jit(M.make_decode_step(cfg), static_argnames=("plan", "kv_len"))
    toks = []
    for i in range(n):
        nxt, caches = dec(params, caches, token, jnp.int32(start + i))
        toks.append(np.asarray(nxt))
        token = nxt[:, None]
    return np.stack(toks, 1)


# ------------------------------------------ chunked == one-shot -------
@pytest.mark.parametrize("arch", PF_ARCHS)
@pytest.mark.parametrize("chunk", [64, 96, 128])
def test_chunked_prefill_bitwise_identical(arch, chunk):
    """Any chunking of a prompt — including a prompt length (200) not
    divisible by the chunk size — must reproduce the one-shot prefill:
    last-position logits and every cache leaf to float32 rounding, the
    unwritten cache tail exactly, and the tokens a subsequent decode
    observes exactly."""
    cfg = get_arch(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    P, max_len = 200, 256
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, P), 0,
                              cfg.vocab_size)
    one_l, one_c = _chunked_prefill(cfg, params, toks, max_len, [P], 256)
    chk_l, chk_c = _chunked_prefill(cfg, params, toks, max_len, [chunk], 256)
    _assert_f32_close(chk_l, one_l)
    _assert_caches_close(chk_c, one_c, live=P)
    # the caches a subsequent decode step observes are the same caches
    tok = jnp.argmax(one_l[:, -1, :], axis=-1)[:, None].astype(jnp.int32)
    np.testing.assert_array_equal(
        _decode_from(cfg, params, chk_c, tok, P, 3),
        _decode_from(cfg, params, one_c, tok, P, 3))


@pytest.mark.parametrize("arch", PF_ARCHS)
def test_one_shot_prefill_matches_make_prefill(arch):
    """The cache-writing prefill path with an exact kv window matches
    the cache-less ``make_prefill(serve=True)`` forward to float32
    rounding — serving semantics share the unrolled group loop and
    drop-free MoE buckets.  (Default ``make_prefill`` keeps the scan
    HLO + dropping capacity the dry-run dimensioning models.)"""
    cfg = get_arch(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(3))
    P = 160
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, P), 0,
                              cfg.vocab_size)
    want = M.make_prefill(cfg, serve=True)(params, {"tokens": toks})
    caches = init_caches(params, cfg, 1, P)
    got, _ = prefill_chunk(params, toks, caches, jnp.int32(0), cfg,
                           kv_len=P)
    _assert_f32_close(got[:, 0], want)


def test_moe_one_token_bucket_path_matches_full_forward():
    """The decode_fast=False contract: a one-token call routed through
    the capacity buckets must reproduce the same token's row of a
    full-sequence forward — the same experts, to float32 rounding (the
    bucket size changes the reduction shapes)."""
    from repro.models.moe import init_moe, moe_apply
    cfg = get_arch("olmoe-1b-7b").reduced()
    p = init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 5, cfg.d_model),
                          jnp.float32)
    full, _ = moe_apply(p, x, cfg, decode_fast=False)
    for i in range(5):
        one, _ = moe_apply(p, x[:, i:i + 1, :], cfg, decode_fast=False)
        _assert_f32_close(one, full[:, i:i + 1], err_msg=f"token {i}")


def test_uneven_chunk_mix_is_bitwise_identical():
    """Grant-driven chunking resizes chunks mid-prompt (the dynamic
    allocator's visible effect): an uneven mix of chunk sizes must
    still match the one-shot prefill — logits and caches to float32
    rounding, the unwritten cache tail exactly.  The server-level
    contracts compare token streams, which argmax decoding keeps
    exact."""
    cases = {"mamba2-370m": (416, [128, 256, 128]),
             "yi-9b": (384, [128, 256]),
             "olmoe-1b-7b": (384, [128, 256])}
    for arch, (P, sizes) in cases.items():
        cfg = get_arch(arch).reduced()
        params = M.init_params(cfg, jax.random.PRNGKey(7))
        toks = jax.random.randint(jax.random.PRNGKey(8), (1, P), 0,
                                  cfg.vocab_size)
        one_l, one_c = _chunked_prefill(cfg, params, toks, 512, [P], 512)
        chk_l, chk_c = _chunked_prefill(cfg, params, toks, 512, sizes, 512)
        _assert_f32_close(chk_l, one_l, err_msg=arch)
        _assert_caches_close(chk_c, one_c, live=P, err_msg=arch)


# -------------------------------------------- chunk lowering ----------
def test_chunk_length_lowers_from_grant():
    from repro.core.vmem import fused_ffn_pages, prefill_chunk_tokens
    lbm = fused_ffn_pages(256, 128, 256, 4)
    # a grant admitting the fused kernel admits the full nominal chunk;
    # tighter grants degrade toward the one-LANE floor
    assert prefill_chunk_tokens(lbm, 128, 256, 4, align=128,
                                max_tokens=256) == 256
    assert prefill_chunk_tokens(lbm - 1, 128, 256, 4, align=128,
                                max_tokens=256) == 128
    assert prefill_chunk_tokens(0, 128, 256, 4, align=128,
                                max_tokens=256) == 128
    # SSD alignment: chunks stay on lcm(LANE, ssm_chunk) boundaries
    assert prefill_chunk_tokens(lbm, 128, 256, 4, align=128,
                                max_tokens=300) == 256


def test_lower_prefill_chunk_absorbs_sub_align_tails():
    from repro.core.allocator import Selection
    from repro.core.mct import MappingCandidate
    from repro.core.plan import lower_prefill_chunk
    from repro.core.vmem import fused_ffn_pages, lower_selection
    lbm = fused_ffn_pages(256, 128, 256, 4)
    cand = MappingCandidate(kind="LBM", p_need=lbm, dram_bytes=0, flops=0,
                            loops=(), cache_map=(), usage_limit_bytes=0)
    plan = lower_selection(Selection(cand, lbm, 0.0), lbm, seq_block=256,
                           d_model=128, d_ff=256, dtype_bytes=4)
    kw = dict(d_model=128, d_ff=256, dtype_bytes=4, align=128,
              max_tokens=256)
    # plenty left: full chunk; 257 left: 256 would strand a 1-token
    # tail -> still 256?  no: 257-256=1 < align -> absorbed to 257
    assert lower_prefill_chunk(plan, remaining=1000, **kw) == 256
    assert lower_prefill_chunk(plan, remaining=257, **kw) == 257
    assert lower_prefill_chunk(plan, remaining=300, **kw) == 300
    assert lower_prefill_chunk(plan, remaining=400, **kw) == 256
    assert lower_prefill_chunk(plan, remaining=90, **kw) == 90


# ------------------------------------------------ server scenarios ----
def _specs():
    # LANE-multiple prompt lengths: every chunk and kv window lands on
    # the 128 grid, the shape regime where chunked == one-shot is
    # robustly bit-exact across backends (see the property tests for
    # the off-grid combinations pinned on this backend)
    from repro.sim.driver import TenantSpec
    return [
        TenantSpec("olmoe-1b-7b", arrive_at=4.0, n_inferences=10,
                   prompt_len=384),
        TenantSpec("mamba2-370m", arrive_at=6.0, n_inferences=10,
                   prompt_len=256),
    ]


@pytest.fixture(scope="module")
def admission_mode_runs():
    from repro.launch.serve import MultiTenantServer
    kw = dict(batch=1, max_len=512, total_pages=128, epoch_len=8)
    outs = {}
    for mode in ("interleaved", "sequential"):
        srv = MultiTenantServer(["olmoe-1b-7b", "mamba2-370m"],
                                tenants=_specs(), admission=mode, **kw)
        outs[mode] = srv.run(steps=16)
        outs[mode + "_srv"] = srv
    return outs


def test_interleaved_decode_bit_identical_to_sequential(admission_mode_runs):
    """Chunked cache-aware prefill interleaved into the decode epochs
    must not change a single decoded token vs whole-prompt-then-decode
    admission — chunked prefill is bitwise one-shot-equivalent, and
    the first decode token is the final chunk's greedy argmax."""
    a, b = (admission_mode_runs["interleaved"],
            admission_mode_runs["sequential"])
    assert a["admission"] == "interleaved"
    assert b["admission"] == "sequential"
    assert set(a["tenants"]) == set(b["tenants"])
    for tid in a["tenants"]:
        np.testing.assert_array_equal(
            a["tenants"][tid]["output"], b["tenants"][tid]["output"],
            err_msg=f"admission modes diverged for {tid}")


def test_arrivals_prefill_in_grant_sized_chunks(admission_mode_runs):
    """Interleaved mode consumes prompts in chunks lowered from the
    grant; sequential mode prefills whole prompts.  Both record TTFT
    for every prompt tenant and a run-level p95."""
    a, b = (admission_mode_runs["interleaved"],
            admission_mode_runs["sequential"])
    for tid, info in a["tenants"].items():
        if info["prompt_len"]:
            assert sum(info["prefill_chunks"]) == info["prompt_len"]
            assert info["ttft_s"] is not None and info["ttft_s"] > 0
            assert b["tenants"][tid]["prefill_chunks"] == \
                [info["prompt_len"]]
            # first token + decoded budget, all served before departure
            assert info["tokens"] == 1 + 10
            assert info["departed"]
    assert a["p95_ttft_s"] is not None and b["p95_ttft_s"] is not None
    assert a["prefill_tokens"] == b["prefill_tokens"] == 640


def test_admission_pool_fully_reclaimed(admission_mode_runs):
    """Departures return every grant AND the KV reservation."""
    for mode in ("interleaved", "sequential"):
        srv = admission_mode_runs[mode + "_srv"]
        resident_kv = sum(
            srv.cache.allocated_pages(t.tid + "#kv")
            for t in srv.tenants if not t.departed)
        assert (srv.cache.free_pages + resident_kv
                == srv.cache.config.num_pages)


def test_per_tenant_kv_windows_match_serial_reference():
    """Regression for the epoch-boundary bug: run() derived KV windows
    from tenants[0].index for ALL tenants.  With staggered admissions
    and unequal prompt lengths, every tenant's epochs must align to its
    OWN index — asserted by bit-exact parity between the pipelined
    interleaved loop and the serial per-step reference."""
    from repro.launch.serve import MultiTenantServer
    kw = dict(batch=1, max_len=512, total_pages=128, epoch_len=5)
    pipe = MultiTenantServer(["olmoe-1b-7b"], tenants=_specs(), **kw)
    serial = MultiTenantServer(["olmoe-1b-7b"], tenants=_specs(),
                               pipeline=False, **kw)
    out_p = pipe.run(steps=13)
    out_s = serial.run(steps=13)
    # indices differ across tenants: t0 decodes from 0, t1 from 384,
    # t2 from 256 — one shared epoch/KV grid would straddle windows
    for tid in out_p["tenants"]:
        np.testing.assert_array_equal(
            out_p["tenants"][tid]["output"], out_s["tenants"][tid]["output"],
            err_msg=f"per-tenant kv window parity broke for {tid}")


def test_departure_grows_survivor_grants_and_chunks():
    """Dynamic allocation end-to-end in the real server: while a
    co-tenant's KV reservation squeezes the pool, the survivor prefills
    in starved 128-token chunks; the co-tenant's departure frees its
    pages and the survivor's next grants — and chunk sizes — grow."""
    from repro.launch.serve import MultiTenantServer
    from repro.sim.driver import TenantSpec
    specs = [TenantSpec("mamba2-370m", arrive_at=0.0, prompt_len=1280,
                        n_inferences=8),
             TenantSpec("olmoe-1b-7b", arrive_at=0.0, prompt_len=256,
                        n_inferences=8)]
    srv = MultiTenantServer([], batch=1, max_len=2048, total_pages=48,
                            tenants=specs, epoch_len=8)
    out = srv.run(steps=8)
    survivor = out["tenants"]["t0:mamba2-370m"]
    chunks = survivor["prefill_chunks"]
    assert sum(chunks) == 1280
    # contended head: starved one-LANE chunks; post-departure tail: the
    # freed reservation admits the fused-kernel grant and 256er chunks
    assert chunks[0] == 128
    assert max(chunks) == 256
    assert chunks.index(256) > 0
    assert out["tenants"]["t1:olmoe-1b-7b"]["departed"]
    # every page is back after both depart
    assert srv.cache.free_pages == srv.cache.config.num_pages


def test_degraded_kv_reservation_recorded_in_stats():
    """Best-effort KV reservation: when the pool cannot back a second
    tenant's full working-set want, admission degrades to what the pool
    can spare instead of failing, and the shortfall is recorded in the
    per-tenant stats (kv_reserved < kv_wanted) — the degraded tenant
    still prefills and decodes to completion."""
    from repro.launch.serve import MultiTenantServer
    from repro.sim.driver import TenantSpec
    specs = [TenantSpec("olmoe-1b-7b", arrive_at=0.0, prompt_len=256,
                        n_inferences=6),
             TenantSpec("olmoe-1b-7b", arrive_at=0.0, prompt_len=256,
                        n_inferences=6)]
    # each wants 16 KV pages; a 24-page pool fully backs the first and
    # can only partially back the second
    srv = MultiTenantServer([], batch=1, max_len=512, total_pages=24,
                            tenants=specs, epoch_len=8)
    out = srv.run(steps=8)
    full = out["tenants"]["t0:olmoe-1b-7b"]
    degraded = out["tenants"]["t1:olmoe-1b-7b"]
    assert full["kv_wanted"] == degraded["kv_wanted"] == 16
    assert full["kv_reserved"] == 16
    assert 0 <= degraded["kv_reserved"] < degraded["kv_wanted"]
    # degradation is best-effort, not denial of service
    for info in (full, degraded):
        assert info["tokens"] == 1 + 6
        assert info["departed"]
        assert sum(info["prefill_chunks"]) == 256
    assert srv.cache.free_pages == srv.cache.config.num_pages


def test_poisson_arrivals_with_prompts_serve_end_to_end():
    """PoissonArrivals drives the real server with string arch ids and
    prompts — the shared arrival vocabulary of sim and serving."""
    from repro.launch.serve import MultiTenantServer
    from repro.sim.driver import PoissonArrivals
    arr = PoissonArrivals(rate_per_s=0.4, models=["mamba2-370m"],
                          n_arrivals=2, n_inferences=6, prompt_len=128,
                          seed=3)
    srv = MultiTenantServer(["olmoe-1b-7b"], batch=1, max_len=256,
                            total_pages=128, epoch_len=8, arrivals=arr)
    out = srv.run(steps=12)
    arrived = [i for tid, i in out["tenants"].items() if i["prompt_len"]]
    assert len(arrived) == 2
    for info in arrived:
        assert info["tokens"] == 1 + 6
        assert info["ttft_s"] is not None
        assert sum(info["prefill_chunks"]) == 128


def test_kv_stats_survive_preempt_resume():
    """The per-tenant KV accounting (kv_wanted / kv_reserved / kv_dtype)
    must survive a preempt -> resume round trip: preemption surrenders
    the reservation, resume re-reserves best-effort against the pool it
    finds — and the final stats record the RE-reserved state, not a
    stale pre-preemption value or a zeroed one."""
    from repro.launch.serve import MultiTenantServer
    from repro.sim.driver import TenantSpec
    from repro.sim.faults import FaultEvent, FaultPlan
    specs = [TenantSpec("olmoe-1b-7b", arrive_at=0.0, prompt_len=256,
                        n_inferences=12),
             TenantSpec("olmoe-1b-7b", arrive_at=0.0, prompt_len=256,
                        n_inferences=12)]
    # step 16: past t1's chunked prefill (a preempt aimed at a tenant
    # still consuming its prompt is a no-op by design)
    plan = FaultPlan([FaultEvent(step=16, kind="preempt",
                                 target="t1:olmoe-1b-7b", hold_epochs=1)])
    srv = MultiTenantServer([], batch=1, max_len=512, total_pages=64,
                            tenants=specs, epoch_len=8, faults=plan)
    out = srv.run(steps=16)
    kept = out["tenants"]["t0:olmoe-1b-7b"]
    bounced = out["tenants"]["t1:olmoe-1b-7b"]
    assert bounced["preemptions"] == 1 and kept["preemptions"] == 0
    # the round trip preserved the accounting invariants
    assert bounced["kv_wanted"] == kept["kv_wanted"] == 16
    assert 0 < bounced["kv_reserved"] <= bounced["kv_wanted"]
    assert bounced["kv_dtype"] == kept["kv_dtype"]
    # and the tenant still completed its full budget
    assert bounced["tokens"] == kept["tokens"] == 1 + 12
    assert srv.cache.free_pages == srv.cache.config.num_pages
