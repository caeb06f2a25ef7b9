"""Serve yi-9b and mamba2-370m at published widths on a TPU.

    python chip_smoke.py            # one chip: the multi-tenant server
    python chip_smoke.py --chips 4  # four chips: the replica fleet only

One chip: ``MultiTenantServer`` (launch/serve.py) serves two resident
tenants and two mid-run arrivals with 512-token prompts, one of each
model, and the script checks that every tenant served its whole budget
inside the vocab with nothing shed, that the fused decode-epoch program
ran compiled Pallas kernels, and that the fused and tiled FFN kernels
agree with the float32 reference (kernels/ref.py) at yi-9b's width.

Four chips: ``FleetServer`` serves eight arrivals over four one-chip
replicas, and each replica's routed scenario is replayed on a
single-device server; the decode streams must be bit-identical.

Weights are random, drawn from fixed seeds.  The printed times are
smoke numbers from one run, not benchmark numbers.  Everything runs in
this one process, which holds the chip(s).  A missing TPU is an error:
the script exits non-zero and prints no result.  The last line of
standard output is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# Every budget below is decoded greedily with batch 1.
MAX_LEN = 1024
PROMPT = 512          # tokens per one-chip arrival
DECODE = 32           # decode steps per one-chip arrival
RESIDENT = 56         # decode steps per resident tenant
ARRIVE_AT = 8.0       # logical seconds (= steps): after the first epoch
FLEET_PROMPT = 256    # one prefill chunk per fleet arrival
FLEET_DECODE = 16
# planned_ffn (bf16 operands, f32 accumulation, the [S, d_ff] hidden
# rounded to bf16 before the down projection, bf16 output) against the
# float32 reference on the same bf16 inputs: the two bf16 roundings give
# a max error near 2**-8 of the output's largest magnitude.  Rounding
# the hidden to fp8 instead would give about 2**-5, so 2**-6 separates
# the precision the kernels state from one step below it.
FFN_RTOL = 2.0 ** -6
FFN_ROWS = 128        # one decode seq_block of tokens


def fail(msg: str) -> None:
    print(f"[smoke] FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class CompileClock:
    """Seconds JAX spent in backend compiles (persistent-cache reads
    included) and persistent-cache hits, from jax.monitoring events."""

    def __init__(self, monitoring):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def count_epoch_compiles(srv, clock: CompileClock) -> list:
    """Compile seconds of each epoch the server dispatches (observes
    ``_dispatch_epoch``; the serving path itself is unchanged)."""
    per_epoch = []
    dispatch = srv._dispatch_epoch

    def timed(work):
        s0 = clock.seconds
        try:
            dispatch(work)
        finally:
            per_epoch.append(clock.seconds - s0)
    srv._dispatch_epoch = timed
    return per_epoch


def smoke_configs():
    from repro.models.base import get_arch
    # yi-9b cut to 8 of 48 layers, so two yi-9b tenants and two
    # mamba2-370m tenants fit one 16 GB chip
    return get_arch("yi-9b").depth_cut(8), get_arch("mamba2-370m")


def describe_configs(cfgs) -> None:
    """Print each config's widths, depth (a cut shows in the name, as
    ``yi-9b@8L``) and parameter bytes."""
    import jax
    from repro.models import model as M
    for cfg in cfgs:
        leaves = jax.tree_util.tree_leaves(M.param_specs_shapes(cfg))
        n = sum(x.size for x in leaves)
        nbytes = sum(x.size * x.dtype.itemsize for x in leaves)
        log(f"config {cfg.name}: d_model {cfg.d_model}, heads "
            f"{cfg.num_heads}/{cfg.num_kv_heads} x {cfg.hd}, d_ff "
            f"{cfg.d_ff}, ssm state {cfg.ssm_state}, vocab "
            f"{cfg.vocab_size}, {cfg.dtype}, {cfg.num_layers} layers; "
            f"{n / 1e9:.3f} B params, {nbytes / 1e9:.2f} GB")


def bytes_in_use(device) -> int:
    return (device.memory_stats() or {}).get("bytes_in_use", 0)


def check_served(out, budgets: dict, vocab: dict) -> None:
    """Every tenant served its whole budget, departed, and emitted only
    ids inside its vocab; nothing was shed or degraded."""
    import numpy as np
    shed = out.get("overload", {}).get("shed", [])   # fleets never shed
    if shed:
        fail(f"shed requests: {shed}")
    for tid, info in out["tenants"].items():
        want = budgets[tid]
        if info["tokens"] != want or not info["departed"]:
            fail(f"{tid} served {info['tokens']} of {want} tokens "
                 f"(departed={info['departed']})")
        if info["kv_reserved"] != info["kv_wanted"]:
            fail(f"{tid} KV reservation degraded: "
                 f"{info['kv_reserved']}/{info['kv_wanted']} pages")
        toks = np.asarray(info["output"])
        if toks.size != want or toks.min() < 0 or toks.max() >= vocab[tid]:
            fail(f"{tid}: {toks.size} tokens, ids in "
                 f"[{toks.min()}, {toks.max()}], vocab {vocab[tid]}")


def report_tenants(out) -> None:
    for tid, info in out["tenants"].items():
        ttft = (f", TTFT {info['ttft_s']:.3f} s, prefill chunks "
                f"{info['prefill_chunks']}" if info["ttft_s"] is not None
                else "")
        plans = sorted(set(info["all_plans"]))
        log(f"{tid}: {info['tokens']} tokens, kv {info['kv_reserved']}/"
            f"{info['kv_wanted']} pages, plans {plans}{ttft}")


def fused_program_text(srv, name: str) -> str:
    """Compiled text of the last fused decode-epoch program that held a
    tenant of arch ``name`` — lowered from the argument shapes it ran
    with, so the compile is a persistent-cache read."""
    from repro.models import model as M
    key = [k for k in srv._fused_jits.keys()
           if any(item[1] == name for item in k)][-1]
    cfgs = srv._cfgs
    lists = ([], [], [], [], [])
    for kind, item_name, *_ in key:
        assert kind == "single", key
        specs = M.decode_epoch_input_specs(cfgs[item_name], srv.batch,
                                           srv.max_len, "native")
        for lst, spec in zip(lists, specs):
            lst.append(spec)
    entry = srv._fused_jits.peek(key)
    return entry.fallback.lower(*lists).compile().as_text()


def check_ffn_kernels(cfg) -> None:
    """Fused (LBM) and tiled (LWM) planned_ffn at the config's width
    against ref.ffn_ref in float32 on the same bf16 inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.plan import lower_ffn
    from repro.core.vmem import VMEM_PAGES
    from repro.kernels import ops, ref
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    x = jax.random.normal(ks[0], (FFN_ROWS, d), jnp.float32)
    wg, wu = (jax.random.normal(k, (d, f), jnp.float32) * d ** -0.5
              for k in ks[1:3])
    wd = jax.random.normal(ks[3], (f, d), jnp.float32) * f ** -0.5
    args = [a.astype(jnp.bfloat16) for a in (x, wg, wu, wd)]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(ref.ffn_ref)(
            *[a.astype(jnp.float32) for a in args]))
    scale = float(np.abs(want).max())
    for plan in (lower_ffn(FFN_ROWS, d, f, 2, VMEM_PAGES, True),
                 lower_ffn(FFN_ROWS, d, f, 2, 64, False)):
        got = np.asarray(ops.planned_ffn(*args, plan=plan), np.float32)
        err = float(np.abs(got - want).max()) / scale
        kind = (f"fused bf{plan.block_f}" if plan.fused else
                f"tiled {plan.up_tile.bm}x{plan.up_tile.bn}x"
                f"{plan.up_tile.bk}")
        log(f"planned_ffn {kind} at d {d} x d_ff {f}: max |err| / max "
            f"|ref| = {err:.3e} (tolerance {FFN_RTOL:.3e})")
        if not np.isfinite(err) or err > FFN_RTOL:
            fail(f"planned_ffn {kind} disagrees with ref.ffn_ref")


def one_chip(jax, clock: CompileClock) -> None:
    from repro.core.vmem import VMEM_PAGES
    from repro.launch.serve import MultiTenantServer
    from repro.sim.driver import TenantSpec
    yi, mamba = smoke_configs()
    describe_configs((yi, mamba))
    specs = [TenantSpec(yi, n_inferences=RESIDENT),
             TenantSpec(mamba, n_inferences=RESIDENT),
             TenantSpec(yi, arrive_at=ARRIVE_AT, n_inferences=DECODE,
                        prompt_len=PROMPT),
             TenantSpec(mamba, arrive_at=ARRIVE_AT, n_inferences=DECODE,
                        prompt_len=PROMPT)]
    t0 = time.perf_counter()
    srv = MultiTenantServer([], batch=1, max_len=MAX_LEN,
                            total_pages=VMEM_PAGES, tenants=specs)
    log(f"resident tenants admitted in {time.perf_counter() - t0:.1f} s")
    for t in srv.tenants:
        layout = ("per-group caches, unrolled" if isinstance(t.caches, tuple)
                  else "stacked caches, scanned")
        log(f"{t.tid} decodes over {layout} layers")
    per_epoch = count_epoch_compiles(srv, clock)
    t0 = time.perf_counter()
    out = srv.run(steps=RESIDENT)
    wall = time.perf_counter() - t0
    for t in srv.tenants:
        out["tenants"][t.tid]["all_plans"] = [p.describe() for p in t.plans]
    budgets = {t.tid: (1 + DECODE if t.prompt_len else RESIDENT)
               for t in srv.tenants}
    vocab = {t.tid: t.cfg.vocab_size for t in srv.tenants}
    log("smoke numbers from one run on one chip, not benchmark numbers:")
    log(f"first epoch compiled for {per_epoch[0]:.1f} s; "
        f"{len(per_epoch)} epochs compiled for {sum(per_epoch):.1f} s; "
        f"run wall {wall:.1f} s")
    report_tenants(out)
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"device peak bytes in use {stats['peak_bytes_in_use'] / 1e9:.2f}"
            f" GB of {stats.get('bytes_limit', 0) / 1e9:.2f} GB")
    check_served(out, budgets, vocab)
    text = fused_program_text(srv, yi.name)
    if "tpu_custom_call" not in text:
        fail("the fused decode-epoch program holds no tpu_custom_call: "
             "the Pallas kernels did not run compiled")
    log("fused decode-epoch program runs compiled Pallas kernels "
        "(tpu_custom_call)")
    del srv
    check_ffn_kernels(yi)


def four_chips(jax, clock: CompileClock) -> None:
    import gc
    import numpy as np
    from repro.core.vmem import VMEM_PAGES
    from repro.launch.serve import FleetServer, MultiTenantServer
    from repro.sim.driver import TenantSpec
    devs = jax.devices()
    if len(devs) < 4:
        fail(f"--chips 4 needs four chips, JAX found {len(devs)}")
    yi, mamba = smoke_configs()
    describe_configs((yi, mamba))
    # listed yi x4 then mamba x4: least-loaded routing puts one of each
    # on every replica (2 tenants per chip)
    specs = [TenantSpec(cfg, n_inferences=FLEET_DECODE,
                        prompt_len=FLEET_PROMPT)
             for cfg in (yi,) * 4 + (mamba,) * 4]
    kw = dict(batch=1, max_len=MAX_LEN, epoch_len=8)
    fleet = FleetServer(n_replicas=4, tp=1, pages_per_replica=VMEM_PAGES,
                        tenants=specs, **kw)
    for r, srv in enumerate(fleet.replicas):
        names = sorted(t.cfg.name for t in srv.tenants)
        if names != sorted((yi.name, mamba.name)):
            fail(f"replica r{r} holds {names}, want one of each model")
        for t in srv.tenants:
            for part in ("params", "caches"):
                on = {d for leaf in jax.tree_util.tree_leaves(
                    getattr(t, part)) for d in leaf.devices()}
                if on != {devs[r]}:
                    fail(f"r{r} {t.tid} {part} on {on}, not {devs[r]}")
    used = [bytes_in_use(d) for d in devs[:4]]
    log("bytes in use per chip after admission: "
        + ", ".join(f"{u / 1e9:.2f} GB" for u in used))
    if min(used) <= 0:
        fail("a chip reports no bytes in use")
    log("every replica's params and caches sit on its own chip")
    t0 = time.perf_counter()
    out = fleet.run(steps=FLEET_DECODE)
    log("smoke numbers from one run on four chips, not benchmark numbers:")
    log(f"fleet run {time.perf_counter() - t0:.1f} s, "
        f"{clock.seconds:.1f} s compiling")
    budgets = {tid: 1 + FLEET_DECODE for tid in out["tenants"]}
    vocab = {tid: (yi if yi.name in tid else mamba).vocab_size
             for tid in out["tenants"]}
    for tid, info in out["tenants"].items():
        info["all_plans"] = info["plans"]
    report_tenants(out)
    check_served(out, budgets, vocab)
    scenarios = fleet.replica_scenarios()
    del fleet
    gc.collect()
    for r, routed in enumerate(scenarios):
        srv = MultiTenantServer([], total_pages=VMEM_PAGES, tenants=routed,
                                **kw)
        rep = srv.run(steps=FLEET_DECODE)
        for tid, info in rep["tenants"].items():
            if not np.array_equal(info["output"],
                                  out["tenants"][tid]["output"]):
                fail(f"r{r} {tid}: decode stream differs from its "
                     f"single-device replay")
        del srv
        gc.collect()
        log(f"r{r}: {sorted(rep['tenants'])} bit-identical to the "
            f"single-device replay")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip replica fleet")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"no src/repro beside {os.path.basename(__file__)}: run it "
             f"from a checkout of the repository")
    sys.path.insert(0, SRC)
    from repro.launch.env import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    from jax import monitoring
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU found: JAX's first device is {devs[0].platform} "
             f"({devs[0].device_kind})")
    kind = devs[0].device_kind
    log(f"device {kind} x {len(devs)}; compile cache {cache_dir}")
    clock = CompileClock(monitoring)
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(jax, clock)
    else:
        one_chip(jax, clock)
    log(f"all checks passed in {time.perf_counter() - t0:.1f} s; "
        f"{clock.compiles} compiles took {clock.seconds:.1f} s, "
        f"{clock.cache_hits} persistent-cache hits")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind, "count": len(devs)}}))


if __name__ == "__main__":
    main()
