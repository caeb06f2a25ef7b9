"""End-to-end driver (the paper's kind: multi-tenant inference).

Continuous-batching serving under realistic traffic: resident tenants
decode while new tenants arrive mid-run with real prompts, each prompt
prefilled as a sequence of **cache-aware chunks** — the CaMDN allocator
arbitrates the shared VMEM page pool per chunk, and the granted
Selection lowers to both the kernel variant (LBM fused-FFN vs LWM
tiles) AND the chunk length, so you can watch chunk sizes follow the
grants as tenants come and go.

  PYTHONPATH=src python examples/multi_tenant_serve.py [--pages 48]

With a tight pool (--pages 24) arrivals get starved grants: prefill
degrades to one-LANE chunks and decode drops from LBM to small LWM
candidates — the paper's Fig. 6 runtime behaviour, now visible in
admission (TTFT, chunk traces) as well as in kernel selection.  Compare
--admission sequential for the static-batching baseline (arrivals wait
for the batch to drain, then whole-prompt prefill): decode outputs are
bit-identical, TTFT is not.

Fleet mode — ``--devices N`` splits the host CPU into N XLA devices
(launch/env.py must win the race with backend init, hence the lazy
import in main) and serves the same workload as N replica chips behind
the least-loaded admission router, each with its own CaMDN allocator:

  PYTHONPATH=src python examples/multi_tenant_serve.py --devices 4
"""
import argparse

from repro.launch.serve import FleetServer, MultiTenantServer
from repro.sim.driver import TenantSpec


def _report(out):
    for tid, info in out["tenants"].items():
        line = (f"  {tid}: {info['tokens']} tokens | "
                f"LBM {info['lbm_frac'] * 100:.0f}% | "
                f"last grants {info['choices']}")
        if info["prompt_len"]:
            line += (f" | prompt {info['prompt_len']} in chunks "
                     f"{info['prefill_chunks']} | "
                     f"TTFT {info['ttft_s'] * 1e3:.0f}ms")
            # best-effort KV reservation: flag admissions the pool
            # could only partially back (kv_reserved < kv_wanted)
            line += f" | kv {info['kv_reserved']}/{info['kv_wanted']}p"
            if info["kv_reserved"] < info["kv_wanted"]:
                line += " (degraded)"
        if info["departed"]:
            line += " | departed (pages reclaimed)"
        print(line)
    p95 = (f", p95 TTFT {out['p95_ttft_s'] * 1e3:.0f}ms"
           if out["p95_ttft_s"] is not None else "")
    print(f"  throughput {out['tokens_per_s']:.1f} tok/s{p95}; "
          f"modeled DRAM {out['dram_bytes'] / 2**20:.1f} MB")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", nargs="+",
                    default=["granite-3-8b", "olmoe-1b-7b", "mamba2-370m"])
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--pages", type=int, default=48)
    ap.add_argument("--prompt-len", type=int, default=512)
    ap.add_argument("--admission", default="interleaved",
                    choices=["interleaved", "sequential"])
    ap.add_argument("--devices", type=int, default=0,
                    help="fleet mode: serve the workload over N replica "
                         "chips (on the CPU backend, N host devices), each "
                         "with its own CaMDN allocator")
    args = ap.parse_args()

    arrivals = [
        TenantSpec("olmoe-1b-7b", arrive_at=4.0, n_inferences=16,
                   prompt_len=args.prompt_len),
        TenantSpec("mamba2-370m", arrive_at=8.0, n_inferences=16,
                   prompt_len=args.prompt_len),
    ]

    if args.devices > 0:
        from repro.launch.env import describe, set_host_device_count
        set_host_device_count(args.devices)
        print(f"fleet: {args.devices} replica chips x {args.pages} pages, "
              f"least-loaded admission of {len(args.archs)} resident + "
              f"{len(arrivals)} arriving tenants ({describe()})")
        fleet = FleetServer(n_replicas=args.devices, arch_ids=args.archs,
                            pages_per_replica=args.pages,
                            max_len=2 * args.prompt_len, tenants=arrivals)
        out = fleet.run(args.steps)
        for rep in out["replicas"]:
            print(f"  {rep['replica']}: {rep['tokens_served']} tokens | "
                  f"page util {rep['page_util_mean'] * 100:.0f}% | "
                  f"tenants {rep['tenants']}")
        print(f"  routed: " + ", ".join(
            f"{tid}->r{r}" for tid, r in out["routes"]))
        p95 = (f", p95 TTFT {out['p95_ttft_s'] * 1e3:.0f}ms"
               if out["p95_ttft_s"] is not None else "")
        print(f"  fleet throughput {out['tokens_per_s']:.1f} tok/s, "
              f"page-util balance {out['page_util_balance']:.2f}{p95}")
        return

    print(f"serving {args.archs} with a {args.pages}-page shared pool; "
          f"2 tenants arrive mid-run with {args.prompt_len}-token prompts "
          f"({args.admission} admission)")
    srv = MultiTenantServer(args.archs, total_pages=args.pages,
                            max_len=2 * args.prompt_len,
                            tenants=arrivals, admission=args.admission)
    _report(srv.run(args.steps))

    print("\ncontended pool (a third of the pages): chunk sizes and "
          "kernel grants shrink, and grow back when a tenant departs")
    srv2 = MultiTenantServer(args.archs,
                             total_pages=max(args.pages // 3, 8),
                             max_len=2 * args.prompt_len,
                             tenants=[TenantSpec(
                                 "olmoe-1b-7b", arrive_at=2.0,
                                 n_inferences=8,
                                 prompt_len=args.prompt_len)],
                             admission=args.admission)
    _report(srv2.run(args.steps))


if __name__ == "__main__":
    main()
