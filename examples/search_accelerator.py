"""Full accelerator DSE scenario: search sparse-accelerator designs for
the dominant GEMMs of an assigned LLM architecture across hardware
platforms, and compare against the prior-work baselines.

    PYTHONPATH=src python examples/search_accelerator.py \
        [--model kimi-k2-1t-a32b] [--budget 4000]

``--arch`` targets any single paper platform or registered accelerator
topology by name (``--list-archs`` prints the registry, including the
published-accelerator zoo from ``repro.configs.archs``); ``--platforms``
takes a comma-separated mix, e.g. ``--platforms cloud,eyeriss_like`` —
the whole stack is ArchSpec-driven, so non-default memory hierarchies
search end-to-end.

``--profile DIR`` wraps the whole sweep in ``jax.profiler`` and dumps a
TensorBoard-loadable trace directory — the tool for eyeballing the
pipelined round loop (device kernels should tile the timeline with the
host planning in the gaps; big host-blocked stalls mean a compile-ahead
miss or a lost overlap).
"""
import argparse
import time


def list_archs():
    from repro.core.accel import PLATFORMS
    from repro.core.arch import registered_archs
    print("paper platforms:")
    for name in sorted(PLATFORMS):
        print(f"  {name}")
    print("registered archs (repro.configs.archs):")
    for name, spec in sorted(registered_archs().items()):
        head = spec.describe().splitlines()[-1]
        print(f"  {name:>16s}  {head}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="kimi-k2-1t-a32b",
                    help="assigned LLM architecture to extract GEMMs from")
    ap.add_argument("--budget", type=int, default=4000)
    ap.add_argument("--arch", default=None, metavar="NAME",
                    help="single target platform/arch name (overrides "
                         "--platforms); see --list-archs")
    ap.add_argument("--platforms", default="edge,cloud",
                    help="comma-separated platform/arch names")
    ap.add_argument("--list-archs", action="store_true",
                    help="print every resolvable platform/arch and exit")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="dump a jax.profiler trace of the sweep to DIR "
                         "(view with TensorBoard)")
    args = ap.parse_args(argv)

    if args.list_archs:
        list_archs()
        return

    from repro.configs.paper_workloads import arch_gemms
    from repro.core import search
    from repro.core.arch import as_arch

    targets = [args.arch] if args.arch else args.platforms.split(",")
    for t in targets:
        as_arch(t)      # fail fast with the full registry listing

    workloads = arch_gemms(args.model, weight_density=0.5,
                           act_density=0.6)
    print(f"extracted {len(workloads)} GEMMs from {args.model} "
          f"(50% pruned weights, 60% dense activations)\n")

    if args.profile:
        import jax
        jax.profiler.start_trace(args.profile)

    methods = ("sparsemap", "sage_like", "random_mapper")
    for plat in targets:
        print(f"== platform: {plat}")
        # the whole (method x workload) grid runs as one concurrent
        # mega-batched fleet — same results as per-method search.run
        # at fixed seeds, one device dispatch per signature per round
        t0 = time.time()
        stats = {}
        grid = search.run_method_sweep(
            methods, workloads, plat, budget=args.budget, seed=0,
            stats_out=stats)
        for wl in workloads:
            row = {m: grid[m][wl.name].best_edp for m in methods}
            ours = row["sparsemap"]
            print(f"  {wl.name:>28s}: ours {ours:10.3e}  "
                  f"SAGE-like {row['sage_like'] / ours:6.1f}x  "
                  f"Sparseloop-like {row['random_mapper'] / ours:6.1f}x")
        print(f"  [{len(workloads) * len(methods)} searches, "
              f"{stats['rounds']} rounds, {stats['dispatches']} device "
              f"dispatches, compile-ahead "
              f"{stats['compile_ahead_hits']}h/"
              f"{stats['compile_ahead_misses']}m, "
              f"host-blocked {stats['host_blocked_s']:.3f}s, "
              f"{time.time() - t0:.1f}s]")
    print("\n(EDP = cycles x pJ; larger ratio = larger our advantage)")

    if args.profile:
        jax.profiler.stop_trace()
        print(f"\nprofiler trace written to {args.profile}/ "
              f"(tensorboard --logdir {args.profile})")


if __name__ == "__main__":
    main()
