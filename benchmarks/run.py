"""Benchmark driver (deliverable d): one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  table1           — Table 1: technique x conflict-type status, run live
  running_example  — §2.3/§6.4 numbers + faithfulness findings
  cofire           — Fig. 4: independent vs Voronoi co-fire rates
  hierarchy        — Fig. 3: per-level decidability costs
  taxonomy         — Fig. 2: detection time per conflict type
  kernels          — Pallas (interpret) vs jnp-oracle microbench
  router           — end-to-end routing throughput + validator latency
  signal_pipeline  — legacy loop vs fused GEMM+grouped-Voronoi pipeline
                     (also writes BENCH_signal_pipeline.json)
  moe_voronoi      — beyond-paper: MoE router as Voronoi partition
  roofline         — deliverable (g): 3-term roofline per (arch x shape)
"""
from __future__ import annotations

import inspect
import pathlib
import sys
import time
import traceback

# flags that consume the next argv token as their value (anything else
# starting with "-" is a bare switch) — keeps the unknown-suite typo
# check intact while letting `run bench_router --scenario flash_crowd
# --autoscale` pass its flags through to the suite
VALUE_FLAGS = {"--scenario", "--diag-log"}


def _split_argv(args):
    """-> (suite-name set, passthrough flag list).  Accepts both bare
    suite names (``router``) and module names (``bench_router``)."""
    only, flags = set(), []
    it = iter(args)
    for a in it:
        if a.startswith("-"):
            flags.append(a)
            if a in VALUE_FLAGS:
                try:
                    flags.append(next(it))
                except StopIteration:
                    pass
        else:
            only.add(a[len("bench_"):] if a.startswith("bench_") else a)
    return only, flags


def main() -> None:
    try:
        import benchmarks                                    # noqa: F401
    except ModuleNotFoundError:    # invoked as `python benchmarks/run.py`
        sys.path.insert(0,
                        str(pathlib.Path(__file__).resolve().parent.parent))
    from benchmarks import (bench_cofire, bench_hierarchy, bench_kernels,
                            bench_moe_voronoi, bench_roofline,
                            bench_router, bench_running_example,
                            bench_signal_pipeline, bench_table1,
                            bench_taxonomy)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    suites = [
        ("table1", bench_table1.main),
        ("running_example", bench_running_example.main),
        ("cofire", bench_cofire.main),
        ("hierarchy", bench_hierarchy.main),
        ("taxonomy", bench_taxonomy.main),
        ("kernels", bench_kernels.main),
        ("router", bench_router.main),
        ("signal_pipeline", bench_signal_pipeline.main),
        ("moe_voronoi", bench_moe_voronoi.main),
        ("roofline", bench_roofline.main),
    ]
    only, flags = _split_argv(sys.argv[1:])
    unknown = only - {name for name, _ in suites}
    if unknown:
        print(f"unknown suite name(s): {sorted(unknown)}; choose from "
              f"{[name for name, _ in suites]}", file=sys.stderr)
        sys.exit(2)
    if flags and not only:
        print("flags require naming the suite they go to, e.g. "
              "`run.py bench_router --scenario steady`", file=sys.stderr)
        sys.exit(2)
    print("name,us_per_call,derived")
    failed = []
    for name, fn in suites:
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            # suites whose main() accepts argv get the passthrough flags
            if flags and inspect.signature(fn).parameters:
                fn(flags)
            else:
                fn()
        except SystemExit as e:                # a suite's own gate tripped
            if e.code not in (None, 0):
                failed.append(name)
                print(f"{name}/SUITE_FAILED,0,exit={e.code}",
                      file=sys.stderr)
        except Exception:                      # noqa: BLE001
            failed.append(name)
            print(f"{name}/SUITE_FAILED,0,{traceback.format_exc(limit=2)!r}",
                  file=sys.stderr)
        print(f"# suite {name} done in {time.time()-t0:.1f}s",
              file=sys.stderr)
    if failed:
        # echo the verdict on stdout too so a piped CSV consumer can't
        # mistake a half-failed sweep for a clean one
        print(f"run/FAILED_SUITES,0,{'+'.join(failed)}")
        sys.exit(1)


if __name__ == "__main__":
    main()
