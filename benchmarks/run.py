"""Benchmark driver. One module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.

  fig5_hpo_baseline_*   — Fig. 5(a,b): k lmDS models, dense/sparse, no reuse
  fig5c/fig5d_*         — Fig. 5(c,d) + Fig. 6: lineage reuse speedups
  fig7_cv_*             — Fig. 7: cross-validation partial reuse
  ex2_fed_*             — §4.3 Example 2: federated MV/VM/gram + lmDS
  fed_compiled_vs_eager — ISSUE 4: federated plans through the compiler
                          (placement pass + per-site fused segments +
                          lineage reuse) vs the eager-numpy federated
                          island (BENCH_federated.json)
  gram_*                — §5.2 kernel trio (dense XLA / BLAS / sparse)
  roofline_*            — §Roofline cells from the dry-run sweep
  fused_vs_interpreted  — ISSUE 1: segment JIT engine vs per-op interpreter
                          (appends a BENCH_fusion.json trajectory entry)
  sparse_*              — ISSUE 3: sparsity-aware fused execution +
                          cost-gated reuse probes (BENCH_sparse.json)
  parfor_batched_grid   — ISSUE 5: the whole HPO grid as one vmapped
                          fused-segment stack vs the sequential-reuse
                          loop, plus federated exchange-round invariants
                          (BENCH_parfor.json)
  distributed_*         — ISSUE 6: shard_map-lowered segments on a
                          forced 8-device host mesh (data-parallel lmDS
                          + config-sharded grid) vs the local fused
                          baseline, parity asserted
                          (BENCH_distributed.json)
  serving_*             — ISSUE 7: `repro.serving.ModelServer` —
                          coalesced scoring on deploy-warmed vmap
                          buckets vs solo PreparedScript calls, plus
                          open-loop p50/p99/QPS at seeded-Poisson load
                          (BENCH_serving.json)
  streaming_*           — ISSUE 8: out-of-core chunked execution at a
                          10x-undersized memory budget (bounded
                          peak_live_bytes, one warm executable) and
                          lineage-driven incremental retrain after a
                          10% row append (BENCH_streaming.json)
  pipeline_*            — ISSUE 9: async pipelined dispatch at depth 2
                          (chunk prefetch + buffer donation + serving
                          rebatching) vs the depth-1 synchronous
                          executor, parity asserted at both lanes
                          (BENCH_pipeline.json)
  faults_*              — ISSUE 10: fault-free overhead of the fault
                          policy (asserted <= 2% vs
                          REPRO_FAULT_POLICY=off) plus seeded chaos
                          recovery — dead federated site, killed
                          prefetch worker, serving shed + supervisor
                          restart, parity asserted at 1e-12
                          (BENCH_faults.json)

Every run ends with a summary table aggregating the latest entry of all
``BENCH_*.json`` trajectories.

``--smoke`` runs the fusion + sparse + federated benchmarks at reduced
sizes (CI).
"""
import glob
import json
import os
import sys

sys.path.insert(0, "src")

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def aggregate() -> None:
    """Print one summary row per BENCH_*.json (latest trajectory entry).

    Tolerant of missing / schema-drifted trajectories: a file that
    vanished mid-run, is not a JSON list, is empty, or whose latest
    entry is not an object gets a warning line and is skipped — a
    single stale trajectory must never crash the whole summary table.
    """
    paths = sorted(glob.glob(os.path.join(BENCH_DIR, "BENCH_*.json")))
    if not paths:
        return
    rows = []
    for path in paths:
        name = os.path.basename(path)
        try:
            with open(path) as f:
                trajectory = json.load(f)
        except FileNotFoundError:
            print(f"!! {name}: disappeared during aggregation — skipped")
            continue
        except Exception as e:
            print(f"!! {name}: unreadable trajectory "
                  f"({type(e).__name__}: {e}) — skipped")
            continue
        if not isinstance(trajectory, list) or not trajectory:
            print(f"!! {name}: expected a non-empty JSON list of entries, "
                  f"got {type(trajectory).__name__} — skipped")
            continue
        entry = trajectory[-1]
        if not isinstance(entry, dict):
            print(f"!! {name}: latest entry is "
                  f"{type(entry).__name__}, not an object — skipped")
            continue
        try:
            metrics = "; ".join(
                f"{k.replace('_us_per_call', '')}={v}us" if
                k.endswith("_us_per_call") else f"{k}={v}"
                for k, v in entry.items()
                if k.endswith("_us_per_call") or k.endswith("speedup")
                or k == "devices"
                # serving latency/throughput columns (BENCH_serving)
                or k.endswith("_p50_us") or k.endswith("_p99_us")
                or k.endswith("_qps")
                # streaming residency columns (BENCH_streaming)
                or k.endswith("chunks") or k == "peak_live_bytes"
                # async-pipeline columns (BENCH_pipeline)
                or k == "rebatches"
                or k == "donated_buffers"
                # fault-tolerance columns (BENCH_faults)
                or k == "incidents" or k.endswith("_overhead_pct"))
            rows.append((name,
                         str(entry.get("benchmark", "?")),
                         str(entry.get("workload", ""))[:46],
                         metrics))
        except Exception as e:  # drifted field types inside the entry
            print(f"!! {name}: schema drift in latest entry "
                  f"({type(e).__name__}: {e}) — skipped")
    if not rows:
        return
    headers = ("trajectory", "benchmark", "workload", "metrics")
    widths = [max(len(headers[i]), *(len(r[i]) for r in rows))
              for i in range(3)]
    print("\n== benchmark summary (latest entry per trajectory) ==")
    print("  ".join(h.ljust(w) for h, w in zip(headers[:3], widths))
          + "  " + headers[3])
    for r in rows:
        print("  ".join(c.ljust(w) for c, w in zip(r[:3], widths))
              + "  " + r[3])


def main() -> None:
    if "--smoke" in sys.argv:
        from benchmarks import (distributed_bench, faults_bench,
                                federated_bench, fusion_bench,
                                parfor_bench, pipeline_bench,
                                serving_bench, sparse_bench,
                                streaming_bench)
        print("name,us_per_call,derived")
        fusion_bench.main(rows=500, cols=32, calls=20, repeats=2)
        sparse_bench.main(rows=512, cols=64, calls=10, repeats=2)
        # large enough that per-site gram dominates the eager baseline
        # (at toy sizes fixed plan/probe overhead hides the reuse win)
        federated_bench.main(rows=4096, cols=96, n_sites=3, repeats=3,
                             eager_layer=False)
        parfor_bench.main(rows=2048, cols=64, k=16, repeats=2,
                          fed_rows=1024, fed_cols=32)
        distributed_bench.main(rows=8192, cols=64, k=8, repeats=2)
        serving_bench.main(d=64, n=256, concurrency=8, max_batch=8,
                           rates=(500.0, 1000.0), openloop_n=120)
        streaming_bench.main(rows=16384, repeats=2, min_speedup=2.5)
        pipeline_bench.main(rows=16384, repeats=2, min_speedup=1.05,
                            d=64, rate=2600.0, openloop_n=300,
                            qps_floor=1200.0)
        faults_bench.main(n_scores=100, rows=8192, repeats=5)
        aggregate()
        return
    from benchmarks import (cv_reuse, distributed_bench, faults_bench,
                            federated_bench, fusion_bench, hpo_baseline,
                            hpo_reuse, kernel_bench, parfor_bench,
                            pipeline_bench, roofline_bench,
                            serving_bench, sparse_bench,
                            streaming_bench)
    quick = "--quick" in sys.argv
    ks = (1, 5, 10) if quick else (1, 5, 10, 20)
    print("name,us_per_call,derived")
    hpo_baseline.main(ks=ks)
    hpo_reuse.main(ks=ks)
    cv_reuse.main(folds=(4,) if quick else (4, 8))
    federated_bench.main()
    kernel_bench.main()
    roofline_bench.main()
    fusion_bench.main(calls=20 if quick else 50)
    sparse_bench.main(calls=10 if quick else 20)
    parfor_bench.main(k=8 if quick else 16, repeats=2 if quick else 3)
    distributed_bench.main(k=8 if quick else 16,
                           repeats=2 if quick else 3)
    serving_bench.main(n=256 if quick else 512,
                       openloop_n=120 if quick else 200)
    streaming_bench.main(rows=65536 if quick else 131072,
                         repeats=2 if quick else 3,
                         min_speedup=3.0 if quick else 5.0)
    pipeline_bench.main(rows=65536 if quick else 131072,
                        repeats=2 if quick else 3,
                        min_speedup=1.1 if quick else 1.15,
                        qps_floor=1800.0 if quick else 2105.0)
    faults_bench.main(rows=16384 if quick else 32768,
                      repeats=5 if quick else 8)
    aggregate()


if __name__ == "__main__":
    main()
