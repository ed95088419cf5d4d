"""Benchmark harness — one function per paper table.

Prints ``name,us_per_call,derived`` CSV rows; the roofline table (from the
dry-run JSON, if present) is appended.  The serving tables (table 9 +
the mixed-traffic A/B) are additionally written machine-readable to
``BENCH_serving.json`` (``--out``); ``--smoke`` runs only those (the CI
artifact step).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

REPO = os.path.join(os.path.dirname(__file__), "..")


def serving_tables(T, concurrencies=(1, 4, 16), tune_db=None) -> dict:
    """Table 9 + the mixed-traffic and speculation A/Bs + the tunedb
    cold-vs-warm autotune comparison, as one payload."""
    table9 = T.table9_serving(concurrencies)
    mixed = T.table9_mixed_traffic()
    spec = T.table9_speculation()
    tunedb = T.table_tunedb_warmstart(tune_db)
    return {"table9": table9, "mixed_traffic": mixed, "speculation": spec,
            "tunedb_warmstart": tunedb}


def print_serving(doc: dict) -> None:
    for r in doc["table9"]:
        print(f"table9/{r['name']}/c{r['concurrency']},"
              f"{r['p50_latency_s'] * 1e6:.0f},"
              f"tok_per_s={r['tokens_per_s']:.1f};"
              f"p50_ms={r['p50_latency_s'] * 1e3:.1f};"
              f"p95_ms={r['p95_latency_s'] * 1e3:.1f};"
              f"ttft_p95_ms={r['p95_ttft_s'] * 1e3:.1f};"
              f"evictions={r['evictions']};refills={r['refills']};"
              f"prefix_hit_rate={r['prefix_hit_rate']:.2f};"
              f"prefill_tok={r['prefill_tokens_computed']};"
              f"syncs_per_tok={r['host_syncs_per_token']:.3f}")
    mt = doc["mixed_traffic"]
    for label in ("baseline", "optimized"):
        r = mt[label]
        print(f"table9/{r['name']},{r['p95_ttft_s'] * 1e6:.0f},"
              f"tok_per_s={r['tokens_per_s']:.1f};"
              f"ttft_p50_ms={r['p50_ttft_s'] * 1e3:.1f};"
              f"ttft_p95_ms={r['p95_ttft_s'] * 1e3:.1f};"
              f"syncs_per_tok={r['host_syncs_per_token']:.3f};"
              f"fori_segments={r['fori_segments']}")
    print(f"table9/mixed/verdict,0,"
          f"p95_ttft_improved={mt['p95_ttft_improved']};"
          f"host_syncs_reduced={mt['host_syncs_reduced']}")
    sp = doc["speculation"]
    for label in ("baseline", "speculative"):
        r = sp[label]
        extra = (f";acceptance_rate={r['acceptance_rate']:.2f};"
                 f"drafted={r['spec_tokens_drafted']};"
                 f"accepted={r['spec_tokens_accepted']};"
                 f"rolled_back={r['spec_rollback_tokens']}"
                 if label == "speculative" else "")
        print(f"table9/{r['name']},{r['p50_latency_s'] * 1e6:.0f},"
              f"tok_per_s={r['tokens_per_s']:.1f};"
              f"p50_ms={r['p50_latency_s'] * 1e3:.1f};"
              f"syncs_per_tok={r['host_syncs_per_token']:.3f}{extra}")
    print(f"table9/spec/verdict,0,"
          f"tokens_match={sp['tokens_match']};"
          f"speedup={sp['speedup']:.2f}x;"
          f"target={sp['target']:.1f}x;target_met={sp['target_met']}")
    td = doc["tunedb_warmstart"]
    print(f"tunedb/warmstart,{td['warm_tuning_s'] * 1e6:.0f},"
          f"cold_s={td['cold_tuning_s']:.2f};"
          f"warm_s={td['warm_tuning_s']:.2f};"
          f"speedup={td['speedup']:.2f}x;"
          f"cold_measured={td['cold_measured']};"
          f"warm_measured={td['warm_measured']};"
          f"flow_identical={td['flow_identical']};"
          f"engine_config_identical={td['engine_config_identical']}")


def main(argv=None) -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="serving tables only (fast; the CI artifact step)")
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_serving.json"),
                    help="path for the machine-readable serving benchmark")
    ap.add_argument("--tune-db", default=None,
                    help="persistent autotune store for the cold-vs-warm "
                         "comparison (default: a fresh temp store; pass a "
                         "path to seed/reuse one across runs)")
    args = ap.parse_args(argv)

    from benchmarks import paper_tables as T

    print("name,us_per_call,derived")
    if not args.smoke:
        for name, params, mode, folded, tile in T.table2_resources():
            print(f"table2/{name},0,params={params};mode={mode};"
                  f"folded_layers={folded};tile={tile}")
        for name, mode, passes in T.table3_passes():
            on = "+".join(k for k, v in passes.items() if v)
            print(f"table3/{name},0,mode={mode};passes={on}")
        for name, t_base, t_opt, fps_b, fps_o, speed in T.table4_base_vs_opt():
            print(f"table4/{name}/base,{t_base:.1f},fps={fps_b:.2f}")
            print(f"table4/{name}/optimized,{t_opt:.1f},"
                  f"fps={fps_o:.2f};speedup={speed:.2f}x")
        for name, t_flow, t_hand, speed in T.table5_comparison():
            print(f"table5/{name}/flow,{t_flow:.1f},"
                  f"vs_handwritten={speed:.2f}x")
            print(f"table5/{name}/handwritten_xla,{t_hand:.1f},")
        for name, pname, compact in T.table6_pass_stats():
            print(f"table6/{name}/{pname},0,{compact}")
        for (name, us_b, us_t, fp_b, fp_t, speed, knobs,
             n_pruned, n_compiled) in T.table7_tuned_vs_base():
            print(f"table7/{name}/base,{us_b:.1f},est_bytes={fp_b:.3g}")
            print(f"table7/{name}/tuned,{us_t:.1f},est_bytes={fp_t:.3g};"
                  f"est_speedup={speed:.2f}x;knobs={knobs};"
                  f"pruned={n_pruned};compiled={n_compiled}")
        for (name, label, fp, step, bound,
             comm) in T.table8_sharded_vs_unsharded():
            print(f"table8/{name}/{label},{step * 1e6:.1f},"
                  f"mem_per_dev={fp / 2 ** 30:.2f}GiB;bound={bound};"
                  f"comm_bytes={comm:.3g}")

    doc = serving_tables(T, concurrencies=(1, 4) if args.smoke
                         else (1, 4, 16), tune_db=args.tune_db)
    print_serving(doc)
    with open(args.out, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {os.path.relpath(args.out, REPO)}", file=sys.stderr)

    if not args.smoke:
        res = os.path.join(REPO, "results", "dryrun_baseline.json")
        for cand in (os.path.join(REPO, "results", "dryrun_optimized.json"),
                     res):
            if os.path.exists(cand):
                from benchmarks.roofline import build_table
                rows = build_table(json.load(open(cand)), pods=1)
                for r in rows:
                    step = max(r["compute_s"], r["memory_s"],
                               r["collective_s"])
                    print(f"roofline/{r['arch']}/{r['shape']},"
                          f"{step * 1e6:.0f},"
                          f"dominant={r['dominant']};"
                          f"roofline_frac={r['roofline_frac']:.3f};"
                          f"mem_gib={r['mem_per_dev_gib']:.2f}")
                break


if __name__ == "__main__":
    main()
