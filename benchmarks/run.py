"""Benchmark entry point: one module per paper table/figure, the serving
load harness and the dry-run roofline table.  Prints
``name,us_per_call,derived`` CSV.  The on-chip benchmark is ``bench/``.

  PYTHONPATH=src python -m benchmarks.run [--only fig2,fig5]
  REPRO_BENCH_SCALE=quick|std|full
"""
import argparse
import sys
import time
import traceback


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--only", default=None, help="comma-separated bench names")
    args = p.parse_args()

    from benchmarks import common as C
    from benchmarks import (appE_scale, appF_fixed_examples, beyond_fusion_ops,
                            fig2_main, fig3_unseen, fig4_fewshot, fig5_contributors,
                            fig6_single_dataset, roofline, serve_load,
                            table1_per_task)

    benches = {
        "serve_load": serve_load.run,
        "fig2": fig2_main.run,
        "fig3": fig3_unseen.run,
        "fig4": fig4_fewshot.run,
        "table1": table1_per_task.run,
        "fig5": fig5_contributors.run,
        "fig6": fig6_single_dataset.run,
        "appE": appE_scale.run,
        "appF": appF_fixed_examples.run,
        "beyond_fusion": beyond_fusion_ops.run,
        "roofline": roofline.run,
    }
    only = set(args.only.split(",")) if args.only else None
    rows = C.Rows()
    print("name,us_per_call,derived")
    t0 = time.time()
    for name, fn in benches.items():
        if only and name not in only:
            continue
        t1 = time.time()
        try:
            fn(rows)
        except Exception as e:
            rows.add(f"{name}/ERROR", 0.0, f"{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
        rows.rows.append(f"# {name} done in {time.time()-t1:.0f}s")
    rows.emit()
    print(f"# total {time.time()-t0:.0f}s scale={C.SCALE}")


if __name__ == "__main__":
    main()
