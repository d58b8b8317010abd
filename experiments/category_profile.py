"""Aggregate a fusion trace by HLO category: where does the batch go?

Companion to fusion_profile.py (which prints the top-20 individual
fusions): sums duration / FLOPs / bytes over ALL fusions per category,
giving the one-line roofline attribution per model the earlier zoo
footnote needs (VERDICT r4 #2).

Run: python experiments/category_profile.py <trace_dir> [batches=8]
"""

import glob
import gzip
import json
import sys
from collections import defaultdict


def aggregate(trace_dir: str, batches: int = 8):
    paths = glob.glob(trace_dir + "/**/*.trace.json.gz", recursive=True)
    assert paths, f"no trace under {trace_dir}"
    with gzip.open(sorted(paths)[-1], "rt") as f:
        doc = json.load(f)
    agg = defaultdict(lambda: [0.0, 0.0, 0.0, 0])  # us, flops*execs, bytes*execs, n
    wall = 0.0
    for e in doc.get("traceEvents", []):
        if e.get("ph") != "X":
            continue
        args = e.get("args") or {}
        if "hlo_category" not in args:
            continue
        cat = args["hlo_category"]
        dur = float(e.get("dur", 0.0))
        row = agg[cat]
        row[0] += dur
        row[1] += float(args.get("model_flops", 0) or 0)
        row[2] += float(args.get("raw_bytes_accessed",
                                 args.get("bytes_accessed", 0)) or 0)
        row[3] += 1
        wall += dur
    print(f"{'category':28s} {'ms/b':>7s} {'%':>6s} {'TF/s':>6s} {'GB/s':>6s}")
    for cat, (us, flops, bts, n) in sorted(agg.items(), key=lambda kv: -kv[1][0]):
        per_batch_s = us / batches / 1e6
        tfs = (flops / batches) / per_batch_s / 1e12 if per_batch_s else 0
        gbs = (bts / batches) / per_batch_s / 1e9 if per_batch_s else 0
        print(f"{cat:28s} {us / batches / 1e3:7.2f} {100 * us / wall:6.1f} "
              f"{tfs:6.1f} {gbs:6.0f}")
    print(f"total {wall / batches / 1e3:.2f} ms/batch")


if __name__ == "__main__":
    aggregate(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 8)
