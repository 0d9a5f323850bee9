"""Config-3 data-plane benchmark: streaming string-id ingest at scale.

Generates an Amazon-Reviews-2023-shaped ratings csv (string user ids,
asin-like item ids, rating, timestamp) of --rows rows, then streams it
host-by-host through tpu_als.io.stream (VERDICT r4 next-round #4:
">=100M synthetic rows with per-host splits feeding dataMode='per_host';
benchmark rows/sec and peak RSS").

Memory protocol: generation runs in a SUBPROCESS (its RSS must not
pollute the ingest measurement); each simulated host's arrays are
dropped after counting, keeping only the (small) vocabularies — peak RSS
therefore demonstrates the per-host bound, not the full rating set.  The
plumbing into training is proven by folding host 0's first rows into a
1-iteration ALS(dataMode='per_host') fit.

Usage:
  python scripts/stream_ingest_bench.py --rows 100000000 --hosts 4
  python scripts/stream_ingest_bench.py --generate PATH --rows N  # internal
"""

import argparse
import json
import os
import resource
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def generate(path, rows, seed=0, users=1_000_000, items=200_000,
             batch=2_000_000):
    rng = np.random.default_rng(seed)
    # realistic-shaped ids: 13-char reviewer ids, 10-char asins
    upool = np.array([f"A{k:012X}" for k in range(users)], dtype="S13")
    ipool = np.array([f"B{k:09X}" for k in range(items)], dtype="S10")
    rpool = np.array([b"1.0", b"1.5", b"2.0", b"2.5", b"3.0", b"3.5",
                      b"4.0", b"4.5", b"5.0"], dtype="S3")
    with open(path, "wb", buffering=1 << 22) as f:
        f.write(b"user_id,parent_asin,rating,timestamp\n")
        done = 0
        while done < rows:
            n = min(batch, rows - done)
            # zipf-ish popularity via squared uniform (heavy head)
            ui = (rng.random(n) ** 2 * users).astype(np.int64)
            ii = (rng.random(n) ** 2 * items).astype(np.int64)
            ri = rng.integers(0, len(rpool), n)
            ts = rng.integers(1_500_000_000, 1_700_000_000, n)
            comma = np.full(n, b",", dtype="S1")
            lines = np.char.add(np.char.add(np.char.add(np.char.add(
                np.char.add(np.char.add(
                    upool[ui], comma), ipool[ii]), comma), rpool[ri]),
                comma), ts.astype("S10"))
            f.write(b"\n".join(lines.tolist()) + b"\n")
            done += n
            if done % 20_000_000 < batch:
                print(f"  generated {done:,}/{rows:,}", file=sys.stderr)
    return os.path.getsize(path)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=100_000_000)
    ap.add_argument("--hosts", type=int, default=4)
    ap.add_argument("--path", default="/tmp/amazon_shape_ratings.csv")
    ap.add_argument("--generate", default="",
                    help="internal: generate mode, write csv to PATH")
    ap.add_argument("--keep", action="store_true",
                    help="keep the generated csv")
    ap.add_argument("--chunk-mb", type=int, default=32)
    args = ap.parse_args()

    if args.generate:
        generate(args.generate, args.rows)
        return

    # host-side data-plane benchmark: what is measured is ingest, so the
    # 1-iter plumbing fit runs on the CPU whatever the shell exports
    import jax

    jax.config.update("jax_platforms", "cpu")

    if not (os.path.exists(args.path)
            and os.path.getsize(args.path) > args.rows * 20):
        print(f"generating {args.rows:,} rows -> {args.path}",
              file=sys.stderr)
        t0 = time.time()
        subprocess.run(
            [sys.executable, __file__, "--generate", args.path,
             "--rows", str(args.rows)], check=True)
        print(f"generation took {time.time() - t0:.0f}s", file=sys.stderr)
    file_bytes = os.path.getsize(args.path)

    from tpu_als.io.stream import merge_vocabularies, stream_ingest

    t0 = time.time()
    total_rows = 0
    per_host_bytes = []
    vocabs_u, vocabs_i = [], []
    first_split = None
    for k in range(args.hosts):
        u, i, r, ul, il = stream_ingest(
            args.path, k, args.hosts, require_cols=4, skip_header=1,
            chunk_bytes=args.chunk_mb << 20)
        total_rows += len(u)
        per_host_bytes.append(u.nbytes + i.nbytes + r.nbytes)
        vocabs_u.append(ul)
        vocabs_i.append(il)
        if k == 0:  # keep a small slice to prove the training plumbing
            first_split = (u[:2_000_000].copy(), i[:2_000_000].copy(),
                           r[:2_000_000].copy())
        del u, i, r
        print(f"  host {k}: {total_rows:,} rows cumulative, "
              f"{time.time() - t0:.0f}s", file=sys.stderr)
    elapsed = time.time() - t0
    gl_u, _ = merge_vocabularies(vocabs_u)
    gl_i, _ = merge_vocabularies(vocabs_i)
    peak_rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024

    # prove the splits feed dataMode='per_host' (1 iteration, small rank)
    from tpu_als import ALS, ColumnarFrame

    u0, i0, r0 = first_split
    fit_t0 = time.time()
    ALS(rank=8, maxIter=1, regParam=0.05, seed=0,
        dataMode="per_host").fit(
        ColumnarFrame({"user": u0, "item": i0, "rating": r0}))
    fit_seconds = time.time() - fit_t0

    if not args.keep:
        os.unlink(args.path)
    print(json.dumps({
        "metric": "stream_ingest_rows_per_sec",
        "value": round(total_rows / elapsed),
        "unit": "rows/sec",
        "vs_baseline": None,
        "config": {
            "rows": total_rows, "hosts": args.hosts,
            "file_bytes": file_bytes,
            "ingest_seconds": round(elapsed, 1),
            "mb_per_sec": round(file_bytes / elapsed / 2**20, 1),
            "distinct_users": len(gl_u), "distinct_items": len(gl_i),
            "peak_rss_mb": round(peak_rss_mb),
            "full_set_mb": round(total_rows * 20 / 2**20),
            "max_per_host_mb": round(max(per_host_bytes) / 2**20),
            "perhost_fit_rows": len(u0),
            "perhost_fit_seconds": round(fit_seconds, 1),
        }}))


if __name__ == "__main__":
    main()
