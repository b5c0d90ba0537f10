"""h2d_ms: the median, over the traced window's batches, of the
program's `serve.h2d` span (the batch's host-to-device copy), in ms."""

import statistics


def read(run):
    rows = [r["h2d"] for r in (run.trace or {}).get("serve_batches", ())
            if "h2d" in r]
    return 1e3 * statistics.median(rows) if rows else None
