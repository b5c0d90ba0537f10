"""host_ms: the median, over the traced window's batches, of the
program's `serve.batch` span less its `serve.await` and `serve.h2d`
children, in ms: the serial host work of a batch (selection, assembly,
dispatch, copy back, answers) that the chip waits through, from the
program's annotations on the device trace's clock."""

import statistics


def read(run):
    rows = (run.trace or {}).get("serve_batches")
    if not rows:
        return None
    return 1e3 * statistics.median(
        r["batch"] - r.get("await", 0.0) - r.get("h2d", 0.0) for r in rows)
