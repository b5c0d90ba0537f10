"""Read the numbers `correct` compares over many seeds in one process, on
the chip: the program's own, or the control's.

    python bench/tools/readings.py --workload mbv2.offline \
        --seeds 11 12 13 --seconds 2 [--reference-in float8_e4m3fn]

Each seed is a whole run of the cell (`bench/run.py`) with a short window
at the cell's own load. With `--reference-in`, the control instead: the
plain reference computed with every product's operands rounded to that
dtype, in the program's place, which the limits have to fail (PERF.md
says why float8 and not the program's bf16 or int8 paths). One JSON line
per seed.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import manifest  # noqa: E402
from bench import run as bench_run  # noqa: E402


def reference_control(cfg, traffic, seeds, dtype) -> int:
    """The control without the program: for every pool image, the
    reference computed in `dtype` against the float32 reference."""
    import importlib

    import jax.numpy as jnp
    from bench import check
    bench_run.require_chips(1)
    ref_mod = importlib.import_module(f"bench.reference.{cfg['reference']}")
    for seed in seeds:
        params, pool, _ = bench_run.draw(ref_mod, cfg, traffic, seed)
        images = range(len(pool))
        ref = bench_run.reference_logits(ref_mod, cfg, params, pool, images)
        ctl = bench_run.reference_logits(ref_mod, cfg, params, pool, images,
                                         round_to=jnp.dtype(dtype))
        print(json.dumps({"config": cfg["name"], "seed": seed,
                          "control": f"reference in {dtype}",
                          "logit_err": max(check.logit_err(ctl[i], ref[i])
                                           for i in images)}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--reference-in", default=None,
                    help="the control as the reference computed with every "
                         "product's operands rounded to this dtype (e.g. "
                         "float8_e4m3fn), in the program's place")
    args = ap.parse_args(argv)
    cell = manifest.workload(manifest.load(), args.workload)
    cfg = manifest.config(cell["config"])
    traffic = manifest.traffic(cell["traffic"])
    if args.reference_in:
        return reference_control(cfg, traffic, args.seeds,
                                 args.reference_in)
    for seed in args.seeds:
        res, checks = bench_run.run(args.workload, seed, args.seconds,
                                    False, cell_files=(cfg, traffic))
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          **{k: c["value"] for k, c in checks.items()}}),
              flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
