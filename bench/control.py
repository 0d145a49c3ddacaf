"""Readings of a cell's lower-precision control, one JSON line per seed.

    python3 bench/control.py --workload <cell> --seeds 1,2,3

The control is the cell's reference computed in the precision below the
one its configuration states, put in the program's place (the driver's
``control``); each line gives the numbers the cell compares, which the
control has to fail.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run
    from bench.spans import Spans
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    traffic, config, driver = run.load_cell(args.workload)
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.time()
            ctx = run.Context(args.workload, traffic, config,
                              run.seed_int(seed), 0.0, False, run.WORK,
                              Spans())
            got = driver.control(ctx)
            print(json.dumps({"seed": seed, "control": got,
                              "limits": traffic["limits"],
                              "seconds": time.time() - t0}), flush=True)
    finally:
        shutil.rmtree(run.WORK, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
