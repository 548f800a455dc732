"""Run one benchmark cell once on the chip.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. Set-up builds and warms everything the cell's
traffic uses; then the window runs for ``--seconds``. ``--trace 0``
reports the cell's end-to-end metrics, ``--trace 1`` its per-layer
metrics from a profiler trace of a short steady stretch. The last line of
standard output is the result object; the numbers compared with the plain
reference are the last lines of standard error. Exits non-zero, printing
no result, when JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

# The TPU runtime maps a premapped host buffer for transfers when it starts
# and unmaps it at exit. On a v5e host without transparent hugepages its
# default size takes 5-9 s to map, varying from process to process, most of
# set-up's spread; 256 MiB takes 1-2 s. It holds the largest transfer of
# any cell, the scheduler's 64 MiB chunk of features, four times over.
os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", str(256 << 20))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from perfbench.harness import enable_compile_cache, log, run_cell

    log(f"compile cache: {enable_compile_cache()}")
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   T_PROCESS)
    if out is None:
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
