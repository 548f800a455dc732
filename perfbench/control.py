"""Read the program's numbers and its control's, seed by seed, on the chip.

    python3 perfbench/control.py --workload <cell> --seconds <s> --seeds <n> [<n> ...]

For each seed, in one process: build the cell, run a window of
``--seconds``, then print one JSON line with what ``check`` compares (the
program's readings, the lower ends of each limit) and what the control
gives in the program's place (the reference one precision below the
configuration's: the upper ends). The limits in the configuration file are
set between the two, by hand, from these lines. The benchmark's own runs
never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(workload: str, seeds, seconds: float, require_tpu: bool = True,
             config=None, mix=None):
    """Yield ``{"seed", "program", "control"}`` per seed."""
    import jax

    from perfbench import harness, traffic

    wl, cfg_entry = harness.cell_entries(harness.load_benchmark(), workload)
    if require_tpu and jax.devices()[0].platform != "tpu":
        raise SystemExit("control: JAX found no TPU; there is no CPU fallback")
    config = config or harness.load_json(cfg_entry["file"])
    mix = mix or traffic.load(wl["traffic"])
    mod = harness.path_module(config["path"])
    for seed in seeds:
        cell = mod.Cell(config, mix, seed, trace=False, seconds=seconds)
        rec = cell.window()
        prog = {k: v["value"] for k, v in cell.check(rec).items()}
        yield {"seed": seed, "failed": rec["failed"], "program": prog,
               "control": cell.control(rec)}
        del cell, rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from perfbench.harness import enable_compile_cache

    enable_compile_cache()
    for line in readings(args.workload, args.seeds, args.seconds):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
