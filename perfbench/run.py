"""Run one benchmark cell once on the chip this process holds.

    python3 perfbench/run.py --workload deepseek-67b-s6.batch --seed 7 \\
        --seconds 30 --trace 0

The cell, its configuration, traffic mix, limits and metric readers are
found by name from BENCHMARK.json at the checkout's root. The last line
on standard output is the result as one JSON object; the numbers the
outputs check compared, each with its limit, are the last lines on
standard error and the last key of that object. With no accelerator, or
fewer chips than the cell asks for, it exits 3 and prints no result.

``--calibrate 1`` also runs the fp8 control over the same served tokens,
prints both widest gaps (the readings the ``logit_gap`` limit is set
from), and judges the control by the run's own checks in the program's
place: ``calibration.control_correct``, which has to read false.
Measured runs never run the control.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".perfbench" / "trace"
TPU_LOG_DIR = ROOT / ".perfbench" / "tpu_logs"


def use_compile_cache():
    """The persistent compile cache at the checkout's fixed path (or where
    JAX_COMPILATION_CACHE_DIR says), every program cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--calibrate", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from pbench import spec
    try:
        cell = spec.load_cell(args.workload, ROOT)
    except spec.SpecError as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    # the TPU runtime logs under /tmp unless told otherwise: keep its logs
    # in the checkout, like everything else a run writes
    os.environ.setdefault("TPU_LOG_DIR", str(TPU_LOG_DIR))
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < cell.chips:
        print(f"run.py: {args.workload} needs {cell.chips} accelerator "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              "device(s)", file=sys.stderr)
        return 3
    use_compile_cache()
    from pbench.run_cell import run_cell
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      t_start=T_START, calibrate=bool(args.calibrate),
                      trace_dir=TRACE_DIR)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
