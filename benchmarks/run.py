"""Run one cell of ``BENCHMARK.json`` once:

    python -m benchmarks.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

A run is a new process.  It refuses to start (non-zero exit, no result
line) unless jax finds the cell's TPU chips; builds the system under test
through its normal entry points on weights made from ``--seed``; warms up
the cell's own shapes; measures for ``--seconds``; checks what the timed
path produced against the plain reference; and prints the contract's one
JSON line last.  ``--trace 0`` reports the end-to-end metrics, ``--trace
1`` the per-layer metrics and the breakdown.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmarks import common

    spec = common.load_cell(args.workload)
    devices = common.require_chips(spec["chips"])
    common.configure_compile_cache()
    kind = spec["config"]["kind"]
    if kind == "train":
        from benchmarks import train as cell
    elif kind == "serve":
        from benchmarks import serve as cell
    else:
        raise SystemExit(f"configuration kind {kind!r}: train or serve")
    cell.run_cell(spec, args.seed, args.seconds, args.trace, T_PROCESS,
                  devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
