"""python3 -m benchmarks.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>: one run of one cell; the last line of stdout is the
result (README.md)."""

import time

_T0 = time.monotonic()          # process start, before any heavy import

import argparse                 # noqa: E402
import importlib                # noqa: E402
import os                       # noqa: E402
import sys                      # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="benchmarks.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run on whatever backend is there; proves the "
                    "control flow, prints no metric")
    a = ap.parse_args(argv)
    from benchmarks import harness
    try:
        cell = harness.load_cell(a.workload)
        run = harness.Run(cell=cell, seed=a.seed, seconds=a.seconds,
                          trace=bool(a.trace), rehearse=a.rehearse_cpu,
                          t0=_T0)
        device = harness.open_devices(run)
        driver = importlib.import_module("benchmarks.drivers." + cell.kind)
        line = driver.run(run, device)
    except harness.RunFailed as e:
        print(f"benchmark run failed: {e}", file=sys.stderr, flush=True)
        return 1
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # The program's data-plane threads and checkpoint manager are cut
    # off mid-run by design (README "How a run ends"): leave without
    # waiting on interpreter teardown.
    os._exit(rc)
