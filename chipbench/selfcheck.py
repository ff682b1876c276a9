"""Run a cell exactly as the driver does and check what it prints::

    python3 -m chipbench.selfcheck --workload <name> [--seed N] [--seconds S]

Starts ``BENCHMARK.json``'s command as a subprocess with ``--trace 0``
and then ``--trace 1``, captures its standard output, and holds the
LAST LINE of that output to ``contract.validate``: the check PR 22
failed in the driver's hands.  Prints each line and a verdict; exits
non-zero if either mode fails.  This process opens no jax backend, so
the child finds the chip free.
"""

from __future__ import annotations

import argparse
import subprocess
import sys

from chipbench import contract


def check(workload: str, seed: int, seconds: float, trace: int,
          extra=()) -> tuple:
    bench = contract.load_benchmark()
    cmd = list(bench["command"]) + [
        "--workload", workload, "--seed", str(seed),
        "--seconds", str(seconds), "--trace", str(trace), *extra,
    ]
    proc = subprocess.run(cmd, cwd=contract.ROOT, stdout=subprocess.PIPE, text=True)
    line = contract.last_line(proc.stdout)
    if proc.returncode != 0:
        return False, line, f"exit code {proc.returncode}"
    n_lines = len(proc.stdout.rstrip("\n").split("\n"))
    try:
        contract.validate(line, workload, trace, bench)
    except contract.ContractError as e:
        return False, line, str(e)
    return True, line, f"valid; stdout held {n_lines} line(s)"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    seconds = args.seconds or contract.load_benchmark()["run_seconds"]
    extra = ("--rehearse",) if args.rehearse else ()
    ok_all = True
    for trace in (0, 1):
        ok, line, why = check(args.workload, args.seed, seconds, trace, extra)
        print(f"SELFCHECK {args.workload} --trace {trace}: "
              f"{'PASS' if ok else 'FAIL'} ({why})\n{line}", flush=True)
        ok_all &= ok
    sys.exit(0 if ok_all else 1)


if __name__ == "__main__":
    main()
