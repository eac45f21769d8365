"""Run one benchmark cell once, from the checkout root::

    python3 chipbench/run.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

The last line of standard output is the result object; the last lines of
standard error are the numbers compared with the plain reference, each
beside its limit.  Exits 3, printing no result, when JAX finds no TPU or
fewer chips than the cell asks for.
"""
import sys
import time

T_START = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    from chipbench.harness import main
    sys.exit(main(t_start=T_START))
