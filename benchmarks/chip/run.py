"""One run of one benchmark cell:

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Run from the root of a checkout on a machine with the chips the cell
asks for. The cell (``BENCHMARK.json`` ``workloads``), its workload file
(``benchmarks/chip/workloads/<name>.json``), its model configuration and
traffic mix are all found by name. Prints one JSON line last on stdout;
exits non-zero without a result when no TPU (or too few chips) is found.
"""
import os
import sys
import time

T_PROCESS = time.perf_counter()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

if __name__ == "__main__":
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        sys.stderr.write("run.py: the program under test (src/repro) is "
                         "not in this checkout\n")
        sys.exit(2)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from benchmarks.chip import harness
    sys.exit(harness.main(sys.argv[1:], t_process=T_PROCESS))
