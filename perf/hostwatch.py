"""A watch to run by hand BESIDE a benchmark run, never by it: a second
process that sleeps 20 ms at a time and logs every time it woke more than
150 ms late. It does nothing else, so a gap in its log is a pause of the
whole machine, and a far-off run whose longest round or chunk coincides
with such a gap (compare wall-clock times) was stalled by the machine, not
by the program, the device or the harness. That is what the stalls of PR 24
were (PERF.md section 6): the chip tool's sandbox stops every process for
seconds at a time, mostly while a process opens or closes the TPU.

    python3 perf/hostwatch.py <log file> <stop file> &
    date +%s.%N; python3 perf/run.py --workload ... ; touch <stop file>
"""

import os
import sys
import time


def main(log_path, stop_path, step_s=0.02, late_s=0.15):
    with open(log_path, "w", buffering=1) as log:
        log.write(f"start wall {time.time():.3f}\n")
        last = time.perf_counter()
        while not os.path.exists(stop_path):
            time.sleep(step_s)
            now = time.perf_counter()
            if now - last > late_s:
                log.write(f"GAP ending wall {time.time():.3f}: slept "
                          f"{1e3 * (now - last):.0f} ms for "
                          f"{1e3 * step_s:.0f}\n")
            last = now
        log.write(f"stop wall {time.time():.3f}\n")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
