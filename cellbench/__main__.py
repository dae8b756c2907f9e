"""Entry point: ``python3 -m cellbench --workload ... --seed ... --seconds ... --trace ...``."""

import sys
import time

_T_START = time.perf_counter()  # set-up is timed from here, before the heavy imports

if __name__ == "__main__":
    from cellbench.harness import main

    sys.exit(main(sys.argv[1:], t_start=_T_START))
