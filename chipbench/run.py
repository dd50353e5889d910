#!/usr/bin/env python3
"""Entry point of the benchmark; see ``chipbench/harness.py``.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
import time

T_START = time.perf_counter()       # set-up is counted from here

import sys                          # noqa: E402
from pathlib import Path            # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
