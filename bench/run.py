"""Run one benchmark cell once on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
      --trace <0|1>

from the root of a checkout. The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer ones),
``device``, with ``--trace 1`` a ``breakdown``, and last ``compared``:
each number compared with the reference, beside its limit (also the
last lines of standard error). It exits nonzero, printing no result,
where JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import sys                                          # noqa: E402
from pathlib import Path                            # noqa: E402

_REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_REPO), str(_REPO / "src")]

from bench.harness import main                      # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
