"""Entry point of the benchmark; see ``chipbench/harness.py``.

    python3 chipbench/run.py --workload internlm2-1.8b.chat --seed 7 \
        --seconds 45 --trace 0
"""
import os
import sys
import time

T_IMPORT = time.time()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

if __name__ == "__main__":
    from chipbench.harness import main
    sys.exit(main(t_start=T_IMPORT))
