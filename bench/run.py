"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. Exits 2, printing no result, where JAX
finds no TPU or fewer chips than the cell asks for. See
``bench/harness.py`` for what a run does.
"""
import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# compiled programs (JAX's persistent cache, the program's AOT cache under
# it, a compressed configuration's artifact) and the TPU runtime's logs
# stay at fixed paths inside the checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".cache", "jax")
os.environ.pop("REPRO_AOT_CACHE", None)
os.environ.setdefault("TPU_LOG_DIR",
                      os.path.join(ROOT, ".cache", "bench", "tpu_logs"))

if __name__ == "__main__":
    from bench import harness
    raise SystemExit(harness.main(sys.argv[1:], t_start=T_START))
