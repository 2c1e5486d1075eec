"""Op-log maker of the replay cells: a process of its own, without JAX.

    python3 log_proc.py <spec.json>

The spec holds the configuration, the traffic mix, the seed, the cache
key and the cache directory. The process makes the seed's log
(`benchmark.traffic.lagged_log`) into the benchmark's cache, unless it
is there already. It runs apart from the process under test so that
the generator's memory never serves that process's allocations: a
seed's first run and its later runs, which read the log from the
cache, start their windows from the same state.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import harness  # noqa: E402
from benchmark import traffic as gen  # noqa: E402


def main() -> None:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    harness.CACHE_DIR = spec["cache_dir"]
    harness.cached_arrays(
        "lagged-log", spec["key"], spec["sources"],
        lambda: vars(gen.lagged_log(spec["config"], spec["traffic"],
                                    spec["seed"])))


if __name__ == "__main__":
    main()
