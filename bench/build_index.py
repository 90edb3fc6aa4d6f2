"""Build a configuration's index into the benchmark's cache.

    python3 bench/build_index.py <configuration file>

``run.py`` starts this as a child process on the first run of a
configuration in a checkout, so that the build's device memory is freed
with this process and never counts in the measured run's peak.
"""
from __future__ import annotations

import sys

import run as bench


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    cfg = bench.load_json(argv[0])
    bench.require_chip(1)
    bench.enable_compile_cache()
    built = bench.build_index(cfg)
    print(f"index: built {cfg['name']} in {built:.1f} s", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
