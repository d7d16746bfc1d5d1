"""Collects benchmark/tests/ into tier-1: the benchmark's own proofs that
`correct` can fail (test_correct.py), that the harness takes a windowed,
skewed cell as files (test_room.py) and that the cell hub-175.catchup is
what its files say (test_hub175.py). They drive benchmark/run.py in child
processes on the host backend and count as cases of this file. The files
stay where `python benchmark/selftest.py` finds them; nothing is copied.
benchmark/conftest.py is loaded with them, as pytest loads it there."""

import glob
import importlib.util
import os
import sys

_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "benchmark", "tests")


def _collect():
    seen = {}
    paths = sorted(glob.glob(os.path.join(_DIR, "test_*.py")))
    for path in [os.path.join(os.path.dirname(_DIR), "conftest.py")] + paths:
        name = "benchmark_tests_" + os.path.basename(path)[:-3]
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        if path in paths:  # a test file imports another by the name pytest gives it there
            sys.modules[os.path.basename(path)[:-3]] = mod
        spec.loader.exec_module(mod)
        for attr, obj in vars(mod).items():
            # tests, and the fixtures they ask for by name
            if attr.startswith("test_") or type(obj).__name__ == "FixtureFunctionDefinition":
                assert attr not in seen, f"{attr} in {seen[attr]} and in {path}"
                seen[attr] = path
                globals()[attr] = obj


_collect()
