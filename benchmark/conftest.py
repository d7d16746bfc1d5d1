"""Holds no fixture. It held one until the accepted tests looked their entries
up by name: `tests/test_hub175.py` found hub-175's entries at `[-1]` of
`configs` and `workloads`, and this file gave that one test the benchmark with
them moved last. No test says now where in its list an entry stands.

tests/test_benchmark_selftests.py loads this file by its path, as pytest
loads it for `python -m pytest benchmark/tests`, so it stays, empty. It stands
here and not in tests/: this directory is on `sys.path` while those tests run,
so a `tests/conftest.py` under it would answer the repo's `from
tests.conftest import ...` (`tests` is a namespace package)."""
