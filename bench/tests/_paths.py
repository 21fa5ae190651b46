"""Puts the benchmark's directory and the program's sources on sys.path."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def bench_run():
    """bench/run.py as the module `bench_run` (a name no other test uses)."""
    import importlib.util
    if "bench_run" not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            "bench_run", os.path.join(BENCH, "run.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules["bench_run"] = mod
        spec.loader.exec_module(mod)
    return sys.modules["bench_run"]
