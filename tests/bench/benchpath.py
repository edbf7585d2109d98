"""Puts bench/ on the import path and loads bench/run.py by file."""
import importlib.util
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


def load_run():
    """bench/run.py as a module (its name would clash on the path)."""
    name = "bench_run_module"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, BENCH / "run.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]
