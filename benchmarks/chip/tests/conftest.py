"""Make the benchmark's modules importable by their own names."""
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
for p in (HERE.parent, HERE.parents[2] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
