"""The benchmark's own tests: the harness at test sizes on the CPU.  The
repository root goes on sys.path so that `bench.lib` imports."""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
