"""The benchmark's tests run on the CPU, at small sizes; the checkout root is
put on the path so that ``bench`` imports as a package."""
import sys
from pathlib import Path

ROOT = str(Path(__file__).resolve().parents[2])
for p in (ROOT, str(Path(ROOT) / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
