"""Import paths of the chip benchmark's tests."""
import sys
from pathlib import Path

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
for p in (CHIP, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
