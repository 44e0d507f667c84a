"""Put the benchmark's modules and the program's ``src`` on the path."""

import pathlib
import sys

CHIP = pathlib.Path(__file__).resolve().parents[1]
for p in (CHIP, CHIP / "references", CHIP.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))
