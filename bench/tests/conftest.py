"""The benchmark's own modules import each other by bare name, as
``bench/run.py`` does when it is run as a script."""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
