import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
# the program under test, for the tests that drive a run
sys.path.append(str(BENCH.parent))
