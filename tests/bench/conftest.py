import sys
from pathlib import Path

# the benchmark's package lives at the root of the checkout
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
