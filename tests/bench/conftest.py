import sys
from pathlib import Path

# the benchmark package lives at the checkout root, beside src/
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
