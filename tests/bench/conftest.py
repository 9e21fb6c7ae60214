import os
import sys

# the benchmark's package ``bench`` lives at the repository's root
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
