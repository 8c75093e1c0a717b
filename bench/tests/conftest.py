import os
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]
# CPU rehearsals: no compile cache, so no CPU executables land in the
# cache the chip runs use
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
