import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parents[1] / 'src')]

import jax  # noqa: E402

# the tests compile small CPU programs: nothing for a persistent cache to keep
jax.config.update('jax_enable_compilation_cache', False)
