import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

# the privacy plane (shares mod ~2^40) needs x64, as in the program's tests
jax.config.update("jax_enable_x64", True)
