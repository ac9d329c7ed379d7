import os
import sys

# Make `import repro` work regardless of PYTHONPATH (tests are documented to
# run as `PYTHONPATH=src pytest tests/`, this is belt-and-braces).
_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

# Smoke tests and benches must see exactly ONE device; a test that needs a
# multi-device CPU mesh sets XLA_FLAGS in a process of its own.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
