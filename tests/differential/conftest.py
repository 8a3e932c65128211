"""Make the shared test helpers (tests/_engine_builders.py) importable
from this sub-package the same way the top-level tests import them."""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
