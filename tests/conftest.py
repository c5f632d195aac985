"""Offline test bootstrap: when the real `hypothesis` package is not
installed (this container has no network), register the deterministic
shim from `_hypothesis_stub.py` under its name before test modules
import it. With the real package, load a profile without the per-example
deadline: a property test's first example pays a jit compile, which runs
for seconds, not the default 200 ms."""
import sys
from pathlib import Path

try:
    import hypothesis
except ModuleNotFoundError:
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    import _hypothesis_stub

    _hypothesis_stub.install()
else:
    hypothesis.settings.register_profile("repro", deadline=None)
    hypothesis.settings.load_profile("repro")
