"""The benchmark's tests of the serving engine's own spans
(``bench/tests/test_program_spans.py``), collected with the rest of the
suite: the reduction of a profile by the engine's ``repro.*``
annotations, on a synthetic profile and on a trace recorded on the chip,
and a small serve cell run with the engine's tracer on.  Imported here
with ``bench/``, ``bench/tests`` and the program's ``src/`` on the path,
as ``bench/tests/conftest.py`` sets it.
"""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT / "bench", ROOT / "bench" / "tests"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from test_program_spans import *  # noqa: E402,F401,F403
