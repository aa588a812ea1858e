"""The benchmark's own tests, collected with the rest of the suite.

They live in ``bench/tests`` and run there alone as well
(``JAX_PLATFORMS=cpu python -m pytest bench/tests``): the trace reduction
on a trace recorded on the chip, the discovery of a configuration, a mix
and a metric added as files, each configuration's layer layout in its own
module (the same weights and costs as before the move, and a second
layout added as files), the rules for names and units, the refusal of an
unknown device kind, of a run with no chip and of one without the
program, and whole runs on the CPU at a small size: sound, with each
fault planted in the timed path, with the control in the program's
place, and the four-chip cell on four virtual devices.  Each is imported here with ``bench/``, ``bench/tests`` and the
program's ``src/`` on the path, as ``bench/tests/conftest.py`` sets it.
"""
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for _p in (ROOT / "src", ROOT / "bench", ROOT / "bench" / "tests"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from test_control import *  # noqa: E402,F401,F403
from test_faults import *  # noqa: E402,F401,F403
from test_harness import *  # noqa: E402,F401,F403
from test_layout import *  # noqa: E402,F401,F403
from test_multichip import *  # noqa: E402,F401,F403
from test_readers import *  # noqa: E402,F401,F403
from test_spec import *  # noqa: E402,F401,F403
from test_trace import *  # noqa: E402,F401,F403
