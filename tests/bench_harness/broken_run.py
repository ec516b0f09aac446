"""A run of the benchmark with one child replaced by a broken stand-in:

    python broken_run.py <role>=<module> <arguments of benchmarks.run>

``<role>`` is a key of ``benchmarks.process.CHILDREN``; ``<module>`` lies
beside this file. The harness itself carries no switch that breaks it:
the tests that must see ``correct`` come out false come in through here.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmarks import process, run   # noqa: E402

role, module = sys.argv[1].split("=")
process.CHILDREN[role] = module
os.environ["PYTHONPATH"] = HERE + os.pathsep + os.environ.get(
    "PYTHONPATH", "")
sys.exit(run.main(sys.argv[2:]))
