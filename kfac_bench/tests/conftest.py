"""The benchmark's own tests run on the CPU at toy size:

    JAX_PLATFORMS=cpu python3 -m pytest kfac_bench/tests -q

They are not part of the repo's tier-1 suite (``tests/``)."""

import os
import sys

os.environ.setdefault('KFAC_COMPILE_CACHE', '0')
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
TOY_SPEC = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        'toy_benchmark.json')
TOY = 'toy_f1i4'
