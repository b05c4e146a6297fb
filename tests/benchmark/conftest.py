"""The benchmark's tests import its code as ``benchmark.*`` from the root of
the checkout and their own helper (``_tiny``) from this directory."""

import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
for _path in (_HERE, os.path.dirname(os.path.dirname(_HERE))):
    if _path not in sys.path:
        sys.path.insert(0, _path)
