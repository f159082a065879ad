# NOTE: no XLA_FLAGS here on purpose — smoke tests must see the real single CPU
# device. Multi-device tests spawn subprocesses that set
# --xla_force_host_platform_device_count themselves (tests/test_distributed.py).
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.dirname(__file__))  # for `from _propcheck import ...`


def make_test_mesh(shape, axes):
    """The one way tests build a mesh — through repro.compat (all-auto axes).

    Subprocess snippets (tests/test_distributed.py) can't import conftest;
    they use `from repro.compat import make_mesh` directly, which this wraps.
    """
    from repro.compat import make_mesh

    return make_mesh(shape, axes)
