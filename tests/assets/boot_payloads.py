"""A rank target whose boot compiles: one program in its constructor, one
in its warm-up hook (``tests/test_boot_trace.py``)."""

import jax
import numpy as np


def _built(x):
    return x * 3 + 1


def _warmed(x):
    return x - 2


class Compiles:
    def __init__(self):
        self.built = np.asarray(jax.jit(_built)(np.ones(4, np.float32)))

    def __kt_warmup__(self):
        self.warmed = np.asarray(jax.jit(_warmed)(np.ones(4, np.float32)))
