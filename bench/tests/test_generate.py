"""The generator gives the inputs it gave before the model code moved into
``bench/families/``: a digest of every array of the tiny
``silo8.cc_power`` cell's inputs at a fixed seed, first computed when
``bench/generate.py`` still made the ResNet-18-GN data and weights
itself."""
import hashlib

import jax
import numpy as np

from bench import generate
from bench.cells import family
from bench.tests.tiny import SEED, tiny

DIGEST = "5ed4e4aa5c0b1063641095d2c68019680797eb3134e379bb992bd8a2456a76de"
FIELDS = ("x", "y", "sizes", "x_test", "y_test", "params", "key",
          "selection", "training", "budgets")


def digest(inputs: generate.Inputs) -> str:
    h = hashlib.sha256()
    for name in FIELDS:
        for leaf in jax.tree.leaves(getattr(inputs, name)):
            a = np.asarray(leaf)
            h.update(name.encode())
            h.update(str(a.dtype).encode())
            h.update(str(a.shape).encode())
            h.update(a.tobytes())
    return h.hexdigest()


def test_tiny_cc_power_inputs_are_unchanged():
    c = tiny("silo8.cc_power")
    assert digest(generate.make_inputs(family(c), c.config, c.traffic,
                                       SEED)) == DIGEST
