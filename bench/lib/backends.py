"""What a traffic shape drives: the program (``ProgramBackend``, through
``Session.collect``) or the plain reference (``ReferenceBackend``), so
that the reference and the control replay the traffic through the same
code as the timed run."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from lib import dsl


class ProgramBackend:
    """The system under test: one ``Session``, one catalog."""

    def __init__(self, session):
        self.session = session
        self.matrices: Dict[str, object] = {}
        self.arrays: Dict[str, jnp.ndarray] = {}

    def load(self, name: str, array) -> None:
        self.arrays[name] = array
        self.matrices[name] = self.session.load(array, name)

    def collect(self, expr):
        """The answer's value, ready on the device."""
        out = dsl.build(expr, self.matrices).collect()
        return out.value.block_until_ready()


class ReferenceBackend:
    """The plain reference (``lib.reference.Reference``) in the
    program's place."""

    def __init__(self, reference):
        self.ref = reference

    @property
    def arrays(self):
        return self.ref.env

    def load(self, name: str, array) -> None:
        self.ref.load(name, array)

    def collect(self, expr):
        return jax.block_until_ready(self.ref.eval(expr))


def load_all(backend, catalog: dict) -> None:
    for name, value in catalog.items():
        backend.load(name, value)
