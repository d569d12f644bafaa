"""Names for the phases of a device step.

The served step programs put each phase under a ``jax.named_scope`` of
these names, so that the compiled program's metadata says which phase an
operation belongs to (observability/device_phases.py reads it back and a
device trace is then read per phase). Scopes are metadata: they change no
arithmetic and no byte of the bitstream.
"""

from __future__ import annotations

import functools

import jax

#: in the order a frame passes them: RGB to YCbCr, 4:2:0 and padding;
#: damage detection; motion estimation and compensation; residual,
#: transform, quantisation and reconstruction; entropy coding and packing
PHASES = ("colour", "damage", "motion", "transform", "entropy")


def phase(name: str):
    """Decorator: everything the function traces lies in the scope."""
    assert name in PHASES, name

    def deco(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return scoped
    return deco
