"""Where a Pallas kernel runs: compiled by Mosaic when the program is lowered
for a TPU, run by the Pallas interpreter when it is lowered for anything else.

This is the one place that choice is made. Every kernel in the repo builds
its call through `pallas_call` below; no op or kernel signature carries an
`interpret` flag. The choice follows the platform the program is LOWERED for
(`jax.lax.platform_dependent`), not `jax.default_backend()` at trace time:
a jitted op compiled for a described v5e from a CPU-only host takes the
Mosaic branch (tests/test_tpu_compile.py relies on this), and the same op
executed on the CPU takes the interpreter's. On a TPU nothing falls back to
the interpreter or to a kernel's `ref.py`.

The Mosaic branch asks for `tiles.VMEM_LIMIT_BYTES` of scoped VMEM (the
v5e default of 16 MiB is too small for the full-map conv tiles of VGG's
first stages); `ConvLaunch.vmem_bytes` / `BsrLaunch.vmem_bytes` model what a
launch needs against that limit, and the static checker (RPA103) holds every
planned launch to it.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiles


def pallas_call(kernel, **kwargs):
    """`pl.pallas_call(kernel, **kwargs)`, compiled for TPU and interpreted
    on every other platform. The VMEM limit is read when the call is built,
    so a test can tighten it to a launch's modeled need."""
    compiled = pl.pallas_call(
        kernel,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=tiles.VMEM_LIMIT_BYTES),
        **kwargs)
    interpreted = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, tpu=compiled,
                                          default=interpreted)

    return call
