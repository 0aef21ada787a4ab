"""Compile the main paths' Pallas kernels for a DESCRIBED TPU v5e: what
the files tests/test_tpu_compile_*.py share, one file a kernel family
(flash, paged, rotary, experts, streams) so that `--dist loadfile` can
give each a worker. As tests/op_test.py is: a module the files import,
no test of its own.

No chip is attached here: the TPU compiler that ships with jaxlib
compiles for a topology that is described, and raises what the chip's
compiler would raise (block shapes off the (8, 128) tiling, scoped
VMEM overrun, ...) — the class of fault interpret mode cannot see.
``paged_attention``'s Pallas path passed every interpret-mode test
while being refused by the compiler at every serving shape; these
compiles are what keeps that from recurring. Nothing runs, so this says
nothing about results or times (chip_smoke.py does, on the chip).
Describing the topology compiles nothing (0.1 s a file, my CPU run, PR
44), so each file makes its own.
"""

import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402


@pytest.fixture(scope="module")
def topo():
    """A described v5e host of four chips. The persistent compile
    cache is off around the module: a compile for a described chip is
    written to it but cannot be read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip("cannot describe a v5e topology: %r" % (e,))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """One of its chips as a sharding."""
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *avals):
    return jax.jit(fn).lower(*avals).compile().as_text()
