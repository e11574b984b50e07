"""Compile-only TPU topology access for deviceless Mosaic AOT checks.

The image's local libtpu can build a compile-only PJRT client for an
abstract v5e topology — `jax.jit(...).lower(...).compile()` against its
devices runs the real Mosaic/XLA TPU compiler with no chip attached (see
dev_scripts/mosaic_aot_check.py and docs/KERNEL.md §Verification).

Only one process at a time may load libtpu, and it keeps the library
until it exits. A second one fails with an error naming libtpu's lock
file; that file is never removed here — on the machine with the chip it
is what keeps two processes off one chip.
"""

from __future__ import annotations


def v5e_topology(name: str = "v5e:2x2"):
    """Topology description for an abstract v5e slice."""
    from jax.experimental import topologies

    return topologies.get_topology_desc(topology_name=name, platform="tpu")
