"""The main path's Pallas kernels, compiled for a DESCRIBED v5e.

Interpret-mode tests cannot see what the chip's compiler refuses — a
slice off the (8, 128) tiling, too much VMEM, a kernel that cannot be
partitioned. The TPU compiler is installed in the sandbox and compiles
for a chip that is described, not attached (on-chip-measurement guide
§2.3), so these cases guard every later PR at no chip time. Nothing
executes: a pass here is not a chip run. The cases are
``tools/chip_rehearsal.py``'s cheap ones (about two seconds each); its
20-second engine windows stay in that script.
"""

import jax
import pytest

from tools import chip_rehearsal as rehearsal
from tpfl.parallel import compat


@pytest.fixture(scope="module")
def described():
    """The described devices, with the kernels steered onto their TPU
    branch and the persistent cache off (a described-topology
    executable can be written to it but not read back without a chip —
    the next compile would warn and recompile)."""
    try:
        devices = rehearsal.described_devices()
    except Exception as e:  # no libtpu / no topology support here
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    from jax.experimental.compilation_cache import compilation_cache

    cache_was_on = jax.config.jax_enable_compilation_cache
    real_on_tpu = compat.on_tpu
    rehearsal.no_persistent_cache()
    rehearsal.force_chip_branch()
    try:
        yield devices
    finally:
        compat.on_tpu = real_on_tpu
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


@pytest.mark.parametrize(
    "case, kernels, permutes",
    [
        ("flash_8k", 3, 0),
        ("flash_4k_h16_d128", 3, 0),
        ("ring_flash_sp4", 6, 1),
        ("node_conv_c32", 2, 0),
        ("node_conv_c32_vmapped", 2, 0),
    ],
)
def test_kernel_compiles_for_described_v5e(described, case, kernels, permutes):
    fn, args = rehearsal.cases(described)[case]()
    report = rehearsal.compile_report(case, fn, args)
    assert report["tpu_custom_call"] == kernels, report
    assert report["collective_permute"] >= permutes, report


def test_narrow_stem_takes_the_xla_backward(described):
    """Cin=3 would pad 42x on the lane axis (the chip's compiler refused
    the 100-node CNN round program for it): node_conv routes it through
    the forward-style XLA backward — no kernel call in the program."""
    fn, args = rehearsal.cases(described)["node_conv_c3_fallback"]()
    report = rehearsal.compile_report("node_conv_c3_fallback", fn, args)
    assert report["tpu_custom_call"] == 0, report
