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
        ("ssm_scan_8k_x2", 2, 0),
    ],
)
def test_kernel_compiles_for_described_v5e(described, case, kernels, permutes):
    fn, args = rehearsal.cases(described)[case]()
    report = rehearsal.compile_report(case, fn, args)
    assert report["tpu_custom_call"] == kernels, report
    assert report["collective_permute"] >= permutes, report


def test_lm_head_owns_its_loss_in_the_compiled_window(described):
    """A window of GPT-2 small's width and vocabulary (one block, two
    silos) compiled for the described v5e: with the head owning its
    loss, no float32 array of the logits' full ``[.., seq, vocab]``
    extent is materialised, no gather reads one (the label's logit is
    a compare-and-select), and the bias gradient comes out of a matmul
    (on the TPU a ``convolution`` at the root of an output fusion), not
    a reduction of its own."""
    import re

    from tpfl.models.head_loss import _ONES_ROWS

    fn, args = rehearsal.cases(described)["engine_gpt2_head_x2"]()
    text = fn.lower(*args).compile().as_text()
    ops = rehearsal.estimated_operations(text)  # what is materialised
    full_logits = re.compile(r"\[[\d,]*1024,50257\]")
    assert any(full_logits.search(op["shape"]) for op in ops)  # bf16 ones are
    wide = [op for op in ops if re.search(r"f32\[[\d,]*1024,50257\]", op["shape"])]
    assert not wide, wide

    shape_of = {
        m.group(1): m.group(2)
        for m in map(rehearsal.OP_HEAD.match, text.splitlines()) if m
    }
    for line in text.splitlines():
        if " gather(" in line:
            operand = re.search(r" gather\(%?([\w.\-]+)", line).group(1)
            assert not full_logits.search(shape_of.get(operand, "")), line

    bias_grads = [
        op for op in ops if op["shape"] == f"f32[{_ONES_ROWS},2,50257]"
        and op["op_name_tail"].endswith("head_cross_entropy/dot_general")
    ]
    assert len(bias_grads) == 1, [op["shape"] for op in ops][:40]
    line = next(
        ln for ln in text.splitlines()
        if re.match(rf"\s*%{re.escape(bias_grads[0]['name'])} = ", ln)
    )
    called = re.search(r"calls=(%[\w.\-]+)", line).group(1)
    start = text.index(f"\n{called} (")
    assert " convolution(" in text[start:text.index("\n}", start)]
