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

import re

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
        # One forward kernel and ONE backward sweep a call (PR 30; the
        # two-kernel backward before it made these 3, 3 and 6).
        ("flash_8k", 2, 0),
        ("flash_4k_h16_d128", 2, 0),
        # The ring's causal step has two call sites a side (the diagonal
        # pair masked, the pairs behind it not).
        ("ring_flash_sp4", 4, 1),
        ("ssm_scan_8k_x2", 2, 0),
        # blockwise_attention's TPU branch at the benchmark's shapes,
        # under the engine's vmap: GPT-2 (two 64-wide heads a lane tile,
        # two blocks) and SambaY (grouped rows, a value width of its own,
        # sixteen blocks, 8 MB of float32 dq resident in VMEM). Grouped
        # heads of whole lane tiles bring a third, small kernel: delta
        # summed from dO and O where they lie (PR 37).
        ("block_attention_gpt2_x4", 2, 0),
        ("block_attention_sambay_x2", 3, 0),
        # Mellum 2's full layer (8 query heads a key head, block 256: a
        # [256, 2048] tile, 64 MiB of dq) takes the kernels; its experts'
        # six grouped products a step are the megablox kernels.
        ("block_attention_mellum_x2", 3, 0),
        ("grouped_products_mellum_x2", 6, 0),
        # ... and one whole banded block under ``nn.remat``: the forward
        # kernel twice (once recomputed), delta and one backward sweep,
        # 3 x 5 grouped products, the way back to tokens three times.
        ("mellum_block_x2", 22, 0),
        # The way back from that head's rows to the tokens, and from the
        # buffer's rest (PR 35): one kernel over 64 token tiles of 512, a
        # tile's runs copied block by block.
        ("rows_to_tokens_mellum_x2", 1, 0),
        ("rows_to_tokens_mellum_rest_x2", 1, 0),
        # The band inside the kernels (PR 33), the block left to
        # ``blockwise_attention``: Mellum 2's window of 1024 at block 256
        # (a sweep of five steps, dq resident as in the full layer) and
        # SambaY's sliding window of 512 at block 512 (two steps).
        ("block_attention_mellum_band_x2", 3, 0),
        ("block_attention_sambay_band_x2", 3, 0),
        # ZAYA1-8B's attention inside the latent (2 key heads x 4 query
        # heads of 128, 8192 keys: block 512 exactly at ``_TILE_BYTES``,
        # 32 MiB of dq) takes the kernels as they are; its experts' six
        # grouped products at 2048 x 4096 (tiles of 1024, the weight
        # gradient's cut to 1024 x 512 to fit VMEM); and ONE whole block
        # — the convolutions, the router MLP and its state, the head of
        # the row buffer and its rest: 3 attention kernels + 2 x 7
        # grouped products (its way back to tokens stays the gather: one
        # choice a token, ``moe._RUN_SLOTS``).
        ("block_attention_zaya_x2", 3, 0),
        ("grouped_products_zaya_x2", 6, 0),
        ("zaya_block_x2", 17, 0),
        # The VMEM guard's edges (``flash_kernel.tiles``): the tallest
        # tile with the longest resident dq, bf16 and float32 gradients.
        ("flash_64k_d128", 2, 0),
        ("flash_40k_d128_f32", 2, 0),
    ],
)
def test_kernel_compiles_for_described_v5e(described, case, kernels, permutes):
    text = _compiled_text(described, case)
    assert text.count("tpu_custom_call") == kernels
    assert len(re.findall(r" collective-permute(-start)?\(", text)) >= permutes


_COMPILED: dict = {}


def _compiled_text(described, case: str) -> str:
    """The text of a rehearsal case compiled for the described v5e, once
    a session (a whole block takes 20 seconds)."""
    if case not in _COMPILED:
        fn, args = rehearsal.cases(described)[case]()
        _COMPILED[case] = fn.lower(*args).compile().as_text()
    return _COMPILED[case]


@pytest.mark.parametrize(
    "q_heads, kv_shape, d_v, seq, block",
    [
        # Equal heads and pairs of heads keep 512 ...
        pytest.param(12, (4, 1024, 12, 64), 64, 1024, 512, id="gpt2"),
        pytest.param(40, (1, 8192, 20, 64), 128, 8192, 512, id="sambay"),
        pytest.param(40, (1, 1024, 20, 64), 128, 1024, 512, id="sambay_check"),
        # ... 8 query heads a key head of 128 get 256 (a 512-block's
        # float32 tile would be 8 MB), in a banded layer as in a full one.
        pytest.param(32, (2, 8192, 4, 128), 128, 8192, 256, id="mellum"),
        pytest.param(32, (2, 2048, 4, 128), 128, 2048, 256, id="mellum_check"),
        # ... and 4 query heads a key head of 128 keep 512 (a 4 MiB tile).
        pytest.param(8, (2, 8192, 2, 128), 128, 8192, 512, id="zaya"),
        pytest.param(8, (1, 2048, 2, 128), 128, 2048, 512, id="zaya_check"),
        # A short sequence is one block; one the kernels cannot tile too.
        pytest.param(12, (4, 256, 12, 64), 64, 256, 256, id="short"),
        pytest.param(12, (4, 100, 12, 64), 64, 100, 100, id="unaligned"),
    ],
)
def test_default_block_is_the_largest_the_kernels_admit(
    q_heads, kv_shape, d_v, seq, block
):
    """``blockwise_attention``'s block when the caller names none: a
    function of shapes alone (no backend, no model's name), checked at
    the cells' calls — and the block it gives is one the kernels take."""
    import jax.numpy as jnp

    from tpfl.parallel import flash_kernel
    from tpfl.parallel.ring_attention import _default_block

    k = jax.ShapeDtypeStruct(kv_shape, jnp.bfloat16)
    v = jax.ShapeDtypeStruct((*kv_shape[:3], d_v), jnp.bfloat16)
    groups = q_heads // kv_shape[2]
    assert _default_block(seq, k, v, groups) == block
    if seq % 128 == 0:
        assert flash_kernel.tiles(kv_shape, d_v, block, groups)


@pytest.fixture(scope="module")
def gpt2_window_text(described):
    """The compiled text of a window of GPT-2 small's width, heads,
    context and vocabulary (one block, two silos)."""
    return _compiled_text(described, "engine_gpt2_head_x2")


def test_attention_kernels_are_in_the_compiled_window(gpt2_window_text):
    """On a TPU the zoo block's default attention is the Pallas kernels:
    the compiled window calls one forward and one backward kernel by
    name, and no ``while`` of the XLA block loop is left under the
    scope."""
    calls = [
        line for line in gpt2_window_text.splitlines()
        if "tpu_custom_call" in line and " custom-call(" in line
    ]
    # A kernel's instruction carries the kernel's name: this is the name
    # a device trace shows (``attention_kernel_share_pct`` reads it).
    names = sorted(
        re.match(r"\s*%?([A-Za-z_]+)", line).group(1).rstrip("_") for line in calls
    )
    assert names == ["block_attention_backward", "block_attention_forward"], calls
    loops = [
        line for line in gpt2_window_text.splitlines()
        if " while(" in line and "block_attention" in line
    ]
    assert not loops, loops


def test_attention_operands_are_not_copied_between_layouts(gpt2_window_text):
    """The kernels read ``[.., heads * d]``. The zoo block splits the
    joint projection FIRST and names the heads after, so the kernels'
    reshape back folds away: no bf16 ``[.., 768]`` copy of q, k, v or
    their gradients is materialised under the scope (a reshape between
    ``[.., 12, 64]`` and ``[.., 768]`` is a copy on a TPU, whose tiles
    span the last two dimensions: six a layer before, 4.3 ms a round of
    GPT-2's cell, PERF.md PR 30)."""
    copies = [
        op for op in rehearsal.estimated_operations(gpt2_window_text)
        if op["name"].startswith("copy") and "block_attention" in op["op_name_tail"]
        and op["shape"].startswith("bf16[") and op["shape"].endswith(",768]")
    ]
    assert not copies, copies


def _grouped_row_operations(text: str) -> list:
    """The operations of a compiled program that exist only to lay a key
    head's query heads side by side as ROWS (PERF.md §6, PR 37): the
    transposes of ``blockwise_attention``'s old grouped-row layout —
    operations named ``.../attention/transpose``, the attention module's
    own (the scalars' ``.../block_attention/transpose`` of delta, a
    ``[B, S, Hq]`` float32, stays) — and copies of the 7-dimensional
    ``[.., blocks, groups, block, heads, d]`` arrays they made of q,
    out, dO and dq."""
    return [
        op for op in rehearsal.estimated_operations(text)
        if op["op_name_tail"].endswith("/attention/transpose")
        or (op["name"].startswith("copy") and op["shape"].count(",") == 6)
    ]


@pytest.mark.parametrize("case", ["mellum_block_x2", "zaya_block_x2"])
def test_grouped_query_heads_are_not_transposed_around_the_kernels(described, case):
    """The kernels read a key head's query heads as LANES of ``[B, S, Hq
    * D]``, where the projection and rotary left them, and write out and
    dq there: in one whole block at published widths (Mellum 2's banded
    layer, 8 query heads a key head, forward and backward under
    ``nn.remat``; ZAYA1's, 4 a key head) no operation transposes q, out,
    dO or dq into grouped rows and none copies their 7-dimensional
    ``[.., 8, 256, 4, 128]`` / ``[.., 256, 4, 8, 128]`` form. Before PR
    37 a round of the Mellum 2 cell held 28 + 4 such operations (ZAYA1's
    20 + 5), 87 ms of ``copy`` and ``reshape`` a round on the chip."""
    text = _compiled_text(described, case)
    assert "block_attention_backward" in text  # the kernels are there
    assert not _grouped_row_operations(text)


def test_lm_head_owns_its_loss_in_the_compiled_window(described, gpt2_window_text):
    """A window of GPT-2 small's width and vocabulary (one block, two
    silos) compiled for the described v5e: with the head owning its
    loss, no float32 array of the logits' full ``[.., seq, vocab]``
    extent is materialised, no gather reads one (the label's logit is
    a compare-and-select), and the bias gradient comes out of a matmul
    (on the TPU a ``convolution`` at the root of an output fusion), not
    a reduction of its own."""
    from tpfl.models.head_loss import _ONES_ROWS

    text = gpt2_window_text
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
