"""Procedurally rendered digit-image datasets (real vision data, no egress).

The reference trains on real MNIST pulled from the HF hub
(``p2pfl/examples/mnist.py:173``, ``test/node_test.py:85``). This build
environment has zero network egress, so instead of Gaussian-prototype
synthetic tensors (:mod:`tpfl.learning.dataset.synthetic`) these
generators *render* actual digit glyphs with PIL — random font, size,
rotation, translation, stroke intensity, and pixel noise — producing a
genuine image-classification task with MNIST's shapes and semantics:
translation-variant strokes a linear model cannot trivially separate but
a small CNN/MLP learns to >90%.

The ``TpflDataset.from_huggingface`` path stays the real-MNIST entry
point when egress exists; every hermetic test uses these.

Fonts come from matplotlib's bundled DejaVu TTFs (always present, no
system font dependency). Rendering is deterministic per seed.
"""

from __future__ import annotations

import os
from functools import lru_cache

import numpy as np

from tpfl.learning.dataset.tpfl_dataset import TpflDataset


@lru_cache(maxsize=1)
def _font_paths() -> tuple[str, ...]:
    """Deterministic list of bundled TTF fonts (DejaVu family)."""
    import matplotlib

    ttf_dir = os.path.join(matplotlib.get_data_path(), "fonts", "ttf")
    names = sorted(
        f for f in os.listdir(ttf_dir)
        if f.endswith(".ttf") and f.startswith("DejaVu")
        and "Display" not in f  # Display variants carry no digit glyphs
    )
    if not names:  # pragma: no cover - matplotlib always bundles DejaVu
        raise RuntimeError(f"No DejaVu fonts under {ttf_dir}")
    return tuple(os.path.join(ttf_dir, n) for n in names)


@lru_cache(maxsize=None)  # full key space ~2k small arrays, a few MB
def _glyph(font_path: str, font_size: int, digit: int) -> "np.ndarray":
    """Render one digit glyph tight-cropped on a large canvas (uint8)."""
    from PIL import Image, ImageDraw, ImageFont

    font = ImageFont.truetype(font_path, font_size)
    img = Image.new("L", (font_size * 2, font_size * 2), 0)
    ImageDraw.Draw(img).text(
        (font_size // 2, font_size // 4), str(digit), fill=255, font=font
    )
    arr = np.asarray(img)
    ys, xs = np.nonzero(arr)
    return arr[ys.min() : ys.max() + 1, xs.min() : xs.max() + 1]


def _render_batch(
    n: int, size: int, rng: np.random.Generator, noise: float
) -> tuple[np.ndarray, np.ndarray]:
    """Render ``n`` (size, size) float32 digit images in [0, 1] + labels."""
    from PIL import Image

    fonts = _font_paths()
    y = rng.integers(0, 10, size=n).astype(np.int32)
    font_idx = rng.integers(0, len(fonts), size=n)
    font_sizes = rng.integers(size * 3 // 4, size * 5 // 4 + 1, size=n)
    angles = rng.uniform(-25.0, 25.0, size=n)
    shifts = rng.integers(-size // 8, size // 8 + 1, size=(n, 2))
    intensity = rng.uniform(0.6, 1.0, size=n).astype(np.float32)

    x = np.empty((n, size, size), dtype=np.float32)
    for i in range(n):
        glyph = _glyph(fonts[font_idx[i]], int(font_sizes[i]), int(y[i]))
        im = Image.fromarray(glyph).rotate(
            float(angles[i]), expand=True, resample=Image.BILINEAR
        )
        # Scale the rotated glyph to ~80% of the canvas, paste centered
        # + random shift (MNIST-style: centered-ish, jittered).
        target = max(1, int(size * 0.8))
        scale = target / max(im.size)
        im = im.resize(
            (max(1, int(im.size[0] * scale)), max(1, int(im.size[1] * scale))),
            resample=Image.BILINEAR,
        )
        canvas = Image.new("L", (size, size), 0)
        ox = (size - im.size[0]) // 2 + int(shifts[i, 0])
        oy = (size - im.size[1]) // 2 + int(shifts[i, 1])
        canvas.paste(im, (ox, oy))
        x[i] = np.asarray(canvas, dtype=np.float32) * (intensity[i] / 255.0)

    if noise > 0:
        x += rng.normal(0.0, noise, size=x.shape).astype(np.float32)
    return np.clip(x, 0.0, 1.0), y


def rendered_digits(
    n_train: int = 2000,
    n_test: int = 400,
    seed: int = 0,
    size: int = 28,
    noise: float = 0.08,
) -> TpflDataset:
    """28×28 grayscale rendered digits, 10 classes — the hermetic stand-in
    for real MNIST (reference examples/mnist.py:173)."""
    rng = np.random.default_rng(seed)
    x_tr, y_tr = _render_batch(n_train, size, rng, noise)
    x_te, y_te = _render_batch(n_test, size, rng, noise)
    return TpflDataset.from_arrays(x_tr, y_tr, x_te, y_te)


def rendered_color_digits(
    n_train: int = 2000,
    n_test: int = 400,
    seed: int = 0,
    size: int = 32,
    noise: float = 0.08,
) -> TpflDataset:
    """32×32×3 rendered digits on colored backgrounds — CIFAR-shaped
    image data for the CNN/ResNet benchmarks (BASELINE configs 2–3)."""
    rng = np.random.default_rng(seed)

    def colorize(x_gray: np.ndarray) -> np.ndarray:
        n = x_gray.shape[0]
        fg = rng.uniform(0.5, 1.0, size=(n, 1, 1, 3)).astype(np.float32)
        bg = rng.uniform(0.0, 0.4, size=(n, 1, 1, 3)).astype(np.float32)
        g = x_gray[..., None]
        return np.clip(g * fg + (1.0 - g) * bg, 0.0, 1.0)

    x_tr, y_tr = _render_batch(n_train, size, rng, noise)
    x_te, y_te = _render_batch(n_test, size, rng, noise)
    return TpflDataset.from_arrays(
        colorize(x_tr), y_tr, colorize(x_te), y_te
    )
