"""What the parallel layer asks of the one installation it runs on.

- :func:`shard_map` — ``jax.shard_map`` under the name every call site
  in tpfl (and ``__graft_entry__``) already imports.
- :func:`on_tpu` / :func:`pallas_interpret` — the ONE place the Pallas
  entry points (flash, ring, conv) decide between the compiled kernel
  and the interpret-mode emulator. The emulator exists for CPU tests; a
  run that was meant for the chip must never land on it silently, so
  the automatic choice logs once at WARNING when it picks the emulator,
  and chip paths pass ``interpret=False`` explicitly.
"""

from __future__ import annotations

from typing import Optional

import jax

shard_map = jax.shard_map

# unguarded: a racy double-read logs the warning twice at worst.
_warned_emulator = False


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    return jax.default_backend() == "tpu"


def pallas_interpret(interpret: Optional[bool]) -> bool:
    """Resolve a Pallas entry point's ``interpret`` argument: an
    explicit True/False wins; None picks the compiled kernel on a TPU
    and the emulator elsewhere, saying so once."""
    global _warned_emulator
    if interpret is not None:
        return bool(interpret)
    if on_tpu():
        return False
    if not _warned_emulator:
        _warned_emulator = True
        from tpfl.management.logger import logger

        logger.warning(
            "_kernels",
            "Pallas kernels run in INTERPRET mode (emulator): the default "
            f"backend is {jax.default_backend()!r}, not a TPU. Correct, "
            "but orders of magnitude slower — never a device measurement.",
        )
    return True
